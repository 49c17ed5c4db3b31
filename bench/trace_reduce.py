"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

One pass over the planes that ``jax.profiler.ProfileData`` reads:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.
  An op's event name is its HLO instruction, ``%name = shape op(operand
  shapes ...)``, so a Pallas kernel is found by its name
  (``ent_matmul_packed_fused``, ``paged_attention``) and its shapes are
  read from the same text;
* host spans: the harness's own ``bench.*`` annotations on the host
  plane, which say what the engine thread was doing;
* the clock skew between the two: the device clock of a TPU trace runs
  behind the host's by about a millisecond.  Each program run ends on
  the device before the host's ``CompleteCallbacks`` event of the same
  ``run_id`` starts, so the least such distance bounds the skew, and
  device times are shifted by it.

The window is the ``bench.window`` span.  Busy time is the union of the
device ops' intervals inside it, averaged over the chips traced.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
_NAME = re.compile(r"^%([A-Za-z0-9_\-]+?)(?:\.\d+)? = ")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")
# control flow spans the ops it runs: counted in busy time, never as an op
_CONTROL = ("while", "conditional", "call")


@dataclass
class OpCall:
    """One device op event: its name, device seconds and HLO shapes."""
    name: str                  # instruction name without its ".N" suffix
    instr: str                 # full instruction name ("fusion.12")
    seconds: float
    shapes: list               # [(dtype, dims)]: result first, then operands


@dataclass
class Reduction:
    window_s: float
    busy_s: float              # averaged over the chips traced
    chips: int
    skew_s: float
    ops: list = field(default_factory=list)          # [OpCall]
    idle_gaps: list = field(default_factory=list)    # [(label, seconds)]

    def calls(self, kernel: str) -> list:
        return [c for c in self.ops if c.name == kernel]

    def device_ops(self, n: int = 10) -> list:
        """The n ops with the most device time: [[instruction, seconds]]."""
        tot = defaultdict(float)
        for c in self.ops:   # anonymous fusions apart, the rest by name
            tot[c.instr if c.name == "fusion" else c.name] += c.seconds
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]


def parse_shapes(text: str) -> list:
    """[(dtype, dims)] of an HLO instruction's text, result first."""
    head, _, _ = text.partition(", custom_call_target")
    head, _, _ = head.partition(", kind=")
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(head)]


def _union(intervals: list) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce_trace(path: str, max_gaps: int = 10) -> Reduction:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    host_spans, callbacks, window = [], {}, None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith("bench."):
                    host_spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name))
                elif e.name == "CompleteCallbacks":
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        callbacks[rid] = min(callbacks.get(rid, e.start_ns),
                                             e.start_ns)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")

    devices = [p for p in planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    skews = []
    for plane in devices:
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                rid = dict(e.stats).get("run_id")
                if rid in callbacks:
                    skews.append(callbacks[rid] - (e.start_ns + e.duration_ns))
    skew = min(skews) if skews else 0.0

    lo, hi = window
    busy, ops, intervals = 0.0, [], []
    for plane in devices:
        spans = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s = e.start_ns + skew
                t = s + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                m = _NAME.match(e.name)
                name = m.group(1) if m else e.name
                if name not in _CONTROL:
                    instr = e.name[1:e.name.find(" = ")] if m else e.name
                    ops.append(OpCall(name, instr, e.duration_ns * 1e-9,
                                      parse_shapes(e.name)))
                spans.append((max(s, lo), min(t, hi)))
        busy += _union(spans)
        intervals.extend(spans)

    gaps, end = [], lo
    for s, t in sorted(intervals) + [(hi, hi)]:
        if s > end:
            gaps.append((end, s))
        end = max(end, t)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:max_gaps]:
        mid = (a + b) / 2
        open_ = [sp for sp in host_spans if sp[0] <= mid <= sp[1]]
        label = (min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_
                 else "no bench span open")
        idle.append((label, (b - a) * 1e-9))
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=busy * 1e-9 / len(devices), chips=len(devices),
                     skew_s=skew * 1e-9, ops=ops, idle_gaps=idle)
