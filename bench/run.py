"""Run one cell of the benchmark once, on the accelerator this process sees.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its files are found by name
(``spec.py``).  One process holds the chip: it serves the model over HTTP
on threads of its own and drives it from a JAX-free load generator in a
child process (``harness.py``).  It exits non-zero, printing no result,
when JAX finds no TPU, fewer chips than the cell asks for, or a device
kind with no published peaks.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and traced
seconds and a breakdown of device time and idle gaps.  Every run checks
the served tokens against the configuration's plain reference
(``correct.py``).  The last lines of stderr give each compared number
beside its limit; the last line of stdout is the result::

    {"correct": true, "attempted": 400, "failed": 0,
     "metrics": {"itl_p95_ms": {"value": 21.3, "unit": "ms"}, ...},
     "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 13958643712},
     "checks": {"logit_gap": {"value": 0.01, "limit": 0.1}, ...}}
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run.py: no TPU found (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1
    from peaks import peaks
    peaks(devs[0].device_kind)          # an unknown device is an error

    import harness
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    print(f"compiles inside the window: {res.pop('compiles_in_window')}")
    print(f"reference sample: {json.dumps(res.pop('sample'))}")
    print(f"request statuses: {json.dumps(res.pop('statuses'))}")
    print(f"recorded dispatches: {json.dumps(res.pop('dispatches'))}")
    missing = res.pop("missing_kernels")
    if missing:
        print(f"kernels missing from the compiled programs: {missing}")
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
