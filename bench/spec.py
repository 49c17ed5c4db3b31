"""Find a cell's files by name: workload, configuration, traffic mix,
program adapter, reference, metrics.

Nothing here imports JAX, so the load generator can use it too.  Every
piece of a cell is a file of its own, found by the name that
``BENCHMARK.json`` or another file gives it:

* ``bench/workloads/<cell>.json``   config and traffic names, clients,
  engine sizes, the check's limit;
* ``bench/configs/<config>.json``   the model as it is run: its published
  keys (``vocab_size`` among them), and a ``bench`` block with its
  weight format, the name ``<r>`` of its architecture, the precisions of
  the reference and of the control, the kernels it must compile;
* ``bench/programs/<r>.py``         the program adapter of architecture
  ``<r>`` (the one place where a configuration's own keys meet the
  program): ``PREFIX_CACHE``, whether the engine must come up with a
  prefix cache; ``model_config(cfg)``, the program's ``ModelConfig``;
  ``make_params(model, cfg, seed)``, the reference's weight draws in the
  program's layout, in one jitted call; ``query_heads(cfg)``, the query
  heads of a paged attention call; ``token_ops(cfg, ctx, head)`` and
  ``prefill_ops(cfg, lo, n, head)``, the logical work of a decoded and of
  prefilled tokens (``work.py`` says how work is counted);
* ``bench/references/<r>.py``       the plain reference of ``<r>``: a
  ``Reference(cfg, seed)`` with ``hidden(tokens, pos, seg, mode)`` and
  ``head()`` (``correct.py``), and the weight draws its adapter hands to
  the program;
* ``bench/traffic/<mix>.json``      parameters of the one generator in
  ``traffic.py``;
* ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``
  the readers, each with a ``read(ctx)`` function.

A metric entry without a ``workloads`` list applies to every cell; only
the kernels' rooflines list theirs, since a kernel is read only where it
runs.  A later cell, mix, metric or architecture is a new file and an
entry in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    name: str
    workload: dict        # bench/workloads/<name>.json
    config: dict          # bench/configs/<workload["config"]>.json
    traffic: dict         # bench/traffic/<workload["traffic"]>.json
    chips: int
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str = ROOT      # the checkout its files were found in


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with every file it names; KeyError if
    ``BENCHMARK.json`` has no such workload."""
    bench = benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    bdir = os.path.join(root, "bench")
    wl = _json(os.path.join(bdir, "workloads", f"{name}.json"))
    if (wl["config"], wl["traffic"]) != (entry["config"], entry["traffic"]):
        raise ValueError(f"{name}: workload file and BENCHMARK.json name "
                         "different config or traffic")
    return Cell(
        name=name, workload=wl,
        config=_json(os.path.join(bdir, "configs", f"{wl['config']}.json")),
        traffic=_json(os.path.join(bdir, "traffic", f"{wl['traffic']}.json")),
        chips=int(entry["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def reader(kind: str, name: str, root: str = ROOT):
    """The module ``bench/<kind>/<name>.py``: a metric's reader (its
    ``read(ctx)``), or an architecture's program adapter or reference."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
