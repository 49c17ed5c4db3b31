"""Cells, configurations, architectures, mixes and metrics are found by
name: a workload, or a whole architecture (program adapter and
reference), dropped into ``bench/`` with an entry in ``BENCHMARK.json``
runs with no edit to any file of the harness."""

import json
import os
import shutil
import time

import pytest

import spec
from tiny import ENGINE, MIX, tiny_config

ROOT = spec.ROOT
ROOFLINES = {"ent_matmul_roofline", "paged_attn_roofline"}
GENERIC = {"step_mfu", "mfu", "device_idle_share", "tick_ms",
           "admit_stall_ms", "host_tick_p95_ms", "table_updates_per_tick",
           "emit_lag_p95_ms"}


def test_every_cell_loads():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end:
            assert callable(spec.reader("end_to_end", m["name"]).read)


def test_layer_readers_declare_what_benchmark_says():
    for m in spec.benchmark()["per_layer"]:
        mod = spec.reader("layer_metrics", m["name"])
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"]), m["name"]


def test_only_kernel_rooflines_name_their_cells():
    bench = spec.benchmark()
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "workloads" in m}
    assert listed == ROOFLINES


def _checkout(tmp_path) -> tuple:
    """A copy of ``bench/`` and ``BENCHMARK.json`` to add files to."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    return root, spec.benchmark()


def _add_cell(root, bench, name, config, traffic, workload, listed=()):
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "a cell added by a file and an entry"})
    for m in bench["per_layer"]:
        if m["name"] in listed:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(dict(workload, config=config, traffic=traffic)))


@pytest.mark.parametrize("listed", [(), ("paged_attn_roofline",)])
def test_new_workload_file_is_found(tmp_path, listed):
    root, bench = _checkout(tmp_path)
    _add_cell(root, bench, "minicpm-2b-bf16.decode", "minicpm-2b-bf16",
              "decode", {"clients": 8,
                         "engine": {"slots": 8, "max_len": 2048,
                                    "page_size": 16, "prefill_chunk": 256},
                         "correct": {"logit_gap": 1.0}}, listed)
    cell = spec.load_cell("minicpm-2b-bf16.decode", root=str(root))
    assert cell.root == str(root)
    assert cell.config["hidden_size"] == 2304
    assert cell.traffic["output"]["median"] == 300
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == GENERIC | set(listed)
    # the cell that lists the rooflines keeps them
    old = spec.load_cell("qwen2.5-3b-ent.decode", root=str(root))
    assert {m["name"] for m in old.per_layer} == GENERIC | ROOFLINES
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root=str(root))


def test_new_architecture_is_files_alone(tmp_path, short):
    """A copy of the dense adapter and reference under a name of its own,
    a configuration naming it, a mix and a cell: the cell loads, builds
    through its own adapter and runs whole at toy size on the CPU."""
    import harness
    import system
    root, bench = _checkout(tmp_path)
    b = root / "bench"
    shutil.copy(b / "programs" / "dense.py", b / "programs" / "toy.py")
    shutil.copy(b / "references" / "dense.py", b / "references" / "toy.py")
    cfg = dict(tiny_config("qwen2.5-3b-ent"), name="toy")
    cfg["bench"]["reference"] = "toy"
    (b / "configs" / "toy.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    _add_cell(root, bench, "toy.tiny", "toy", "tiny",
              {"clients": 4, "engine": ENGINE,
               "correct": {"logit_gap": 0.05}})
    cell = spec.load_cell("toy.tiny", root=str(root))
    prog = system.program(cell)
    assert prog.__file__ == str(b / "programs" / "toy.py")
    assert prog.ref.__file__ == str(b / "references" / "toy.py")
    res = harness.run_cell(cell, 2**33 + 5, 2.0, False,
                           t_start=time.monotonic())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert res["sample"]["served_tokens"] > 0
