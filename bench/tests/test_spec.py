"""Cells, configurations, mixes and metrics are found by name: a workload
dropped into ``bench/workloads/`` with an entry in ``BENCHMARK.json``
runs with no edit to any file of the harness."""

import json
import os
import shutil

import pytest

import spec

ROOT = spec.ROOT


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


def test_new_workload_file_is_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = spec.benchmark()
    bench["workloads"].append({"name": "minicpm-2b-bf16.decode",
                               "config": "minicpm-2b-bf16",
                               "traffic": "decode", "chips": 1,
                               "why": "a cell added by a file and an entry"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "workloads" / "minicpm-2b-bf16.decode.json").write_text(
        json.dumps({"config": "minicpm-2b-bf16", "traffic": "decode",
                    "clients": 8,
                    "engine": {"slots": 8, "max_len": 2048, "page_size": 16,
                               "prefill_chunk": 256},
                    "correct": {"logit_gap": 1.0}}))
    cell = spec.load_cell("minicpm-2b-bf16.decode", root=str(root))
    assert cell.config["hidden_size"] == 2304
    assert cell.traffic["output"]["median"] == 300
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", root=str(root))
