"""BENCHMARK.json against the files it names and the rules it must keep."""
from __future__ import annotations

import json
import re

import jax
import pytest

from bench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (harness.ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + METRICS,
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for text in (entry.get("why"), entry.get("layer"), entry.get("source")):
        if text is not None:
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("workload", CELLS)
def test_cell_files_resolve(workload):
    cell = harness.resolve(workload)
    assert (harness.BENCH / "cells" / f"{cell.traffic['entry']}.py").is_file()
    assert cell.reference.param_count(cell.config) == cell.config["trainable_params"]
    assert {"loss_gap", "change_gap"} <= set(cell.limits) <= {
        "loss_gap", "update1_gap", "change_gap", "resid_gap"}
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    cells = metric.get("workloads", CELLS)
    for workload in cells:
        assert workload in CELLS
        reported = {m["name"] for m in harness.resolve(workload).end_to_end}
        assert metric["moves"] in reported, (metric["name"], workload)


def test_metrics_sources_and_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert "bound" not in m
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_is_the_programs_shape(conf):
    from bench.cells import common

    cfg = json.loads((harness.ROOT / conf["file"]).read_text())
    assert conf["reduced"] == cfg["reduced"] == []
    model = common.program_model(cfg)
    ref = harness.resolve(next(w["name"] for w in BENCH["workloads"]
                               if w["config"] == conf["name"])).reference
    key = jax.random.key(0)
    ours = jax.eval_shape(lambda k: ref.init_params(cfg, k), key)
    theirs = jax.eval_shape(model.init, key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(ours)] == [
        b.shape for b in jax.tree.leaves(theirs)]
    assert sum(x.size for x in jax.tree.leaves(ours)) == cfg["params"]


def test_peaks_table():
    peaks = json.loads((harness.BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes"] == 16e9 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9 and "TPU v5e" in v5e["source"]
