"""Benchmark-driver drift gate (ISSUE 8 satellite).

The benchmark entry points call the library through its public signatures
but are not imported by anything else, so they silently rot when those
signatures move.  This module pins them: every driver must import, and the
cheap paths must run against the CURRENT library — a signature change that
breaks a bench now fails here, not in a release-week CI artifact.
"""
import importlib
import json
import sys

import numpy as np
import pytest


@pytest.mark.parametrize("mod", [
    "benchmarks.run",
    "benchmarks.paper_tables",
    "benchmarks.scan_bench",
    "benchmarks.mesh_bench",
    "benchmarks.compression_bench",
    "benchmarks.population_bench",
    "benchmarks.straggler_bench",
])
def test_benchmark_module_imports(mod):
    importlib.import_module(mod)


def test_run_smoke_microbenches(capsys):
    """``benchmarks.run --smoke`` exercises make_round_step, the
    aggregation oracle, and the int8 quantizer against live signatures."""
    from benchmarks import run as bench_run

    argv, sys.argv = sys.argv, ["run.py", "--smoke"]
    try:
        bench_run.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    lines = [ln for ln in out.strip().splitlines() if ln]
    assert lines[0] == "name,us_per_call,derived"
    names = [ln.split(",")[0] for ln in lines[1:]]
    assert any(n.startswith("fl_round_step") for n in names)
    assert any(n.startswith("fedavg_reduce") for n in names)
    assert any(n.startswith("quantize_int8") for n in names)
    assert any(n.startswith("collective_pack") for n in names)
    assert any(n.startswith("structured_lora_roundtrip") for n in names)
    # --smoke skips the paper tables (minutes of training)
    assert not any(n.startswith("table") for n in names)


def test_lora_frontier_writes_json_and_guards(tmp_path, capsys):
    """The lora[] section: frontier rows at full LLM scale, the acceptance
    run on the reduced LM, and BENCH_lora.json with both."""
    from benchmarks.compression_bench import bench_lora_frontier

    out = tmp_path / "BENCH_lora.json"
    rows = bench_lora_frontier(rounds=1, smoke=True, out=str(out))
    names = [r.split(",")[0] for r in rows]
    assert any(n.startswith("lora[qwen3-0.6b/r4]") for n in names)
    assert any(n.startswith("lora[mixtral-8x7b/r4]") for n in names)
    assert any(n.startswith("lora[qwen3_reduced/lora_r4]") for n in names)
    data = json.loads(out.read_text())
    assert data["bench"] == "lora" and data["frontier"]
    runs = data["runs"]
    assert runs["int8"]["wire_bytes"] >= 10 * runs["lora_r4"]["wire_bytes"]


def test_paper_tables_one_cell():
    """One tiny cell of table2a end-to-end through Server.run — the bench
    that trains must still agree with the Server/Strategy signatures."""
    from benchmarks.paper_tables import table2a

    rows = table2a(rounds=1, epochs_grid=(1,))
    assert len(rows) == 1
    label, acc, mins, kj = rows[0]
    assert label == "E=1"
    assert 0.0 <= acc <= 1.0
    assert mins > 0 and kj > 0
