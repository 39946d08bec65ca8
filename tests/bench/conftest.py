import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402


def small_cell(workload: str):
    """A cell of BENCHMARK.json at the program's ``.reduced()`` widths and
    a traffic small enough for a CPU test; its limits are the cell's own."""
    from bench import harness

    cell = harness.resolve(workload)
    cfg, t = dict(cell.config), dict(cell.traffic)
    cfg["program_reduced"] = True
    if cfg["family"] == "resnet":
        cfg.update(stage_sizes=[1, 1], stage_widths=[16, 32])
        t.update(clients=3, examples_per_client=8, batch=4, epochs=min(2, t["epochs"]))
    else:
        cfg.update(feature_dim=64, hidden_dim=32)
        t.update(shard_sizes=[40, 70, 33, 90], clients=4)
    cell.config, cell.traffic = cfg, t
    return cell


@pytest.fixture
def make_small_cell():
    return small_cell
