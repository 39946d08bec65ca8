"""``correct`` at a size a CPU test can hold: a sound run passes, and the
control and every fault a cell can have fail it.  These drive the whole
harness run except its look for a chip."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference import compare

SCANNED = "resnet18-cifar10.fedavg-null.c10"
TOPK = "resnet18-cifar10.topk1pct.c10"
FLOWER = "mobilenet-head-office31.flower-run.c10"
SEED = 2**31 + 101          # wider than 32 signed bits, as a run's seed may be


def _run(cell, faults=()):
    return harness.measure(cell, SEED, 0.3, False, jax.devices(),
                           time.perf_counter(), faults=faults,
                           peaks=harness.peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("workload", [SCANNED, TOPK, FLOWER])
def test_sound_run_is_correct(workload, make_small_cell):
    res = _run(make_small_cell(workload))
    assert res["correct"], res["checked"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checked"
    assert "setup_s" in res["metrics"]
    assert any(m.startswith("client_updates_per_s") for m in res["metrics"])


@pytest.mark.parametrize("workload,fault", [
    (SCANNED, "unchanged"), (SCANNED, "half_batch"),
    (TOPK, "unchanged"), (TOPK, "half_batch"),
    (FLOWER, "unchanged"), (FLOWER, "half_batch"), (FLOWER, "unweighted"),
])
def test_planted_fault_is_not_correct(workload, fault, make_small_cell):
    res = _run(make_small_cell(workload), faults=(fault,))
    assert not res["correct"], res["checked"]


@pytest.mark.parametrize("workload", [SCANNED, TOPK, FLOWER])
def test_bf16_control_is_not_correct(workload, make_small_cell):
    """The reference computed in bfloat16, in the program's place, fails at
    least one of the cell's numbers."""
    cell = make_small_cell(workload)
    runner = harness.make_runner(cell, SEED, jax.devices())
    p0, ref = runner.reference_rounds()
    _, low = runner.reference_rounds(dtype=jnp.bfloat16)
    nums = compare.training_numbers(p0, low, ref)
    assert any(nums[k] > limit for k, limit in cell.limits.items()), nums


def test_worst_leaf_and_support_mismatch():
    """The witness helpers name the leaf that sets a gap and count the moved
    entries that only one side moved."""
    import numpy as np

    p0 = {"a": np.zeros(4), "b": np.zeros(2)}
    ref = {"first": {"a": np.array([1.0, 0, 0, 0]), "b": np.array([1.0, 0])},
           "last": {"a": np.array([1.0, 0, 0, 0]), "b": np.array([1.0, 0])}}
    prog = {"first": None, "last": {"a": np.array([1.0, 0, 0, 0]), "b": np.array([0, 1.5])}}
    worst = compare.worst_leaves(p0, prog, ref)
    assert list(worst) == ["change_gap"]
    (name, size, gap), = worst["change_gap"]
    assert (name, size) == ("['b']", 2) and np.isclose(gap, 0.5)
    assert np.isclose(compare.support_mismatch(p0, prog["last"], ref["last"]), 2 / 3)
