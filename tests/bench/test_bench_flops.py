"""The benchmark's FLOP and byte counts, against XLA's cost analysis and
against counts by hand."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness
from bench.reference import head, resnet

RESNET = json.loads((harness.BENCH / "configs" / "resnet18-cifar10.json").read_text())
HEAD = json.loads((harness.BENCH / "configs" / "mobilenet-head-office31.json").read_text())
PEAKS = harness.peaks_for("TPU v5 lite")


def _xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float((cost[0] if isinstance(cost, list) else cost)["flops"])


def test_resnet_counts_by_hand():
    # 555,422,720 multiply-adds with every padded 3x3 tap counted
    assert resnet.flops_per_sample(RESNET) == {"forward": 1_110_845_440,
                                               "train": 3_332_536_320}
    assert resnet.conv_flops(RESNET, padded=False) == 963_718_656
    assert resnet.param_count(RESNET) == 11_173_962


def test_resnet_forward_against_xla_cost_analysis():
    """XLA counts the taps over the input only, plus the elementwise work
    (GroupNorm, ReLU, adds): 970,444,416 FLOPs, 0.7% above the valid-tap
    convolutions and 12.6% below the padded count the benchmark uses."""
    p = jax.eval_shape(lambda k: resnet.init_params(RESNET, k), jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    xla = _xla_flops(lambda p, x: resnet.logits(RESNET, p, x), p, x)
    valid = resnet.conv_flops(RESNET, padded=False)
    assert valid <= xla <= 1.02 * valid
    assert xla < resnet.flops_per_sample(RESNET)["forward"]


def test_head_against_xla_cost_analysis():
    p = jax.eval_shape(lambda k: head.init_params(HEAD, k), jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, HEAD["feature_dim"]), jnp.float32)
    xla = _xla_flops(lambda p, x: head.logits(HEAD, p, x), p, x)
    fwd = head.flops_per_sample(HEAD)["forward"]
    assert fwd == 2 * (1280 * 1280 + 1280 * 256 + 256 * 31)
    assert fwd <= xla <= 1.01 * fwd                 # plus the biases and ReLUs
    assert head.flops_per_sample(HEAD)["train"] == 2 * 1280 * 1280 + 3 * (fwd - 2 * 1280 * 1280)
    assert head.param_count(HEAD) == 335_903


@pytest.mark.parametrize("c,n", [(10, 2_359_296), (10, 64), (4, 11_173_962)])
def test_fedavg_reduce_bytes(c, n):
    work = flops.fedavg_reduce(c, n)
    assert work == {"flops": 3 * c * n, "bytes": 4 * (c * n + c + 2 * n)}
    assert flops.bound(work, PEAKS) == "hbm"
    assert flops.least_seconds(work, PEAKS) == work["bytes"] / 819e9


def test_topk_scatter_reduce_bytes():
    work = flops.topk_scatter_reduce(10, 23_592, 2_359_296)
    assert work == {"flops": 2 * 10 * 23_592,
                    "bytes": 4 * (2 * 10 * 23_592 + 10 + 2_359_296)}
    assert flops.bound(work, PEAKS) == "hbm"
