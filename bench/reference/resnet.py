"""Plain reference of the ResNet-18 CIFAR classifier (He et al. 2016, the
CIFAR variant: a 3x3 stem, no max-pool), written from the published
description in straightforward ``jax.numpy`` with nothing of the program
imported.

Departure from the paper, the same as the program's: GroupNorm (8 groups,
eps 1e-5) in place of BatchNorm, since BatchNorm's running statistics do
not survive federated averaging.

``init_params`` also makes the weights the benchmark gives the program:
the layout (``stem``, ``stem_n``, ``stages`` of blocks with ``conv1``,
``n1``, ``conv2``, ``n2`` and, where the shape changes, ``proj`` and
``proj_n``; ``fc_w``, ``fc_b``) is the program's parameter interface.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GROUPS = 8
EPS = 1e-5


def _strides(cfg):
    """(stage, block) -> stride: the first block of every stage after the
    first halves the resolution."""
    return [[2 if (s > 0 and b == 0) else 1 for b in range(n)]
            for s, n in enumerate(cfg["stage_sizes"])]


def init_params(cfg: dict, key, dtype=jnp.float32) -> dict:
    """He-normal convolutions, unit-scale zero-bias norms, a 1/sqrt(fan_in)
    classifier: one pytree in ``dtype``, made by one traced function."""
    widths, sizes = cfg["stage_widths"], cfg["stage_sizes"]
    keys = iter(jax.random.split(key, 3 * sum(sizes) + 2))

    def conv(k, kh, cin, cout):
        std = math.sqrt(2.0 / (kh * kh * cin))
        return (std * jax.random.normal(k, (kh, kh, cin, cout), jnp.float32)).astype(dtype)

    def norm(c):
        return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}

    params = {"stem": conv(next(keys), 3, cfg["channels"], widths[0]),
              "stem_n": norm(widths[0]), "stages": []}
    cin = widths[0]
    for s, (n, cout) in enumerate(zip(sizes, widths)):
        stage = []
        for b in range(n):
            stride = _strides(cfg)[s][b]
            blk = {"conv1": conv(next(keys), 3, cin, cout), "n1": norm(cout),
                   "conv2": conv(next(keys), 3, cout, cout), "n2": norm(cout)}
            k_proj = next(keys)
            if stride != 1 or cin != cout:
                blk["proj"] = conv(k_proj, 1, cin, cout)
                blk["proj_n"] = norm(cout)
            stage.append(blk)
            cin = cout
        params["stages"].append(stage)
    fc = jax.random.normal(next(keys), (widths[-1], cfg["num_classes"]), jnp.float32)
    params["fc_w"] = (fc / math.sqrt(widths[-1])).astype(dtype)
    params["fc_b"] = jnp.zeros((cfg["num_classes"],), dtype)
    return params


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _groupnorm(x, p):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, GROUPS, c // GROUPS)
    mu = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mu), axis=(1, 2, 4), keepdims=True)
    y = ((g - mu) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    return y * p["scale"] + p["bias"]


def logits(cfg: dict, params: dict, x):
    h = jax.nn.relu(_groupnorm(_conv(x, params["stem"], 1), params["stem_n"]))
    for s, stage in enumerate(params["stages"]):
        for b, blk in enumerate(stage):
            stride = _strides(cfg)[s][b]
            y = jax.nn.relu(_groupnorm(_conv(h, blk["conv1"], stride), blk["n1"]))
            y = _groupnorm(_conv(y, blk["conv2"], 1), blk["n2"])
            if "proj" in blk:
                h = _groupnorm(_conv(h, blk["proj"], stride), blk["proj_n"])
            h = jax.nn.relu(h + y)
    return jnp.mean(h, axis=(1, 2)) @ params["fc_w"] + params["fc_b"]


def loss(cfg: dict, params: dict, batch: dict):
    """Mean softmax cross-entropy of the batch."""
    z = logits(cfg, params, batch["x"].astype(jax.tree.leaves(params)[0].dtype))
    logp = jax.nn.log_softmax(z.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))


def trainable(params: dict) -> dict:
    """Every leaf trains."""
    return jax.tree.map(lambda _: True, params)


def param_count(cfg: dict) -> int:
    return sum(math.prod(x.shape) for x in jax.tree.leaves(
        jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))))


def _taps(hw: int, stride: int, k: int, padded: bool) -> int:
    """Taps of a 'SAME' k-wide window along one axis, summed over the
    outputs: all k at every output, or only those over the input."""
    out = -(-hw // stride)
    if padded:
        return out * k
    lo = max((out - 1) * stride + k - hw, 0) // 2
    return sum(0 <= o * stride - lo + t < hw for o in range(out) for t in range(k))


def conv_flops(cfg: dict, padded: bool = True) -> int:
    """FLOPs (2 a multiply-add) of one image's convolutions and classifier,
    with the padded taps of the 3x3 windows counted or not."""
    hw, cin = cfg["image_size"], cfg["stage_widths"][0]
    macs = _taps(hw, 1, 3, padded) ** 2 * cfg["channels"] * cin
    for s, (n, cout) in enumerate(zip(cfg["stage_sizes"], cfg["stage_widths"])):
        for b in range(n):
            stride = _strides(cfg)[s][b]
            macs += _taps(hw, stride, 3, padded) ** 2 * cin * cout
            hw = -(-hw // stride)
            macs += _taps(hw, 1, 3, padded) ** 2 * cout * cout
            if stride != 1 or cin != cout:
                macs += hw * hw * cin * cout
            cin = cout
    return 2 * (macs + cin * cfg["num_classes"])


def flops_per_sample(cfg: dict) -> dict:
    """The work one image needs.  Every 3x3 'SAME' convolution is counted
    at every output pixel with all 9 taps, padded taps included (the MXU
    computes them); GroupNorm, ReLU, the residual adds and the pooling are
    elementwise and left out.  Training needs three times the forward: the
    forward, the gradient with respect to the activations and the gradient
    with respect to the weights.  (XLA's cost analysis counts only the taps
    over the input, ``conv_flops(cfg, padded=False)``, plus the elementwise
    work.)"""
    forward = conv_flops(cfg, padded=True)
    return {"forward": forward, "train": 3 * forward}
