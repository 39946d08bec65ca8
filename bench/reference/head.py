"""Plain reference of the paper's Android personalisation model (Flower
paper §4.1): a frozen base turns each input into 1280 features, and a
two-layer head (1280 -> 256 -> 31 with ReLU) is the only part that trains.

The paper's base is MobileNetV2.  The program stands it in with one frozen
1280x1280 random projection followed by ReLU, and this reference does the
same: the base is part of every forward pass and of the FLOPs, and never of
the update.

``init_params`` makes the weights the benchmark gives the program, in the
program's layout (``base.w``; ``head.w1``, ``b1``, ``w2``, ``b2``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init_params(cfg: dict, key, dtype=jnp.float32) -> dict:
    f, h, c = cfg["feature_dim"], cfg["hidden_dim"], cfg["num_classes"]
    k0, k1, k2 = jax.random.split(key, 3)

    def dense(k, fan_in, fan_out):
        w = jax.random.normal(k, (fan_in, fan_out), jnp.float32) / math.sqrt(fan_in)
        return w.astype(dtype)

    return {
        "base": {"w": dense(k0, f, f)},
        "head": {"w1": dense(k1, f, h), "b1": jnp.zeros((h,), dtype),
                 "w2": dense(k2, h, c), "b2": jnp.zeros((c,), dtype)},
    }


def logits(cfg: dict, params: dict, x):
    feats = jax.nn.relu(x @ params["base"]["w"])
    hid = jax.nn.relu(feats @ params["head"]["w1"] + params["head"]["b1"])
    return hid @ params["head"]["w2"] + params["head"]["b2"]


def loss(cfg: dict, params: dict, batch: dict):
    """Mean softmax cross-entropy of the batch."""
    z = logits(cfg, params, batch["x"].astype(params["head"]["w1"].dtype))
    logp = jax.nn.log_softmax(z.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))


def trainable(params: dict) -> dict:
    """The head trains; the base is frozen."""
    return {"base": jax.tree.map(lambda _: False, params["base"]),
            "head": jax.tree.map(lambda _: True, params["head"])}


def param_count(cfg: dict) -> int:
    f, h, c = cfg["feature_dim"], cfg["hidden_dim"], cfg["num_classes"]
    return f * h + h + h * c + c


def flops_per_sample(cfg: dict) -> dict:
    """2 FLOPs a multiply-add.  The training sample needs the frozen base's
    forward (its output feeds the head) and the head's forward and its two
    gradients; the base's gradient is not needed, since nothing of it
    trains."""
    f, h, c = cfg["feature_dim"], cfg["hidden_dim"], cfg["num_classes"]
    base = 2 * f * f
    head = 2 * (f * h + h * c)
    return {"forward": base + head, "train": base + 3 * head}
