"""Plain FedAvg rounds (McMahan et al. 2017), the yardstick of ``correct``.

Clients train one at a time, each with plain SGD from the round's global
model over its own batches; the new global is the average of the clients'
models weighted by the example counts given; the round's loss is the
same weighted average of the clients' mean step losses.  With a top-k
uplink (Stich et al. 2018), each client sends, leaf by leaf, the k largest
entries by magnitude of its delta plus its carried residual (ties to the
lower index), keeps the rest as its next residual, and the server adds
the weighted average of what was sent.

Nothing of the program is imported.  ``dtype`` float32 runs under
``default_matmul_precision("highest")``; bfloat16 is the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def make_train_client(mod, cfg: dict, lr: float, mask: dict):
    """jit(global params, batches (steps, B, ...)) -> (params, mean loss):
    one client's local SGD, only the ``mask``-ed leaves moving."""

    def step(p, batch):
        loss, g = jax.value_and_grad(lambda q: mod.loss(cfg, q, batch))(p)
        p = jax.tree.map(
            lambda w, d, m: (w - lr * d).astype(w.dtype) if m else w, p, g, mask)
        return p, loss

    @jax.jit
    def train(params, batches):
        p, losses = jax.lax.scan(step, params, batches)
        return p, jnp.mean(losses.astype(jnp.float32))

    return train


def topk_transmit(delta: np.ndarray, resid: np.ndarray, frac: float):
    """One leaf's top-k uplink: (sent, new residual), both flat float arrays.
    k = floor(frac * size), at least 1; ties go to the lower index."""
    eff = delta + resid
    k = max(1, math.floor(eff.size * frac))
    order = np.argsort(-np.abs(eff), kind="stable")[:k]
    sent = np.zeros_like(eff)
    sent[order] = eff[order]
    return sent, eff - sent


def run_rounds(mod, cfg: dict, params, client_batches, weights, *, lr: float,
               rounds: int, dtype=jnp.float32, topk_frac: float | None = None):
    """``rounds`` FedAvg rounds from ``params``.

    ``client_batches(rnd, c)`` gives client c's (steps, B, ...) batches of
    round rnd (0-based), as numpy or device arrays.  Returns per-round
    losses, the global after each round (numpy float64 pytrees), and, for
    top-k, the mean over clients and leaves of the L2 norm of each leaf's
    residual after each round."""
    weights = np.asarray(weights, np.float64)
    mask = mod.trainable(params)
    train = make_train_client(mod, cfg, lr, mask)
    g = jax.tree.map(lambda x: np.asarray(x, np.float64), params)
    leaves, treedef = jax.tree.flatten(g)
    resid = [[np.zeros(x.size) for x in leaves] for _ in weights]
    losses, globals_, resid_norms = [], [], []
    ctx = (jax.default_matmul_precision("highest") if dtype == jnp.float32
           else jax.default_matmul_precision("default"))
    with ctx:
        for rnd in range(rounds):
            g_dev = jax.tree.map(lambda x: jnp.asarray(x, dtype), g)
            g_leaves = treedef.flatten_up_to(g)
            total = [np.zeros_like(x) for x in g_leaves]
            round_losses = []
            for c, w in enumerate(weights):
                b = jax.tree.map(lambda x: jnp.asarray(x), client_batches(rnd, c))
                if dtype != jnp.float32:
                    b = {**b, "x": b["x"].astype(dtype)}
                p, loss = train(g_dev, b)
                round_losses.append(float(loss))
                p_leaves = treedef.flatten_up_to(jax.tree.map(
                    lambda x: np.asarray(x, np.float64), p))
                for i, (pl, gl) in enumerate(zip(p_leaves, g_leaves)):
                    delta = (pl - gl).reshape(-1)
                    if topk_frac is not None:
                        delta, resid[c][i] = topk_transmit(delta, resid[c][i], topk_frac)
                    total[i] += w * delta.reshape(gl.shape)
            g = jax.tree.unflatten(treedef, [
                gl + t / weights.sum() for gl, t in zip(g_leaves, total)])
            losses.append(float(np.average(round_losses, weights=weights)))
            globals_.append(g)
            if topk_frac is not None:
                resid_norms.append(float(np.mean([
                    np.linalg.norm(r) for rows in resid for r in rows])))
    return {"losses": losses, "globals": globals_, "resid_norms": resid_norms}
