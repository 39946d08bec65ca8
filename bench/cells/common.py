"""Pieces the cell runners share: the program's model for a configuration,
the weights made from the seed, and the faults a test can plant."""
from __future__ import annotations

import jax

from bench.harness import Refused


def keys(seed: int):
    """(params key, data key) from the run's seed."""
    root = jax.random.key(seed)
    return jax.random.fold_in(root, 0), jax.random.fold_in(root, 1)


def program_model(cfg: dict):
    """The program's model for the configuration (its ``.reduced()``
    variant where the file sets ``program_reduced``, as CPU tests do)."""
    from repro.configs.base import get_config
    from repro.models import build_model

    arch = get_config(cfg["program_model"])
    if cfg.get("program_reduced"):
        arch = arch.reduced()
    return build_model(arch)


def init_params(cell, key, model):
    """The configuration's weights from ``key``, made on the device in one
    jitted call, in the program's parameter layout."""
    ref, cfg = cell.reference, cell.config
    ours = jax.eval_shape(lambda k: ref.init_params(cfg, k), key)
    theirs = jax.eval_shape(model.init, key)
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs))):
        raise Refused(f"bench: {cfg['name']} is not the shape of the program's "
                      f"{cfg['program_model']}")
    n = sum(x.size for x in jax.tree.leaves(ours))
    if not cfg.get("program_reduced") and n != cfg["params"]:
        raise Refused(f"bench: {cfg['name']} has {n} params, its file states {cfg['params']}")
    return jax.jit(lambda k: ref.init_params(cfg, k))(key)


def planted_loss(loss_fn, faults):
    """The program's loss with the loss-side faults a test (or the control
    readings) can plant under the timed path: ``"unchanged"`` cuts the
    gradient so every local step leaves the params as they were;
    ``"half_batch"`` takes the mean over the first half of each batch.
    (``"unweighted"``, equal server weights in place of example counts, is
    planted in the Flower runner's strategy.)"""
    if "half_batch" in faults:
        inner = loss_fn

        def loss_fn(p, b):  # noqa: F811
            return inner(p, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
    if "unchanged" in faults:
        inner2 = loss_fn

        def loss_fn(p, b):  # noqa: F811
            loss, aux = inner2(p, b)
            return jax.lax.stop_gradient(loss), aux
    return loss_fn
