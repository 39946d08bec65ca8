"""FedAvg weighted aggregation (Pallas TPU) — the server's compute hotspot.

updates: (C, N) flat client updates, weights: (C,), and optionally a
center (N,) subtracted from every row.  Grid = (ceil(N/bn),): each
step loads a (C, bn) tile and sums its rows against the (C, 1) weight
column on the VPU into a lane-dense (1, bn) output tile, in fp32 — one
pass over the C x N payload at HBM bandwidth, which is the roofline for
this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.pytree import safe_weight_sum

# Static VMEM ceiling audited by fedlint (pallas-vmem-budget), in
# fp32-equivalent elements: 3M elems = 12 MB of the ~16 MB/core VMEM.
VMEM_BUDGET_ELEMS = 3 * (1 << 20)
# Worst-case dims the audit pins: the cohort height of the (C, bn) tile
# and the flat update length.  The bn clamp below keeps any C <= this
# inside the budget at runtime.
VMEM_ASSUMES = {"c": 1024, "n": 1 << 22}


def _reduce_kernel(*refs, centered: bool):
    if centered:
        u_ref, g_ref, w_ref, o_ref = refs
        u = u_ref[...].astype(jnp.float32) - g_ref[...].astype(jnp.float32)
    else:
        u_ref, w_ref, o_ref = refs
        u = u_ref[...].astype(jnp.float32)      # (C, bn)
    w = w_ref[...]                              # (C, 1) normalized weights
    o_ref[...] = jnp.sum(u * w, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def fedavg_reduce(updates, weights, center=None, *, bn: int = 8192,
                  interpret: bool = False):
    """(C,N) x (C,) -> (N,) weighted mean (weights auto-normalized).

    With a ``center`` (N,), the mean of ``updates - center`` in fp32: the
    difference is taken tile by tile in VMEM, so a caller that holds client
    params and the global never writes the (C, N) delta matrix.  The grid
    is a ceiling division over N: the last tile is partial (reads padded,
    writes masked), so tail elements are reduced in place.
    """
    c, n = updates.shape
    # shrink the tile for large cohorts so the double-buffered (C, bn)
    # update tiles + the (C, 1) weight column + the (1, bn) center and
    # output stay inside the declared VMEM budget:
    # 2*C*bn + 4*bn + C <= VMEM_BUDGET_ELEMS
    cap = (VMEM_BUDGET_ELEMS - c) // (2 * (c + 2))
    # a lane-aligned tile width, or one full-width tile for a narrow N
    bn = min(n, max(128, min(bn, cap) // 128 * 128))
    wf = weights.astype(jnp.float32)
    wn = (wf / safe_weight_sum(wf)).reshape(c, 1)
    operands = [updates]
    in_specs = [pl.BlockSpec((c, bn), lambda i: (0, i))]
    if center is not None:
        operands.append(center.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda i: (0, i)))
    out_dtype = updates.dtype if center is None else jnp.float32

    out = pl.pallas_call(
        functools.partial(_reduce_kernel, centered=center is not None),
        grid=(pl.cdiv(n, bn),),
        in_specs=in_specs + [pl.BlockSpec((c, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), out_dtype),
        interpret=interpret,
    )(*operands, wn)
    return out[0]
