"""Fused dequantize + FedAvg weighted reduce (Pallas TPU) — the server-side
decode hotspot of the compressed-wire round path.

Input is the int8 wire payload of every client: q (C, N) int8 values and
per-256-block fp32 scales (C, N/block).  The unfused reduce materializes
the dequantized fp32 (C, N) matrix in HBM (4x the int8 payload) and then
reads it back for the weighted reduce — three HBM passes over C x N.  This
kernel makes ONE pass.  It keeps the payload's own lane-dense (C, N)
layout and the tiling of ``quantize.py``: the grid is (column tiles,
client tiles); each step loads a (cb, tn) int8 tile plus its (cb, tn/block)
scales, dequantizes it block by block in VMEM, and adds the weighted sum of
its cb client rows into the (1, tn) fp32 output tile, which stays resident
across the client axis.  HBM traffic of the reduce is the int8 payload +
scales + the (N,) result — the bandwidth roofline for this op.  (The
error-feedback residual in core/rounds.py still dequantizes the payload
separately, once per round.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils.pytree import safe_weight_sum

from .quantize import BLOCK, LANES, block_scale, tiling

# Static VMEM ceiling audited by fedlint (pallas-vmem-budget), in
# fp32-equivalent elements (the int8 tile is costed at fp32 — the kernel
# dequantizes it in VMEM anyway): 3M elems = 12 MB of ~16 MB/core.
VMEM_BUDGET_ELEMS = 3 * (1 << 20)
# Worst-case tile: ROW_TILE clients x one column tile of LANES blocks.
VMEM_ASSUMES = {"mb": 32, "tn": 128 * 256, "block": 256}


def _dequant_reduce_kernel(w_ref, q_ref, s_ref, o_ref, *, block: int, c: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...]                                      # (cb, 1) weights
    s = s_ref[...]                                      # (cb, tn/block)
    valid = None
    if c % w.shape[0]:
        # the last client tile is partial: its rows past C are unspecified
        # (they may hold NaN), so they are zeroed, not just weighted by 0
        row = pl.program_id(1) * w.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, w.shape, 0)
        valid = row < c
    for g in range(q_ref.shape[1] // block):
        cols = slice(g * block, (g + 1) * block)
        x = w * (q_ref[:, cols].astype(jnp.float32) * block_scale(s, g))
        if valid is not None:
            x = jnp.where(valid, x, 0.0)
        o_ref[:, cols] += jnp.sum(x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "bn", "interpret"))
def dequant_reduce(
    q, scales, weights, *, block: int = BLOCK, bn: int = LANES * BLOCK,
    interpret: bool = False,
):
    """(C,N) int8 x (C,N/block) fp32 x (C,) -> (N,) fp32 weighted mean.

    N % block == 0 (the encoder pads).  The grid is a ceiling division:
    the last column tile is partial (reads padded, writes masked), and the
    rows of a partial last client tile are masked out of the sum.  Weights
    are auto-normalized.
    """
    c, n = q.shape
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    assert scales.shape == (c, n // block), scales.shape
    mb, tn, (row_tiles, col_tiles) = tiling(c, n, bn, block)
    wf = weights.astype(jnp.float32)
    wn = (wf / safe_weight_sum(wf)).reshape(c, 1)

    out = pl.pallas_call(
        functools.partial(_dequant_reduce_kernel, block=block, c=c),
        grid=(col_tiles, row_tiles),
        in_specs=[
            pl.BlockSpec((mb, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((mb, tn), lambda i, j: (j, i)),
            pl.BlockSpec((mb, tn // block), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, tn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(wn, q, scales.astype(jnp.float32))
    return out[0]
