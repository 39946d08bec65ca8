"""Fused TopK scatter-accumulate weighted reduce (Pallas TPU) — the
server-side aggregation of sparse (idx, val) uplinks in O(C·k).

Input is the TopK wire payload of every client: idx (C, k) int32 positions
and val (C, k) fp32 magnitudes, plus the (C,) aggregation weights.  The
densify baseline scatters every client into a dense (C, N) fp32 matrix and
then runs the weighted reduce over it — O(C·N) time AND memory, defeating
the whole point of shipping k << N entries.  This kernel never builds that
matrix.  The payload is flattened to C·k (index, weighted value) entries,
``w_c * val[c, j]`` formed outside the kernel; grid = (entry chunks,), each
step streams one chunk of indices and values into SMEM, and the (N,) fp32
accumulator — laid out as (N/1024, 8, 128) tiles — stays resident in VMEM
across all grid steps (same out-block index every step).  For each entry
the kernel loads the one (8, 128) tile that holds it, adds the value at
its (sublane, lane) through an iota mask, and stores the tile back:

    out[idx[c, j]] += w_c * val[c, j]        for every c, j

HBM traffic is the C·k·8-byte payload plus one (N,) result write — the
wire itself is the roofline.  The entry loop serializes one tile
read-modify-write per entry, which is the price of arbitrary indices on a
vector unit, but VMEM latency is ~2 orders below HBM and k << N, so the
loop stays far under the dense path's C·N·4-byte HBM cost.

Contract (mirrors ``ref.topk_scatter_reduce``):
- duplicate indices within a client ACCUMULATE (scatter-add, not set);
- weights are auto-normalized with ``safe_weight_sum`` semantics: an
  all-zero weight vector yields a zero average, never NaNs;
- k == 0 (a payload with no entries) yields the zero vector;
- out-of-range indices (negative or >= N — a corrupt/hostile wire
  payload) are DROPPED, identically on kernel and oracle: both sanitize
  before scattering, so neither raw-VMEM writes (here) nor numpy-style
  negative wrapping (XLA scatter) can leak into the aggregate;
- N needs no alignment: the accumulator is padded to whole (8, 128)
  tiles and the pad is sliced off (in-range indices never touch the pad).

Fallback: the (N,) accumulator must fit in VMEM, so ``ops`` dispatches to
the XLA scatter-add oracle above ``MAX_N_PARAMS`` (derived from this
file's declared ``VMEM_BUDGET_ELEMS``) — still O(C·k), just not fused.
The only remaining densify path is ``TopKCodec.decode_batch``, which
exists for callers that *want* the dense per-client matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils.pytree import safe_weight_sum

# Static VMEM ceiling, audited by fedlint (pallas-vmem-budget): the
# resident footprint of every pallas_call in this file — double-buffered
# pipelined blocks, grid-invariant blocks, scratch — must stay under it.
# Units are fp32-equivalent elements (4 bytes each): 3M elems = 12 MB of
# the ~16 MB/core VMEM.
VMEM_BUDGET_ELEMS = 3 * (1 << 20)

# Payload entries per grid step, streamed into SMEM (double-buffered
# int32 indices + fp32 values: 16 KB of SMEM).
CHUNK = 1024
TILE = 8 * 128  # accumulator elements per (8, 128) tile
# Largest dense accumulator the budget admits beside the entry chunks: the
# ops dispatch falls back to the XLA scatter-add oracle above this.
MAX_N_PARAMS = (VMEM_BUDGET_ELEMS - 4 * CHUNK) // TILE * TILE

VMEM_ASSUMES = {"n_tiles": MAX_N_PARAMS // TILE}


def _scatter_reduce_kernel(idx_ref, val_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)

    def body(e, carry):
        i = idx_ref[e]
        t = i // TILE
        hit = (sub == (i // 128) % 8) & (lane == i % 128)
        o_ref[t] = o_ref[t] + jnp.where(hit, val_ref[e], 0.0)
        return carry

    jax.lax.fori_loop(0, CHUNK, body, 0)


@functools.partial(jax.jit, static_argnames=("n_params", "interpret"))
def topk_scatter_reduce(idx, val, weights, n_params: int, *, interpret: bool = False):
    """(C,k) int32 x (C,k) fp x (C,) -> (N,) fp32 weighted mean of the
    scattered sparse updates (weights auto-normalized)."""
    c, k = idx.shape
    assert val.shape == (c, k), (val.shape, idx.shape)
    if k == 0 or c == 0:
        return jnp.zeros((n_params,), jnp.float32)

    # sanitize the wire: out-of-range indices contribute nothing (idx -> 0
    # with val -> 0), so the unchecked VMEM store below cannot be steered
    # outside the accumulator by a corrupt payload
    idx = idx.astype(jnp.int32)
    valid = (idx >= 0) & (idx < n_params)
    wf = weights.astype(jnp.float32)
    wn = wf / safe_weight_sum(wf)
    contrib = jnp.where(valid, val.astype(jnp.float32), 0.0) * wn[:, None]
    # pad entries (index 0, value 0) fill the last chunk
    pad = (-(c * k)) % CHUNK
    idx = jnp.pad(jnp.where(valid, idx, 0).reshape(-1), (0, pad))
    contrib = jnp.pad(contrib.reshape(-1), (0, pad))

    n_tiles = -(-n_params // TILE)
    out = pl.pallas_call(
        _scatter_reduce_kernel,
        grid=((c * k + pad) // CHUNK,),
        in_specs=[
            pl.BlockSpec((CHUNK,), lambda e: (e,), memory_space=pltpu.SMEM),
            pl.BlockSpec((CHUNK,), lambda e: (e,), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((n_tiles, 8, 128), lambda e: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 8, 128), jnp.float32),
        interpret=interpret,
    )(idx, contrib)
    return out.reshape(-1)[:n_params]
