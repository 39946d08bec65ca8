"""int8 block quantization codec (Pallas TPU) for FL update compression.

Symmetric per-256-block scaling along the last axis.  Inputs are (M, N)
(a 1-D (N,) vector is one row), kept in their own lane-dense layout: no
relayout to a (blocks, 256) view, which on the TPU is a full HBM copy and
a slow compile for int8 at round widths.  The grid is (row tiles, column
tiles).  A column tile holds 128 scale blocks, so the scales of a tile are
one lane-dense (rows, 128) tile; the kernel walks the tile's blocks with
static 256-lane slices, and puts each block's scale in its lane through an
iota mask.  An N that is no wider than one column tile is one full-width
tile.  The grid is a ceiling division: Pallas pads the reads of the last,
partial tile and masks its writes, so a tail that is not a tile multiple
is processed in place, with no padded copy of the operand.  Rows are
independent and a 256-block lies wholly inside or wholly outside the
operand (tiles are whole blocks), so the padded lanes never reach a valid
output.  Dequantize reverses it.

The scaled elementwise kernels of ``collective_quant.py`` and the fused
reduce of ``dequant_reduce.py`` share this tiling (``tiling``) and the
per-block scale extraction (``block_scale``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 256
LANES = 128     # scale blocks per column tile: one lane-dense scale tile
ROW_TILE = 32   # int8 tiles are (32, 128) on the TPU

# Static VMEM ceiling audited by fedlint (pallas-vmem-budget), in
# fp32-equivalent elements: 4.5M elems.  int8 tiles are costed at fp32
# here, so the real worst case (a 32 x 32768 fp32 tile in, its int8 tile
# out, both double-buffered) is about 10 MB of the 16 MB/core.
VMEM_BUDGET_ELEMS = 9 * (1 << 19)
# Worst-case tile: ROW_TILE rows x one column tile of LANES blocks.
VMEM_ASSUMES = {"mb": ROW_TILE, "tn": LANES * BLOCK, "block": BLOCK}


def tiling(m: int, n: int, bn: int, block: int) -> tuple[int, int, tuple[int, int]]:
    """(rows per tile, columns per tile, (row tiles, column tiles)) for an
    (M, N) operand with ``block``-wide scale blocks; ``bn`` asks for a
    column tile width, rounded to whole scale tiles of LANES blocks."""
    mb = m if m <= ROW_TILE else ROW_TILE
    unit = LANES * block
    tn = n if n <= unit else max(unit, bn // unit * unit)
    return mb, tn, (pl.cdiv(m, mb), pl.cdiv(n, tn))


def block_scale(s, g: int):
    """Column ``g`` of a (rows, blocks) scale tile as a (rows, 1) column,
    taken with an iota mask and a lane sum (exact: one lane is non-zero)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.sum(jnp.where(lane == g, s, 0.0), axis=1, keepdims=True)


def _as_rows(a, block: int):
    """(..., N) -> (M, N), with N % block checked."""
    n = a.shape[-1]
    assert n % block == 0, f"N={n} not a multiple of block={block}"
    return a.reshape(-1, n)


def _quant_kernel(x_ref, q_ref, s_ref, *, block: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s = jnp.zeros(s_ref.shape, jnp.float32)
    for g in range(x_ref.shape[1] // block):
        cols = slice(g * block, (g + 1) * block)
        x = x_ref[:, cols].astype(jnp.float32)               # (rows, block)
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        q_ref[:, cols] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        s = jnp.where(lane == g, scale, s)
    s_ref[...] = s


@functools.partial(jax.jit, static_argnames=("block", "bn", "interpret"))
def quantize_int8(x, *, block: int = BLOCK, bn: int = LANES * BLOCK,
                  interpret: bool = False):
    """x: (..., N) -> (q int8 (..., N), scales fp32 (..., N/block)).
    N % block == 0."""
    x2 = _as_rows(x, block)
    m, n = x2.shape
    mb, tn, grid = tiling(m, n, bn, block)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((mb, tn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((mb, tn), lambda i, j: (i, j)),
            pl.BlockSpec((mb, tn // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((m, n // block), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    lead = x.shape[:-1]
    return q.reshape(*lead, n), s.reshape(*lead, n // block)


def scaled_map(fn, a, scales, out_dtype, *, block: int, bn: int,
               interpret: bool):
    """out[..., j] = fn(a[..., j], scales[..., j // block]) over (..., N)
    ``a`` and its (..., N/block) ``scales``, one HBM pass."""
    a2 = _as_rows(a, block)
    m, n = a2.shape
    mb, tn, grid = tiling(m, n, bn, block)

    def kernel(a_ref, s_ref, o_ref):
        s = s_ref[...]
        for g in range(a_ref.shape[1] // block):
            cols = slice(g * block, (g + 1) * block)
            o_ref[:, cols] = fn(a_ref[:, cols], block_scale(s, g)).astype(out_dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((mb, tn), lambda i, j: (i, j)),
            pl.BlockSpec((mb, tn // block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((mb, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(a2, scales.astype(jnp.float32).reshape(m, n // block))
    return out.reshape(a.shape)


def dequant(q, s):
    """One block's dequantization: integer payload times its scale."""
    return q.astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("block", "bn", "interpret"))
def dequantize_int8(q, scales, *, block: int = BLOCK, bn: int = LANES * BLOCK,
                    interpret: bool = False):
    """(q int8 (..., N), scales fp32 (..., N/block)) -> fp32 (..., N)."""
    return scaled_map(dequant, q, scales, jnp.float32, block=block, bn=bn,
                      interpret=interpret)
