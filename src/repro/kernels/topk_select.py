"""Exact top-k selection of TopK uplinks (Pallas TPU): for every leaf of an
update, each client row's k kept coordinates in ascending order with their
values, and the row with them zeroed (the error-feedback residual), all
leaves in one kernel call and one pass over their rows.

The selection is the oracle's (``ref.topk_select``): each row's threshold
``t`` on the int32 keys of |x| (``ref.topk_keys``, ``ref.topk_threshold``);
every key above ``t`` is kept, and of the keys equal to it the first
``need = k - count(key > t)`` in index order.  The oracle then places the
kept by a binary search for every output slot, k gathers a level, and a
TPU gathers at ~15 ns an element.  Here each leaf's rows are laid out as
lines of 128 lanes, the leaves one after another (each on whole steps of
``STEP_LINES`` lines), and:

1. XLA finds every leaf's thresholds in the same counting passes over all
   the lines (``_line_tables``: one fusion a pass for the whole update,
   not one a leaf), then counts, per line, the keys above ``t`` and those
   equal to it, and from the two counts each line's kept and two running
   totals: ``a``, the equal keys before the line in its leaf, and ``p``,
   the output slot of its first kept (the leaves' slots one after another).
2. The kernel streams the lines in blocks.  In each step of lines it
   rebuilds the keys, keeps ``key > t`` and the equal keys whose rank
   (``a`` plus an in-line prefix count) is within ``need``, writes the
   residual, and moves each kept coordinate's index and value bits to its
   slot: left to the front of its line by the count of unkept before it
   (seven roll-and-select steps of 1..64 lanes, lowest bit first, which
   keeps order and never collides), a rotation by ``p mod 128``, and, for
   every eight lines, one store of the two output tiles their slots fall
   in.  The output tiles stay in VMEM across a client's blocks.

One call serves every leaf, so a program holds one such kernel however
many leaf shapes its model has (each distinct kernel costs seconds of
compile).  HBM traffic: the rows read by the counts and by the kernel, the
residual written once, 8 bytes a kept coordinate; the threshold's passes
before.
"""
from __future__ import annotations

import functools
from itertools import accumulate

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

# Static VMEM ceiling, audited by fedlint (pallas-vmem-budget), in fp32
# elements: 3M = 12 MB of the ~16 MB/core VMEM.
VMEM_BUDGET_ELEMS = 3 * (1 << 20)

LANES = 128
TILE = 8 * LANES
BLOCK_LINES = 512  # lines per grid step
STEP_LINES = 64  # lines per step of the kernel's loop: eight (8, 128) tiles
# Output tiles of the two payload arrays (index, value bits) that fit
# beside the double-buffered line and residual blocks; ``ops`` sends a
# larger total k to the oracle.
MAX_OUT_TILES = (VMEM_BUDGET_ELEMS - 4 * BLOCK_LINES * LANES) // (4 * TILE)
MAX_K = (MAX_OUT_TILES - 2) * TILE

VMEM_ASSUMES = {"bl": BLOCK_LINES, "out_tiles": MAX_OUT_TILES}


def _lane_cumsum(x, lane):
    """Inclusive prefix sum of an int32 tile along its lanes."""
    for s in (1, 2, 4, 8, 16, 32, 64):
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
    return x


def _select_kernel(t_ref, need_ref, leaf_ref, line_ref, a_ref, p_ref, x_ref,
                   idx_ref, val_ref, res_ref, *scratch, n_leaves: int, block_lines: int):
    c = pl.program_id(0)
    per_line_ref, moved_ref = scratch[:5], scratch[5:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        idx_ref[...] = jnp.zeros_like(idx_ref)
        val_ref[...] = jnp.zeros_like(val_ref)

    sub = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (STEP_LINES, LANES), 1)

    def step(i, carry):
        r0 = pl.multiple_of(i * STEP_LINES, STEP_LINES)

        # per line, one scalar each, spread over its lanes: its leaf's
        # threshold and need; its line within the leaf and valid lanes
        # (``line_ref``: line << 8 | lanes); the equal keys and the kept
        # before it in its leaf
        def spread(g, carry):
            rows = [r0 + 8 * g + s for s in range(8)]
            cols = (
                [t_ref[c * n_leaves + leaf_ref[r]] for r in rows],
                [need_ref[c * n_leaves + leaf_ref[r]] for r in rows],
                [line_ref[r] for r in rows],
                [a_ref[r] for r in rows],
                [p_ref[r] for r in rows],
            )
            for ref_, col in zip(per_line_ref, cols):
                v = jnp.zeros((8, LANES), jnp.int32)
                for s, value in enumerate(col):
                    v = jnp.where(sub == s, value, v)
                ref_[pl.ds(pl.multiple_of(8 * g, 8), 8), :] = v
            return carry

        jax.lax.fori_loop(0, STEP_LINES // 8, spread, 0)
        t, need, line, a, p = (ref_[...] for ref_ in per_line_ref)

        x = x_ref[0, pl.ds(r0, STEP_LINES), :]
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        mag = bits & 0x7FFFFFFF
        key = jnp.where(mag > 0x7F800000, 0, mag + 1)  # ref.topk_keys
        valid = lane < (line & 0xFF)
        above = (valid & (key > t)).astype(jnp.int32)
        at = (valid & (key == t)).astype(jnp.int32)
        # one prefix count of both (each at most 128: 16 bits apiece)
        counts = _lane_cumsum(above + (at << 16), lane)
        keep = (above == 1) | ((at == 1) & (a + (counts >> 16) <= need))
        res_ref[0, pl.ds(r0, STEP_LINES), :] = jnp.where(keep, 0.0, x)
        # kept up to each lane: the above, and the equal keys within need
        rank = (counts & 0xFFFF) + jnp.minimum(counts >> 16, jnp.maximum(need - a, 0))

        # to the line's front: a kept coordinate moves left by the count of
        # unkept before it, bit by bit from the lowest.  ``tag`` holds its
        # lane (bits 8..14) and that shift (bits 0..6); -1 marks a lane that
        # holds nothing
        tag = jnp.where(keep, (lane << 8) | (lane + 1 - rank), -1)
        vv = bits
        for j in range(7):
            s = 1 << j
            rt = pltpu.roll(tag, LANES - s, 1)  # lane i sees lane i + s
            arrive = (lane < LANES - s) & (rt >= 0) & ((rt & s) != 0)
            leave = (tag >= 0) & ((tag & s) != 0)
            tag = jnp.where(arrive, rt, jnp.where(leave, -1, tag))
            vv = jnp.where(arrive, pltpu.roll(vv, LANES - s, 1), vv)
        # rotate each line to its first slot's lane
        rho = p & (LANES - 1)
        for j in range(7):
            s = 1 << j
            turn = (rho & s) != 0
            tag = jnp.where(turn, pltpu.roll(tag, s, 1), tag)
            vv = jnp.where(turn, pltpu.roll(vv, s, 1), vv)
        # the line's first output row takes the lanes from rho on, the next
        # row the lanes that wrapped; -1 where nothing is held
        moved_ref[0][...] = (line >> 8) * LANES + (tag >> 8)  # position in the leaf
        moved_ref[1][...] = vv
        moved_ref[2][...] = jnp.where(tag < 0, -1, (lane < rho).astype(jnp.int32))

        # eight lines' slots run from the first line's on, at most 1024 of
        # them: they lie in that slot's output tile and the next, which are
        # filled in registers and stored once
        def store(g, carry):
            r8 = pl.multiple_of(8 * g, 8)
            vi, vv, row = (ref_[pl.ds(r8, 8), :] for ref_ in moved_ref)
            out = p_ref[r0 + r8] // TILE
            base = out * 8  # the first row of output tile ``out``
            tiles = [ref_[0, out + h] for ref_ in (idx_ref, val_ref) for h in (0, 1)]
            for s in range(8):
                rel = p_ref[r0 + r8 + s] // LANES - base  # the line's first row, 0..15
                at_row = rel + jnp.broadcast_to(row[s:s + 1], (8, LANES))
                lines = [jnp.broadcast_to(v[s:s + 1], (8, LANES)) for v in (vi, vv)]
                held = jnp.broadcast_to(row[s:s + 1] >= 0, (8, LANES))
                for h in (0, 1):
                    hit = held & (sub + 8 * h == at_row)
                    tiles[h] = jnp.where(hit, lines[0], tiles[h])
                    tiles[2 + h] = jnp.where(hit, lines[1], tiles[2 + h])
            for ref_, (lo, hi) in ((idx_ref, tiles[:2]), (val_ref, tiles[2:])):
                ref_[0, out] = lo
                ref_[0, out + 1] = hi
            return carry

        jax.lax.fori_loop(0, STEP_LINES // 8, store, 0)
        return carry

    jax.lax.fori_loop(0, block_lines // STEP_LINES, step, 0)


def _pad_to(v, size, fill):
    return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, size - v.shape[-1])],
                   constant_values=fill)


def _line_tables(x3, leaf, nvalid, spans, ks):
    """Every leaf's lines (C, L, 128), one after another -> each (client,
    leaf) threshold and need (C, leaves), and per line the equal keys and
    the kept before it in its leaf (C, L) and its first output slot (C, L).

    The threshold is ``ref.topk_threshold``'s, for every leaf in the same
    passes: each pass counts the keys of every ``STEP_LINES`` block against
    its leaf's probes, and a running sum over the blocks gives each leaf's
    counts (a block never straddles two leaves).  NaN pads key 0: they
    reach no probe and are never above a threshold; equal to one of 0, they
    are taken back out."""
    c = x3.shape[0]
    key = ref.topk_keys(x3)
    k = jnp.asarray(ks, jnp.int32)
    ends = list(accumulate(spans))  # each leaf's end line
    # index tables as arrays: indexing with a long list is slow to trace
    blk_leaf = jnp.asarray(leaf[::STEP_LINES], jnp.int32)
    blk_end = jnp.asarray([e // STEP_LINES - 1 for e in ends], jnp.int32)
    first = jnp.asarray([e - s for e, s in zip(ends, spans)], jnp.int32)
    pads = jnp.asarray([LANES - v for v in nvalid], jnp.int32)
    leaf = jnp.asarray(leaf, jnp.int32)

    def leaf_sums(v):  # (C, blocks, ...) -> (C, leaves, ...)
        run = jnp.cumsum(v, axis=1)[:, blk_end]
        return run - jnp.concatenate([jnp.zeros_like(run[:, :1]), run[:, :-1]], axis=1)

    blocks = key.reshape(c, -1, STEP_LINES * LANES)

    def settle(t, low, width):
        tb = t[:, blk_leaf][:, :, None]
        counts = jnp.stack([
            jnp.sum(blocks >= tb + (j << low), axis=2, dtype=jnp.int32)
            for j in range(1, 1 << width)
        ], axis=-1)
        reach = leaf_sums(counts) >= k[:, None]
        return t + (jnp.sum(reach, axis=-1, dtype=jnp.int32) << low)

    passes, top = divmod(31, ref.TOPK_PASS_BITS)
    t = settle(jnp.zeros((c, len(ks)), jnp.int32), ref.TOPK_PASS_BITS * passes, top)
    for i in reversed(range(passes)):
        t = settle(t, ref.TOPK_PASS_BITS * i, ref.TOPK_PASS_BITS)

    t_line = t[:, leaf]
    above = jnp.sum(key > t_line[:, :, None], axis=2, dtype=jnp.int32)
    at = jnp.sum(key == t_line[:, :, None], axis=2, dtype=jnp.int32)
    at = at - jnp.where(t_line == 0, pads, 0)
    need = k - leaf_sums(above.reshape(c, -1, STEP_LINES).sum(axis=2))
    before = jnp.cumsum(at, axis=1) - at
    a = before - before[:, first][:, leaf]
    kept = above + jnp.clip(need[:, leaf] - a, 0, at)
    # each leaf keeps exactly its k, so a running count over every line
    # places the leaves' slots one after another
    return t, need, a, jnp.cumsum(kept, axis=1) - kept


@functools.partial(jax.jit, static_argnames=("ks", "interpret"))
def topk_select_rows(xs, ks, *, interpret: bool = False):
    """Leaves ``xs[i]`` (..., n_i) fp32, one leading shape, and their
    ``ks[i]`` -> a list of (idx (..., k_i) int32, val (..., k_i) fp32,
    residual (..., n_i) fp32), each bit for bit ``ref.topk_select``;
    ``sum(ks) <= MAX_K``."""
    lead = xs[0].shape[:-1]
    rows = [x.reshape(-1, x.shape[-1]) for x in xs]
    c, nl = rows[0].shape[0], len(rows)
    ns = [r.shape[1] for r in rows]
    lines = [-(-n // LANES) for n in ns]
    spans = [-(-m // STEP_LINES) * STEP_LINES for m in lines]
    starts = [0, *accumulate(spans)]
    offs = [0, *accumulate(ks)]
    total = starts[-1]
    bl = min(BLOCK_LINES, total)
    nb = -(-total // bl)
    bs = -(-bl // TILE) * TILE  # SMEM blocks of whole 1024s: XLA's layout of a long int32 vector

    # every leaf on whole steps of lines (NaN past its end: keys 0, masked)
    x3 = jnp.concatenate([
        _pad_to(r, s * LANES, jnp.nan).reshape(c, s, LANES) for r, s in zip(rows, spans)
    ], axis=1)
    # per line (static): its leaf, its line within the leaf and valid lanes
    leaf = [i for i, s in enumerate(spans) for _ in range(s)]
    nvalid = [min(max(n - LANES * j, 0), LANES) for n, s in zip(ns, spans) for j in range(s)]
    line = [j << 8 | v for j, v in zip((j for s in spans for j in range(s)), nvalid)]
    t, need, a, p = _line_tables(x3, leaf, nvalid, spans, ks)

    # per-line tables, in SMEM blocks of ``bs`` a grid step; lines past the
    # last leaf's hold nothing and start at the last slot

    def blocks(v, fill):
        v = _pad_to(v, nb * bl, fill).reshape(v.shape[:-1] + (nb, bl))
        return _pad_to(v, bs, fill).reshape(-1)

    def table(v):  # (c, leaves) row-major, padded to whole 1024s
        v = v.reshape(-1)
        return _pad_to(v, -(-v.shape[0] // TILE) * TILE, 0)

    k_total = offs[-1]
    out_tiles = k_total // TILE + 2  # the slots, and the row past the last
    tables = pl.BlockSpec((-(-c * nl // TILE) * TILE,), lambda i, j: (0,),
                          memory_space=pltpu.SMEM)
    static_lines = pl.BlockSpec((bs,), lambda i, j: (j,), memory_space=pltpu.SMEM)
    client_lines = pl.BlockSpec((bs,), lambda i, j: (i * nb + j,), memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((1, out_tiles, 8, LANES), lambda i, j: (i, 0, 0, 0))
    line_spec = pl.BlockSpec((1, bl, LANES), lambda i, j: (i, j, 0))
    idx, val, res = pl.pallas_call(
        functools.partial(_select_kernel, n_leaves=nl, block_lines=bl),
        grid=(c, nb),
        in_specs=[tables, tables, static_lines, static_lines, client_lines,
                  client_lines, line_spec],
        out_specs=[out_spec, out_spec, line_spec],
        out_shape=[
            jax.ShapeDtypeStruct((c, out_tiles, 8, LANES), jnp.int32),
            jax.ShapeDtypeStruct((c, out_tiles, 8, LANES), jnp.int32),
            jax.ShapeDtypeStruct((c, total, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((STEP_LINES, LANES), jnp.int32) for _ in range(8)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        table(t), table(need),
        blocks(jnp.asarray(leaf, jnp.int32)[None], 0),
        blocks(jnp.asarray(line, jnp.int32)[None], 0),
        blocks(a, 0),
        blocks(p, k_total),
        x3,
    )
    idx = idx.reshape(c, -1)
    val = jax.lax.bitcast_convert_type(val.reshape(c, -1), jnp.float32)
    out = []
    for n, m, k, off, start in zip(ns, lines, ks, offs, starts):
        r = res[:, start:start + m]
        out.append((
            idx[:, off:off + k].reshape(lead + (k,)),
            val[:, off:off + k].reshape(lead + (k,)),
            r.reshape(c, -1)[:, :n].reshape(lead + (n,)),
        ))
    return out
