"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references (tests assert_allclose pallas-interpret
vs these) AND the lowering path used on non-TPU backends (the CPU dry-run
lowers these; XLA counts identical matmul FLOPs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.utils.pytree import safe_weight_sum

NEG_INF = -1e30


# --------------------------------------------------------------------------
# attention (GQA, causal, optional sliding window)
# --------------------------------------------------------------------------
def _attention_dense(qg, kf, vf, qpos, kpos, causal, window):
    """qg: (B,H,Sq,D); kf/vf: (B,H,Skv,D). Full score matrix."""
    scores = jnp.einsum("bhqd,bhsd->bhqs", qg, kf)
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bhsd->bhqd", probs, vf)


_DENSE_LIMIT = 2048        # max seq for the single-shot score matrix; above
                           # this the flash-equivalent streaming paths run, so
                           # the dry-run's HBM-traffic model matches the TPU
                           # Pallas kernel (K/V streamed per query tile)
_Q_CHUNK = 512             # query tile of the chunked paths


def attention(
    q: jnp.ndarray,           # (B, Sq, H, D)
    k: jnp.ndarray,           # (B, Skv, KV, D)
    v: jnp.ndarray,           # (B, Skv, KV, D)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,        # absolute position of q[0] (prefill chunks / decode)
    scale: float | None = None,
) -> jnp.ndarray:
    """Oracle attention.  Three lowering paths, all numerically identical:

    - dense:  S <= 4096 — one score matrix (the literal definition);
    - banded: sliding window < Skv — per query tile only the
      [tile_start - window, tile_end) key band is touched (linear cost);
    - flash-style: long full attention — online-softmax scan over KV chunks
      inside a lax.map over query tiles (O(S * chunk) memory).
    """
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    dv = v.shape[-1]          # MLA: value head dim may differ from qk dim
    groups = h // kv
    scale = scale if scale is not None else d ** -0.5

    # GQA via K/V broadcast to H heads (NOT by grouping Q into (KV, G):
    # that reshape breaks GSPMD head-sharding when KV < mesh model size)
    qg = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)   # (B,H,Sq,D)
    kf = jnp.repeat(k.astype(jnp.float32), groups, axis=2).transpose(0, 2, 1, 3)
    vf = jnp.repeat(v.astype(jnp.float32), groups, axis=2).transpose(0, 2, 1, 3)

    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(skv)

    if max(sq, skv) <= _DENSE_LIMIT:
        out = _attention_dense(qg, kf, vf, qpos, kpos, causal, window)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)

    qc = min(_Q_CHUNK, sq)
    n_tiles = sq // qc
    assert sq % qc == 0, f"Sq={sq} not divisible by query tile {qc}"

    if window is not None and window < skv:
        band = window + qc  # static key-band width per tile

        def tile(i):
            q_i = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=2)
            lo = jnp.clip(i * qc + q_offset - window + 1, 0, skv - band)
            k_i = jax.lax.dynamic_slice_in_dim(kf, lo, band, axis=2)
            v_i = jax.lax.dynamic_slice_in_dim(vf, lo, band, axis=2)
            qp = jnp.arange(qc) + i * qc + q_offset
            kp = jnp.arange(band) + lo
            return _attention_dense(q_i, k_i, v_i, qp, kp, causal, window)

        out = jax.lax.map(tile, jnp.arange(n_tiles))  # (T,B,H,qc,Dv)
        out = out.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, dv)
        return out.astype(q.dtype)

    # flash-style online softmax over KV chunks
    kc = min(1024, skv)
    assert skv % kc == 0
    n_kv = skv // kc
    kfc = kf.reshape(b, h, n_kv, kc, d).transpose(2, 0, 1, 3, 4)
    vfc = vf.reshape(b, h, n_kv, kc, dv).transpose(2, 0, 1, 3, 4)

    def tile(i):
        q_i = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=2)
        qp = jnp.arange(qc) + i * qc + q_offset

        def kv_step(carry, xs):
            m, l, acc = carry
            j, k_j, v_j = xs
            kp = jnp.arange(kc) + j * kc
            s = jnp.einsum("bhqd,bhsd->bhqs", q_i, k_j)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= kp[None, :] <= qp[:, None]
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum("bhqs,bhsd->bhqd", p, v_j)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, h, qc), NEG_INF),
            jnp.zeros((b, h, qc)),
            jnp.zeros((b, h, qc, dv)),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (jnp.arange(n_kv), kfc, vfc))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(tile, jnp.arange(n_tiles))   # (T,B,H,qc,Dv)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,            # (B, H, D) single new token
    k_cache: jnp.ndarray,      # (B, S, KV, D)
    v_cache: jnp.ndarray,      # (B, S, KV, D)
    *,
    kv_valid: jnp.ndarray,     # (B, S) bool — which cache slots attend
    scale: float | None = None,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, s, kv, _ = k_cache.shape
    groups = h // kv
    scale = scale if scale is not None else d ** -0.5

    # q is tiny: group it (B,KV,G,D); the cache is NEVER copied/expanded —
    # fp32-repeat of a 32k cache costs ~100 GB/device at decode_32k scale.
    qg = ((q.astype(jnp.float32) * scale).astype(k_cache.dtype)
          .reshape(b, kv, groups, d))
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba selective scan
# --------------------------------------------------------------------------
def selective_scan(
    x: jnp.ndarray,    # (B, S, Di)      input sequence
    dt: jnp.ndarray,   # (B, S, Di)      softplus'd step sizes
    A: jnp.ndarray,    # (Di, N)         negative-real state matrix
    Bm: jnp.ndarray,   # (B, S, N)       input->state projection
    Cm: jnp.ndarray,   # (B, S, N)       state->output projection
    D: jnp.ndarray,    # (Di,)           skip
    *,
    init_state: jnp.ndarray | None = None,  # (B, Di, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """y_t = C_t h_t + D x_t,  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    Chunked formulation: lax.scan over sequence chunks carrying the (B,Di,N)
    state, associative scan *within* each chunk.  Materializing full
    (B,S,Di,N) dA/dBx tensors (the textbook parallel form) costs S*N times
    the residual — ~68 GB/layer for Jamba — while the Pallas kernel streams
    the state through VMEM; this oracle matches the kernel's traffic shape.
    """
    bsz, s, di = x.shape
    n = A.shape[-1]
    chunk = min(64, s)
    if s % chunk != 0:
        chunk = s
    n_chunks = s // chunk

    def combine(a, b):
        (a1, b1), (a2, b2) = a, b
        return a1 * a2, a2 * b1 + b2

    def chunk_body(h0, xs):
        xc, dtc, bc, cc = xs              # (B, chunk, ...)
        dtf = dtc.astype(jnp.float32)
        dA = jnp.exp(dtf[..., None] * A[None, None])   # (B,c,Di,N)
        dBx = dtf[..., None] * bc[:, :, None, :].astype(jnp.float32) * (
            xc.astype(jnp.float32)[..., None]
        )
        first = dA[:, 0] * h0 + dBx[:, 0]
        dBx = dBx.at[:, 0].set(first)
        dA = dA.at[:, 0].set(jnp.ones_like(dA[:, 0]))
        _, h = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
        y = jnp.einsum("bsn,bsdn->bsd", cc.astype(jnp.float32), h)
        y = y + D[None, None].astype(jnp.float32) * xc.astype(jnp.float32)
        return h[:, -1], y.astype(x.dtype)

    def to_chunks(t):
        return t.reshape(bsz, n_chunks, chunk, t.shape[-1]).transpose(1, 0, 2, 3)

    h0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((bsz, di, n), jnp.float32)
    )
    hT, ys = jax.lax.scan(
        jax.checkpoint(chunk_body), h0,
        (to_chunks(x), to_chunks(dt), to_chunks(Bm), to_chunks(Cm)),
    )
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, s, di)
    return y, hT


def selective_scan_step(
    x: jnp.ndarray,    # (B, Di)
    dt: jnp.ndarray,   # (B, Di)
    A: jnp.ndarray,    # (Di, N)
    Bm: jnp.ndarray,   # (B, N)
    Cm: jnp.ndarray,   # (B, N)
    D: jnp.ndarray,    # (Di,)
    state: jnp.ndarray,  # (B, Di, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token recurrent step (decode path)."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf[..., None] * A[None])
    new_state = dA * state.astype(jnp.float32) + (
        dtf[..., None] * Bm[:, None, :].astype(jnp.float32) * xf[..., None]
    )
    y = jnp.einsum("bn,bdn->bd", Cm.astype(jnp.float32), new_state)
    y = y + D[None].astype(jnp.float32) * xf
    return y.astype(x.dtype), new_state


# --------------------------------------------------------------------------
# FedAvg weighted aggregation (the server hotspot)
# --------------------------------------------------------------------------
def fedavg_reduce(updates: jnp.ndarray, weights: jnp.ndarray,
                  center: jnp.ndarray | None = None) -> jnp.ndarray:
    """(C, N) x (C,) -> (N,): sum_c w_c * u_c / sum_c w_c, fp32 accumulate.
    With a ``center`` (N,), the fp32 mean of ``updates - center``."""
    wf = weights.astype(jnp.float32)
    u = updates.astype(jnp.float32)
    if center is not None:
        u = u - center.astype(jnp.float32)
    acc = jnp.einsum("c,cn->n", wf, u, precision=jax.lax.Precision.HIGHEST)
    out_dtype = updates.dtype if center is None else jnp.float32
    return (acc / safe_weight_sum(wf)).astype(out_dtype)


def topk_scatter_reduce(
    idx: jnp.ndarray,      # (C, k) int32 sparse positions
    val: jnp.ndarray,      # (C, k) fp sparse values
    weights: jnp.ndarray,  # (C,) aggregation weights
    n_params: int,
) -> jnp.ndarray:
    """O(C·k) oracle for the scatter-accumulate kernel: one XLA scatter-add
    of every client's weighted payload into a zero (N,) accumulator — the
    dense (C, N) per-client matrix is never built.  Duplicate indices within
    a client accumulate; weights follow ``safe_weight_sum`` semantics;
    out-of-range indices (corrupt wire) are dropped — masked explicitly, so
    a negative index cannot wrap numpy-style into a valid coordinate."""
    c, k = idx.shape
    wf = weights.astype(jnp.float32)
    if k == 0 or c == 0:
        return jnp.zeros((n_params,), jnp.float32)
    valid = (idx >= 0) & (idx < n_params)
    safe_idx = jnp.where(valid, idx, 0)
    contrib = jnp.where(valid, val.astype(jnp.float32), 0.0) * wf[:, None]
    acc = (
        jnp.zeros((n_params,), jnp.float32)
        .at[safe_idx.reshape(-1)]
        .add(contrib.reshape(-1))
    )
    return acc / safe_weight_sum(wf)


# --------------------------------------------------------------------------
# int8 block quantization (update compression codec)
# --------------------------------------------------------------------------
def quantize_int8(x: jnp.ndarray, block: int = 256) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., N) fp -> (values int8 (..., N), scales fp32 (..., N/block)).
    N % block == 0."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, block)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    """(q int8 (..., N), scales (..., N/block)) -> fp32 (..., N)."""
    qf = q.reshape(*q.shape[:-1], -1, block).astype(jnp.float32)
    return (qf * scale[..., None]).reshape(q.shape)


def collective_pack(x: jnp.ndarray, scales: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    """Compressed-collective pack oracle: quantize one device's partial sum
    against a SHARED (pre-pmax'd) per-block scale.  Unlike ``quantize_int8``
    the scale is an input, not derived from ``x`` — scale choice is a
    collective decision, so every reducing device rounds against the same
    grid and the int8-valued payloads sum exactly.  int32 container: the
    psum accumulator dtype (values fit int8; |q| <= 127)."""
    xf = x.astype(jnp.float32).reshape(-1, block)
    sf = scales.astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / sf[:, None]), -127, 127)
    return q.reshape(-1).astype(jnp.int32)


def collective_unpack(q: jnp.ndarray, scales: jnp.ndarray, block: int = 256) -> jnp.ndarray:
    """Fused post-psum dequant oracle: int32 payload (one device's pack or
    the psum of many) * shared block scales -> fp32."""
    qf = q.reshape(-1, block).astype(jnp.float32)
    return (qf * scales.astype(jnp.float32)[:, None]).reshape(-1)


def dequant_reduce(
    q: jnp.ndarray,        # (C, N) int8 wire payload
    scales: jnp.ndarray,   # (C, N/block) fp32 block scales
    weights: jnp.ndarray,  # (C,) aggregation weights
    block: int = 256,
) -> jnp.ndarray:
    """Fused-kernel oracle: dequantize every client row, weighted mean."""
    c, n = q.shape
    x = q.astype(jnp.float32).reshape(c, n // block, block) * (
        scales.astype(jnp.float32)[:, :, None]
    )
    wf = weights.astype(jnp.float32)
    acc = jnp.einsum("c,cn->n", wf, x.reshape(c, n),
                     precision=jax.lax.Precision.HIGHEST)
    return acc / safe_weight_sum(wf)


# --------------------------------------------------------------------------
# TopK selection: an exact magnitude threshold, no sort
# --------------------------------------------------------------------------
TOPK_PASS_BITS = 4  # threshold bits settled per counting pass: 15 probes a pass


def topk_keys(x: jnp.ndarray) -> jnp.ndarray:
    """int32 keys that order as |x| does: the fp32 bits of |x| plus one (up
    to 0x7F800001 for inf), NaN 0, below every number; -0 keys as +0.
    The order of a stable sort of -|x|."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32) & 0x7FFFFFFF
    return jnp.where(bits > 0x7F800000, 0, bits + 1)


@functools.partial(jax.jit, static_argnames="k")
def topk_threshold(key: jnp.ndarray, k: int) -> jnp.ndarray:
    """(C, ...) keys -> (C,) the k-th largest key of each row: the largest
    t with count(key >= t) >= k.  Built from the top bit down,
    ``TOPK_PASS_BITS`` bits a pass; each pass counts the row against its
    2**TOPK_PASS_BITS - 1 probes in one fused reduction.  Jitted, with the
    passes in one unrolled loop, so that a model's leaves of one shape
    trace and lower it once."""
    axes = tuple(range(1, key.ndim))

    def settle(t, low, width):
        # counts fall as the probe rises, so the number of probes that still
        # reach k is the highest step of these bits that t can take
        reach = [
            jnp.sum(key >= t + (j << low), axis=axes, keepdims=True, dtype=jnp.int32) >= k
            for j in range(1, 1 << width)
        ]
        return t + (sum(r.astype(jnp.int32) for r in reach) << low)

    # keys < 2**31: bits 30..0 to settle, the top ones first
    passes, top = divmod(31, TOPK_PASS_BITS)
    t = jnp.zeros(key.shape[:1] + (1,) * len(axes), jnp.int32)
    t = settle(t, TOPK_PASS_BITS * passes, top)
    t = jax.lax.fori_loop(
        0, passes,
        lambda i, t: settle(t, TOPK_PASS_BITS * (passes - 1 - i), TOPK_PASS_BITS),
        t, unroll=True,
    )
    return t.reshape(key.shape[:1])


def topk_select(x: jnp.ndarray, k: int):
    """(..., n) -> (idx (..., k) int32, val (..., k), residual (..., n)): the
    k largest |x| of each row, equal magnitudes to the lower index, their
    positions ascending; ``val = x[idx]`` and ``residual`` is ``x`` with
    them zeroed.  Ties: of the keys equal to the threshold, the first
    ``k - count(key > t)`` in index order (an inclusive cumsum).  The j-th
    kept position is the first whose running count of kept reaches j: a
    binary search of that count for every j."""
    lead, n = x.shape[:-1], x.shape[-1]
    rows = x.reshape(-1, n)
    key = topk_keys(rows)
    t = topk_threshold(key, k)[:, None]
    above, at = key > t, key == t
    need = k - jnp.sum(above, axis=1, keepdims=True, dtype=jnp.int32)
    keep = above | (at & (jnp.cumsum(at, axis=1, dtype=jnp.int32) <= need))

    kept = jnp.cumsum(keep, axis=1, dtype=jnp.int32)  # kept[:, -1] == k
    target = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], k), 1) + 1
    idx = jnp.zeros_like(target)  # grows to the count of positions with kept < target
    step = 1 << (n.bit_length() - 1)
    while step:
        probe = idx + step
        at_probe = jnp.minimum(probe, n) - 1
        below = jnp.take_along_axis(kept, at_probe, axis=1, mode="promise_in_bounds") < target
        idx = jnp.where((probe <= n) & below, probe, idx)
        step >>= 1
    val = jnp.take_along_axis(rows, idx, axis=1)
    residual = jnp.where(keep, jnp.zeros((), x.dtype), rows)
    return idx.reshape(lead + (k,)), val.reshape(lead + (k,)), residual.reshape(x.shape)
