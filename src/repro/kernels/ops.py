"""Public kernel entry points with backend dispatch.

Models and codecs call these.  On a TPU backend they run the Pallas
kernels; on any other backend (the CPU the tests run on) they run the
pure-jnp oracles in ref.py.  ``set_impl`` lets tests force either path
(the ``REPRO_KERNEL_IMPL`` env var sets the same switch at import, which
is how CI forces the Pallas bodies through interpret mode on its CPU
runners), and ``interpret=True`` runs the Pallas kernel bodies on CPU for
the per-kernel allclose tests.
"""
from __future__ import annotations

import os

from functools import partial

import jax
import jax.numpy as jnp

from . import ref

_IMPL = os.environ.get("REPRO_KERNEL_IMPL", "auto").strip()
if _IMPL not in ("auto", "pallas", "reference"):
    # fail loud: a typo here would silently turn the CI pallas-interpret job
    # into a ref.py run that tests zero kernel bodies
    raise ValueError(
        f"REPRO_KERNEL_IMPL={_IMPL!r}: expected auto | pallas | reference"
    )


def set_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("auto", "pallas", "reference")
    _IMPL = impl


def _use_pallas() -> bool:
    if _IMPL == "pallas":
        return True
    if _IMPL == "reference":
        return False
    return jax.default_backend() == "tpu"


# ---------------- attention ----------------
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0, interpret=False):
    if _use_pallas() or interpret:
        from .flash_attention import flash_attention as fa

        b, sq, h, d = q.shape
        # kernel needs MXU-aligned tiles; fall back for tiny/ragged shapes
        if sq % 128 == 0 and k.shape[1] % 128 == 0 and d % 8 == 0:
            return fa(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                interpret=interpret or jax.default_backend() != "tpu",
            )
    return ref.attention(q, k, v, causal=causal, window=window, q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, *, kv_valid, interpret=False):
    if _use_pallas() or interpret:
        from .decode_attention import decode_attention as da

        b, s, kv, d = k_cache.shape
        if s % 128 == 0 and d % 8 == 0:
            return da(
                q, k_cache, v_cache, kv_valid=kv_valid,
                interpret=interpret or jax.default_backend() != "tpu",
            )
    return ref.decode_attention(q, k_cache, v_cache, kv_valid=kv_valid)


# ---------------- mamba scan ----------------
def selective_scan(x, dt, A, B, C, D, *, init_state=None, interpret=False):
    if _use_pallas() or interpret:
        from .selective_scan import selective_scan as ss

        if x.shape[1] % 128 == 0:
            return ss(
                x, dt, A, B, C, D, init_state=init_state,
                interpret=interpret or jax.default_backend() != "tpu",
            )
    return ref.selective_scan(x, dt, A, B, C, D, init_state=init_state)


selective_scan_step = ref.selective_scan_step  # trivially small; no kernel


# ---------------- FL aggregation ----------------
def _denormalize(out, weights):
    """Undo the reduce kernels' internal safe_weight_sum normalization,
    turning the weighted mean back into the weighted SUM — the group-partial
    form the mixed-codec engine combines under ONE fleet-wide denominator.
    Exact for the all-zero-weight case (0 * 1 == 0 on both forms)."""
    from repro.utils.pytree import safe_weight_sum

    return out * safe_weight_sum(weights.astype(jnp.float32)).astype(out.dtype)


def fedavg_reduce(updates, weights, center=None, *, interpret=False,
                  normalize=True):
    """(C, N) x (C,) -> (N,) weighted mean; with a ``center`` (N,), the fp32
    mean of ``updates - center``, without writing the difference."""
    if _use_pallas() or interpret:
        from .fedavg_reduce import fedavg_reduce as fr

        # the kernel covers a tail tile itself: no shape gate
        out = fr(
            updates, weights, center,
            interpret=interpret or jax.default_backend() != "tpu",
        )
    else:
        out = ref.fedavg_reduce(updates, weights, center)
    return out if normalize else _denormalize(out, weights)


def dequant_reduce(q, scales, weights, block: int = 256, *, interpret=False,
                   normalize=True):
    """Fused server-side decode: int8 payload (C,N) + scales -> (N,) mean.
    N % block == 0 (the encoder pads); the kernel tile-pads beyond."""
    if _use_pallas() or interpret:
        from .dequant_reduce import dequant_reduce as dr

        out = dr(
            q, scales, weights, block=block,
            interpret=interpret or jax.default_backend() != "tpu",
        )
    else:
        out = ref.dequant_reduce(q, scales, weights, block=block)
    return out if normalize else _denormalize(out, weights)


# count of sparse-path dispatches (trace-time): benchmarks/compression_bench
# --smoke asserts this moves when TopK aggregates, so the scatter path cannot
# silently regress to densify-then-reduce
_TOPK_SPARSE_CALLS = 0
# count of dispatches that took the VMEM-resident Pallas branch (vs the XLA
# scatter-add oracle).  Segmented codecs call this reduce once per segment,
# so the `n_params <= MAX_N_PARAMS` gate below sees seg.size — a model whose
# TOTAL size is over budget still takes the Pallas path for every in-budget
# segment; tests pin that per-segment dispatch moves this counter where the
# monolithic flat vector would not.
_TOPK_PALLAS_CALLS = 0


def topk_sparse_calls() -> int:
    return _TOPK_SPARSE_CALLS


def topk_pallas_calls() -> int:
    return _TOPK_PALLAS_CALLS


def topk_scatter_reduce(idx, val, weights, n_params: int, *, interpret=False,
                        normalize=True):
    """Sparse TopK aggregation: (C,k) idx/val + (C,) weights -> (N,) mean.

    O(C·k) on every branch — the Pallas kernel keeps the (N,) accumulator
    VMEM-resident (so it only runs when N fits); above that, the XLA
    scatter-add oracle.  Neither materializes a dense (C, N) matrix.
    ``n_params`` is whatever span the caller reduces — the whole flat
    update, or one segment of a ``SegmentMap``-structured one — so the
    VMEM gate is per-call, i.e. per segment for segmented codecs.
    """
    global _TOPK_SPARSE_CALLS, _TOPK_PALLAS_CALLS
    _TOPK_SPARSE_CALLS += 1
    if _use_pallas() or interpret:
        # the kernel file owns its VMEM budget; the dispatch gate is derived
        # from it (fedlint audits that the two stay consistent)
        from .scatter_reduce import MAX_N_PARAMS, topk_scatter_reduce as sr

        if n_params <= MAX_N_PARAMS:
            _TOPK_PALLAS_CALLS += 1
            out = sr(
                idx, val, weights, n_params,
                interpret=interpret or jax.default_backend() != "tpu",
            )
            return out if normalize else _denormalize(out, weights)
    out = ref.topk_scatter_reduce(idx, val, weights, n_params)
    return out if normalize else _denormalize(out, weights)


def topk_select_rows(xs, ks, *, interpret=False):
    """The TopK encoder's selection, for every leaf of an update at once:
    leaves ``xs[i]`` (..., n_i), one leading shape, and their ``ks[i]`` ->
    a list of (idx (..., k_i) int32, the k_i largest |x| of each row in
    ascending index order, equal magnitudes to the lower index; val =
    x[idx]; x with those coordinates zeroed).  Both branches find the k by
    an exact magnitude threshold and agree bit for bit; the kernel compacts
    every leaf in one call and one pass, where the oracle binary-searches a
    running count leaf by leaf."""
    xs, ks = tuple(xs), tuple(ks)
    if (_use_pallas() or interpret) and all(x.dtype == jnp.float32 for x in xs):
        from .topk_select import MAX_K, topk_select_rows as ts

        if sum(ks) <= MAX_K:
            return ts(xs, ks, interpret=interpret or jax.default_backend() != "tpu")
    return [ref.topk_select(x, k) for x, k in zip(xs, ks)]


# ---------------- int8 codec ----------------
# The int8 kernels take any (..., N) with N % block == 0 (the encoders pad
# to a block multiple) and tile-pad beyond it themselves: no shape gate.
def quantize_int8(x, block: int = 256, *, interpret=False):
    if _use_pallas() or interpret:
        from .quantize import quantize_int8 as qz

        return qz(x, block=block, interpret=interpret or jax.default_backend() != "tpu")
    return ref.quantize_int8(x, block=block)


def dequantize_int8(q, scale, block: int = 256, *, interpret=False):
    if _use_pallas() or interpret:
        from .quantize import dequantize_int8 as dq

        return dq(q, scale, block=block, interpret=interpret or jax.default_backend() != "tpu")
    return ref.dequantize_int8(q, scale, block=block)


# ---------------- compressed collective (mesh psum wire) ----------------
def collective_pack(x, scales, block: int = 256, *, interpret=False):
    """Quantize one device's partial weighted sum against a SHARED per-block
    scale (pre-pmax'd across the reducing devices) -> int32 psum payload
    with every value in [-127, 127] (one int8 byte on the wire)."""
    if _use_pallas() or interpret:
        from .collective_quant import collective_pack as cp

        return cp(x, scales, block=block,
                  interpret=interpret or jax.default_backend() != "tpu")
    return ref.collective_pack(x, scales, block=block)


def collective_unpack(q, scales, block: int = 256, *, interpret=False):
    """Fused post-psum dequant: int32 summed payload + shared scales -> fp32."""
    if _use_pallas() or interpret:
        from .collective_quant import collective_unpack as cu

        return cu(q, scales, block=block,
                  interpret=interpret or jax.default_backend() != "tpu")
    return ref.collective_unpack(q, scales, block=block)
