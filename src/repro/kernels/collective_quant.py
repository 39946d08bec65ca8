"""int8 collective pack/unpack (Pallas TPU) for the compressed mesh psum.

The mesh round's hierarchical psum moves each device's *partial weighted
sum* across the interconnect.  ``CompressedPsum`` (core/compression.py)
shrinks that wire: every device quantizes its partial sum against a
block-max scale that is **shared across the reducing devices** (a cheap
``lax.pmax`` of per-256-block absmax runs before the psum), so the int8
payloads are exactly summable in the integer domain — the int32 psum
loses nothing, ``unpack(sum_d pack(x_d))`` equals
``sum_d unpack(pack(x_d))`` up to ONE final fp32 rounding per element
(instead of a requantization per hop) — and one fused dequant after the
last hop recovers the fp32 sum.

Unlike ``quantize.py`` (the uplink codec, which derives its scale from its
own input), both kernels here take the scale as an INPUT: scale choice is
a collective decision, not a local one.  ``pack`` writes the quantized
values into an int32 container — the psum accumulator dtype; the values
themselves fit int8 (|q| <= 127, the wire carries one byte per element),
and the int32 sum cannot overflow below a 2**31/127 ~= 16.9M-device fan-in.

Both are ``quantize.scaled_map`` kernels: one HBM pass over the lane-dense
(N,) operand and its (N/block,) scales, with the same tiling, ceil-division
grid as the uplink quantizer (``quantize.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .quantize import BLOCK, LANES, dequant, scaled_map


def _pack(x, s):
    return jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)


@functools.partial(jax.jit, static_argnames=("block", "bn", "interpret"))
def collective_pack(x, scales, *, block: int = BLOCK, bn: int = LANES * BLOCK,
                    interpret: bool = False):
    """x: (N,) fp32, scales: (N/block,) fp32 (shared, pre-pmax'd) ->
    q int32 (N,) with every value in [-127, 127].  N % block == 0."""
    return scaled_map(_pack, x, scales, jnp.int32, block=block, bn=bn,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "bn", "interpret"))
def collective_unpack(q, scales, *, block: int = BLOCK, bn: int = LANES * BLOCK,
                      interpret: bool = False):
    """q: (N,) int32 (one device's pack, or the psum of many), scales as in
    ``collective_pack`` -> fp32 (N,): the fused post-psum dequant."""
    return scaled_map(dequant, q, scales, jnp.float32, block=block, bn=bn,
                      interpret=interpret)
