"""Update compression codecs — first-class citizens of every execution path.

The paper measures communication as a first-class system cost; these codecs
shrink the client->server payload that the cost model charges for:

- ``Int8Codec``: int8 block quantization (~4x over fp32 wire), via the
  Pallas quantize kernel; decoded server-side through the fused
  dequantize+weighted-reduce kernel (one HBM pass over the int8 payload).
- ``TopKCodec``: top-k sparsification with error feedback (classic gradient
  compression).  Server-side aggregation is O(C·k): the (idx, val) payloads
  feed the scatter-accumulate kernel directly (see the O(C·k) reduce
  contract below) — the dense (C, n_params) delta matrix is never built.
- ``LoRACodec``: low-rank factor wire for matrix-shaped segments — the
  structure-aware codec that makes LLM-scale federated fine-tuning fit the
  paper's smartphone uplink numbers (see the LoRA wire format below).
- ``NullCodec``: identity fp32 wire — the uncompressed baseline with the
  same interface, and the *default* codec of ``RoundSpec``, so the round
  engine has exactly one code path.

The segmented wire contract (``SegmentMap`` / ``StructuredUpdate``)
-------------------------------------------------------------------

Historically every codec operated on ONE flat ``(n_params,)`` fp32 vector.
That representation is now the degenerate case of a *leafwise-segmented*
wire:

- A ``SegmentMap`` is a static tuple of ``Segment(name, shape, offset)``
  records covering ``[0, n_params)`` contiguously — usually one segment per
  model leaf (``SegmentMap.from_tree``), with ``SegmentMap.flat(n)`` as the
  single-segment legacy layout.  It is frozen/hashable python data, so a
  codec carrying one stays a valid jit-static closure constant.
- ``codec.with_segments(segmap)`` returns a segmented copy.  With
  ``segments=None`` (the default) every codec runs the EXACT pre-segment
  flat code path; with a map set, the codec surface becomes per-segment:

  * ``init_client_state`` returns a *tuple* of per-segment state entries
    (``(C, seg.size)`` fp32 residual rows for stateful segments, ``()``
    for stateless ones) instead of one ``(C, n_params)`` buffer — the
    population layer spills/rehydrates these rows leafwise.
  * ``encode``/``decode`` happen per segment (``encode_segment`` /
    ``decode_segment``); the full-update payload is a ``StructuredUpdate``
    — the segment map plus one codec payload per segment.
  * ``transmit_tree`` works leaf-by-leaf when the map matches the delta
    tree (a sharded/fsdp model is never flattened into one replicated
    vector); when it does not match, the flat vector is sliced per
    segment.
  * ``wire_bytes`` is the sum of ``segment_wire_bytes(seg)`` — wire
    accounting composes per segment, and a codec that changes a segment's
    wire (LoRA) restates exactly that segment's cost.
  * ``aggregate_batch`` reduces column-blocks per segment through the same
    kernels as before — and because each block is ``seg.size`` wide, the
    VMEM-budget dispatch in ``kernels/ops.py`` is consulted *per segment*:
    a model whose total ``n_params`` exceeds ``scatter_reduce.MAX_N_PARAMS``
    can still take the Pallas scatter path segment-by-segment.

  Bitwise parity: a single-segment map (``SegmentMap.flat``) produces
  bit-identical results to the legacy flat path for Null/Int8/TopK on all
  three execution modes — the per-segment driver degenerates to the flat
  code applied to the whole-vector slice (pinned in
  ``tests/test_structured_update.py``).

The LoRA wire format (``LoRACodec``)
------------------------------------

Per segment, the wire is either the low-rank factorization or the wrapped
fallback codec:

- **Matrix segments** (``len(seg.shape) >= 2``, folded to
  ``(prod(shape[:-1]), shape[-1])``, and strictly cheaper than dense at the
  effective rank ``r = min(rank, m, n)``): the delta block ``X`` ships as
  PowerSGD-style factors ``A (m, r)`` (orthonormalized ``X @ q``) and
  ``B (r, n) = A.T @ X``, each encoded by ``factor_codec`` (e.g. Int8 on
  the factors) — ``segment_wire_bytes = factor_codec.wire_bytes(m*r) +
  factor_codec.wire_bytes(r*n)``.  The random projection ``q`` is derived
  from ``(seed, seg.offset)`` only, so server and clients agree on it
  without it ever crossing the wire.  The reconstruction ``A @ B`` is what
  the server decodes; the factorization error feeds back through the
  per-segment residual rows, so it telescopes across rounds exactly like
  TopK's untransmitted coordinates.
- **Non-matrix segments** (biases, norm scales, or matrices too small to
  win): delegate wholesale to ``fallback`` (default Int8) — encode, state,
  and wire accounting.

The O(C·k) TopK reduce contract
-------------------------------

- **Payload layout**: per client, ``idx`` (k,) int32 positions and ``val``
  (k,) fp32 values, 8k wire bytes.  The encoder is deterministic (equal
  magnitudes tie-break toward the lower index) and emits indices in
  canonical ascending order, so a given delta yields bit-identical wire
  bytes under jit and eager alike.  It selects by an exact magnitude
  threshold found in counting passes over the row, never by a sort
  (``ops.topk_select_rows``, every segment's rows in one call).
- **Duplicate-index semantics**: our encoder emits distinct indices, but
  every consumer (``decode``, ``decode_batch``, ``reduce``, the Pallas
  kernel and its oracle) treats duplicates as scatter-ADD — a foreign
  payload with repeated indices means the same thing on every path.
- **Reduce paths**: ``aggregate_batch`` (jit-parallel engine) scatter-
  reduces the encoded payload and updates the error-feedback state by
  zeroing the transmitted coordinates — O(C·k), no dense decode;
  ``transmit_tree`` (mesh shard_map / sequential scan) decodes one
  client's (n_params,) vector at a time, never a (C, n_params) matrix;
  ``Strategy.aggregate_fit`` scatter-reduces serialized wire payloads when
  the whole fleet shipped TopK.  Under a segment map every bound holds
  per segment with k = k_of(seg.size).
- **When densify still applies**: ``decode_batch`` exists for callers that
  explicitly want the dense per-client matrix — nothing on any reduce path
  calls it.  The fused kernel additionally requires the (n_params,)
  accumulator to fit VMEM; above ``scatter_reduce.MAX_N_PARAMS`` (derived
  from the kernel file's declared ``VMEM_BUDGET_ELEMS``) the dispatch
  falls back to the XLA scatter-add oracle, which is still O(C·k).

Mixed-batch group semantics (``MixedCodec``)
--------------------------------------------

A heterogeneous fleet (some clients on TopK, some Int8, some fp32) runs
inside ONE jitted ``round_step`` through ``MixedCodec``: a codec *bank*
plus a static per-client group assignment (e.g. derived once from
``BandwidthCodecPolicy`` over the fleet's ``DeviceProfile``s).  The
contract extends the O(C·k) reduce contract group-wise:

- **Trace-time partition**: the assignment is static python data, so the
  client axis is partitioned into per-codec groups when the round step is
  traced — every group is a fixed, shape-static slice of the batch, and
  each group's encode + reduce runs on its own kernel path (TopK group →
  scatter-accumulate, Int8 group → fused dequant+reduce, Null group →
  ``fedavg_reduce`` on the flat surface / the leafwise mean on the pytree
  surface).  The TopK group is still O(C_g·k): its payload is never
  densified (``decode_batch`` stays off every mixed path too).
- **One denominator**: each group contributes its *partial weighted sum*
  (the group mean scaled back by the group's weight mass); the groups'
  partials combine into one mean with a single ``safe_weight_sum``
  denominator over the whole fleet, so the result equals a flat weighted
  mean of the per-client decoded deltas up to fp rounding (the partials
  are recovered as group-mean x weight mass) — an all-zero-weight group
  contributes exactly zero, never NaNs.
- **Per-group state**: ``init_client_state`` returns a *tuple* pytree, one
  entry per bank codec — residual rows only for the groups whose codec
  carries error feedback ((C_g, n_params) fp32 flat, or the per-segment
  tuple for a segmented group codec), ``()`` for Null groups — carried
  opaquely through the uniform ``round_step`` signature on the
  vmap-parallel and sequential paths alike.
- **Segment maps thread through group construction**: bank codecs may be
  segmented (``MixedCodec.with_segments`` maps the whole bank) — a LoRA
  group and an Int8 group coexist in one fleet.  Codecs carrying
  *different* explicit maps are rejected at build time (the client axis
  shares one model, so there is exactly one valid leaf layout).
- **Per-group wire accounting**: ``wire_bytes`` returns one uplink size
  per client (the codec its group ships, segmented codecs included),
  which is what ``CostModel.round_costs`` charges a mixed fleet.
- The mesh shard_map path is NOT supported for ``MixedCodec`` (an SPMD
  program cannot run a different wire format per device);
  ``make_round_step`` rejects the combination at build time.

Codecs operate on the *delta* (client params - global params), which is
small-magnitude and quantizes well.  The ``UpdateCodec`` base class defines
the full surface the engine and protocol layer program against:

- ``init_client_state(n_clients, n_params)`` — the codec-owned per-client
  state pytree carried across rounds by ``round_step``.  Error-feedback
  codecs return fp32 residual rows ((C, n_params) flat, or a per-segment
  tuple under a segment map); ``NullCodec`` returns an empty pytree (no
  state is allocated for the uncompressed wire).
- ``aggregate_batch(deltas, weights, state)`` — the batched (C, N) path
  used inside the jitted parallel round step: fold the residual in, encode,
  reduce straight off the *encoded* payload (for Int8 the fused
  dequant+reduce kernel never materializes the fp32 (C, N) matrix), and
  return the new residual state.
- ``transmit_tree(delta_tree, state_row)`` — the per-client path used
  inside the mesh ``shard_map`` manual region and the sequential scan:
  what the server would decode from this one client's uplink, plus the
  client's next state row.  ``NullCodec`` overrides it to the identity so
  sharded models never round-trip through a flat replicated vector.
- ``wire_payload(enc)`` / ``from_wire(payload)`` — the exact arrays that
  cross the wire (Int8 trims encoder padding; the receiver re-pads), used
  by the protocol layer's ``CompressedParameters`` serialization.  Under a
  segment map the per-segment hooks ``segment_wire_payload`` /
  ``segment_from_wire`` serialize each ``StructuredUpdate`` payload; the
  protocol layer namespaces the fields ``s{i}.<key>``.
- ``wire_bytes(n)`` — the per-client uplink charge; accepts an int or a
  vector of per-client sizes so ``CostModel.round_costs`` can account for
  a heterogeneous fleet where every client ships a different payload.
"""
from __future__ import annotations

import dataclasses
import math
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.utils.pytree import (
    safe_weight_sum,
    tree_flatten_to_vector,
    tree_sub,
    tree_unflatten_from_vector,
)

PyTree = Any


# ---------------- segment map: the static leaf layout of an update ----------------
@dataclass(frozen=True)
class Segment:
    """One contiguous span of the flat update: a leaf's shape at an offset.

    Static python data (hashable): codecs carry segments as jit-closure
    constants, so every field is a plain int/str/tuple.
    """

    name: str
    shape: tuple
    offset: int

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(int(d) for d in self.shape))
        object.__setattr__(self, "offset", int(self.offset))

    @property
    def size(self) -> int:
        return math.prod(self.shape) if self.shape else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def matrix_shape(self) -> tuple:
        """The 2-D view structured codecs factorize: leading axes fold into
        rows — (..., m, n) -> (prod(leading) * m, n).  A stacked-expert MoE
        leaf (E, d_in, d_out) is E matrices sharing the output basis, which
        is exactly the fold a low-rank factorization wants."""
        assert self.ndim >= 2, f"segment {self.name!r} has no matrix view"
        return (math.prod(self.shape[:-1]), int(self.shape[-1]))


@dataclass(frozen=True)
class SegmentMap:
    """A static, contiguous tuple of ``Segment``s covering [0, n_params).

    ``flat(n)`` is the single-segment legacy layout; ``from_tree`` builds
    one segment per model leaf in ``tree_flatten`` order (the same order
    ``tree_flatten_to_vector`` concatenates), so offsets line up with the
    flat vector bitwise.
    """

    segments: tuple = ()

    def __post_init__(self):
        object.__setattr__(self, "segments", tuple(self.segments))
        off = 0
        for seg in self.segments:
            assert seg.offset == off, (
                f"segment {seg.name!r} at offset {seg.offset}, expected {off}"
                " — segments must tile the flat vector contiguously"
            )
            off += seg.size

    @classmethod
    def flat(cls, n_params: int) -> "SegmentMap":
        return cls((Segment("flat", (n_params,), 0),))

    @classmethod
    def from_tree(cls, tree: PyTree) -> "SegmentMap":
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        segs, off = [], 0
        for path, leaf in flat:
            seg = Segment(jax.tree_util.keystr(path) or "leaf", tuple(leaf.shape), off)
            segs.append(seg)
            off += seg.size
        return cls(tuple(segs))

    @property
    def n_params(self) -> int:
        return sum(s.size for s in self.segments)

    def __iter__(self):
        return iter(self.segments)

    def __len__(self) -> int:
        return len(self.segments)

    def __getitem__(self, i):
        return self.segments[i]

    def matches_leaves(self, leaves) -> bool:
        """Do these pytree leaves line up 1:1 with the segments (same count,
        same shapes, tree_flatten order)?  When true, segmented codecs work
        leaf-by-leaf and never build the flat (n_params,) vector."""
        return len(leaves) == len(self.segments) and all(
            tuple(leaf.shape) == seg.shape
            for leaf, seg in zip(leaves, self.segments)
        )

    def split(self, vec: jnp.ndarray):
        """Slice a flat (n_params,) vector into per-segment vectors."""
        return [vec[s.offset : s.offset + s.size] for s in self.segments]


@dataclass(frozen=True, eq=False)
class StructuredUpdate:
    """A segmented wire payload: one codec payload per segment.

    Registered as a pytree (segments are static aux data), so it crosses
    jit boundaries and ``jax.tree`` transforms transparently.
    """

    segments: SegmentMap
    payloads: tuple


jax.tree_util.register_pytree_node(
    StructuredUpdate,
    lambda su: (su.payloads, su.segments),
    lambda segs, payloads: StructuredUpdate(segs, tuple(payloads)),
)


class UpdateCodec:
    """Base codec: error-feedback residual state + flat-vector wire.

    Subclasses implement the wire format (``encode``/``decode`` and their
    batched variants, ``reduce``, ``_wire_bytes_scalar``); the state and
    transport machinery below is shared.  ``NullCodec`` overrides the state
    hooks to be stateless/identity.

    With ``segments`` set (see the module docstring's segmented wire
    contract) the public surface dispatches per segment through the
    ``*_segment`` hooks; their defaults apply the flat wire format to each
    segment's slice, so Null/Int8/TopK are segment-ready without further
    overrides and a single flat segment reproduces the legacy path bitwise.
    """

    # dataclass subclasses redeclare this as a field; plain access must work
    segments: SegmentMap | None = None

    def with_segments(self, segments: SegmentMap) -> "UpdateCodec":
        """A copy of this codec bound to a static segment map."""
        if dataclasses.is_dataclass(self):
            return dataclasses.replace(self, segments=segments)
        raise TypeError(f"{type(self).__name__} cannot carry a segment map")

    def segment_map(self, n_params: int | None = None) -> SegmentMap:
        if self.segments is not None:
            if n_params is not None:
                assert self.segments.n_params == n_params, (
                    f"{type(self).__name__} segment map covers "
                    f"{self.segments.n_params} params, caller has {n_params}"
                )
            return self.segments
        assert n_params is not None, "flat codec needs n_params for a map"
        return SegmentMap.flat(n_params)

    # ---- per-client state (carried by round_step across rounds) ----
    def init_client_state(self, n_clients: int, n_params: int) -> PyTree:
        """Zero error-feedback state: one flat fp32 residual per client, or
        (under a segment map) a tuple of per-segment residual rows."""
        if self.segments is not None:
            self.segment_map(n_params)
            return tuple(
                self.init_segment_state(n_clients, seg) for seg in self.segments
            )
        return self._init_flat_state(n_clients, n_params)

    def _init_flat_state(self, n_clients: int, n_params: int) -> PyTree:
        return jnp.zeros((n_clients, n_params), jnp.float32)

    def init_segment_state(self, n_clients: int, seg: Segment) -> PyTree:
        return self._init_flat_state(n_clients, seg.size)

    def segment_stateful(self, seg: Segment) -> bool:
        return bool(jax.tree_util.tree_leaves(self.init_segment_state(1, seg)))

    def carries_client_state(self, n_params: int = 1) -> bool:
        """Whether this codec owns round-to-round per-client state.

        The population layer's ``CohortState`` consults this: a stateless
        codec gathers ``()`` and spills nothing, a stateful one gathers a
        dense residual row per sampled client.  Probes a one-client state
        rather than trusting subclasses to remember a flag.
        """
        if self.segments is not None:
            n_params = self.segments.n_params
        return bool(jax.tree_util.tree_leaves(
            self.init_client_state(1, n_params)
        ))

    # ---- batched (C, N) surface: the jitted parallel round step ----
    def aggregate_updates(
        self, client_params: PyTree, global_params: PyTree,
        weights: jnp.ndarray, state,
    ):
        """Full aggregation of vmapped client params -> (avg params, state).

        Default (flat): flatten per-client deltas to the (C, n_params) wire
        layout and aggregate off the encoded payload (``aggregate_batch``).
        ``NullCodec`` overrides this leafwise so the uncompressed engine
        never materializes the flat fp32 matrix.

        Segmented: when the map matches the model leaves, each leaf's
        (C, seg.size) delta block aggregates independently — the full
        (C, n_params) concat is never built; otherwise the flat matrix is
        sliced per segment (bitwise-equal column spans).
        """
        if self.segments is None:
            flat_global = tree_flatten_to_vector(global_params)
            deltas = jax.vmap(
                lambda p: tree_flatten_to_vector(p) - flat_global
            )(client_params)
            avg_delta, new_state = self.aggregate_batch(deltas, weights, state)
            return (
                tree_unflatten_from_vector(flat_global + avg_delta, global_params),
                new_state,
            )

        segs = self.segment_map()
        leaves_g, treedef = jax.tree_util.tree_flatten(global_params)
        if segs.matches_leaves(leaves_g):
            leaves_c = jax.tree_util.tree_flatten(client_params)[0]
            blocks = [
                lc.astype(jnp.float32).reshape(lc.shape[0], -1)
                - lg.astype(jnp.float32).reshape(-1)
                for lc, lg in zip(leaves_c, leaves_g)
            ]
            means, new_state = self.aggregate_segment_blocks(blocks, weights, state, segs)
            new_leaves = [
                (lg.astype(jnp.float32) + m.reshape(lg.shape)).astype(lg.dtype)
                for m, lg in zip(means, leaves_g)
            ]
            return jax.tree_util.tree_unflatten(treedef, new_leaves), new_state

        flat_global = tree_flatten_to_vector(global_params)
        self.segment_map(flat_global.shape[0])
        deltas = jax.vmap(
            lambda p: tree_flatten_to_vector(p) - flat_global
        )(client_params)
        avg_delta, new_state = self.aggregate_batch(deltas, weights, state)
        return (
            tree_unflatten_from_vector(flat_global + avg_delta, global_params),
            new_state,
        )

    def aggregate_batch(self, deltas: jnp.ndarray, weights: jnp.ndarray, state):
        """(C, N) deltas + state -> (weighted-mean decoded delta (N,), new state).

        Error feedback in, encode, reduce off the encoded payload; what was
        not transmitted becomes the next residual, so the compression error
        telescopes across rounds instead of accumulating.  Under a segment
        map, each segment's column block reduces independently through
        ``aggregate_segment_batch`` (the per-segment sizes are what the
        kernel dispatch's VMEM budget sees).
        """
        if self.segments is None:
            return self._aggregate_batch_flat(deltas, weights, state)
        segs = self.segment_map(deltas.shape[1])
        parts, new_state = self.aggregate_segment_blocks(
            [deltas[:, seg.offset : seg.offset + seg.size] for seg in segs],
            weights, state, segs,
        )
        return jnp.concatenate(parts), new_state

    def _aggregate_batch_flat(self, deltas, weights, state):
        with jax.named_scope("fl.encode"):
            eff = deltas + state
            enc = self.encode_batch(eff)
            new_state = eff - self.decode_batch(enc)
        return self.reduce(enc, weights), new_state

    def aggregate_segment_blocks(self, blocks, weights, state, segs: SegmentMap):
        """Every segment's (C, seg.size) block -> (means, new state tuple):
        by default each segment on its own (``aggregate_segment_batch``)."""
        out = [
            self.aggregate_segment_batch(b, weights, row, seg)
            for b, row, seg in zip(blocks, state, segs)
        ]
        return [m for m, _ in out], tuple(row for _, row in out)

    def aggregate_segment_batch(self, deltas, weights, state, seg: Segment):
        """One segment's (C, seg.size) block -> (mean (seg.size,), new state).

        Default: the flat wire format applied to the block — which is why a
        single flat segment is bitwise the legacy path.
        """
        return self._aggregate_batch_flat(deltas, weights, state)

    # ---- per-client surface: mesh shard_map region / sequential scan ----
    def transmit_tree(self, delta_tree: PyTree, state_row):
        """One client's uplink: -> (decoded delta tree, new state row).

        The returned tree contains exactly the information that survives the
        wire (encode -> decode); the caller aggregates it, so only codec-
        representable values ever cross the slow inter-pod links.  Under a
        segment map matching the tree, each leaf transmits on its own — a
        sharded model never round-trips through one replicated flat vector.
        """
        if self.segments is None:
            vec = tree_flatten_to_vector(delta_tree)
            seg = Segment("flat", (vec.shape[0],), 0)
            dec, new_row = self.transmit_segment(vec, state_row, seg)
            return tree_unflatten_from_vector(dec, delta_tree), new_row

        segs = self.segment_map()
        leaves, treedef = jax.tree_util.tree_flatten(delta_tree)
        if segs.matches_leaves(leaves):
            decs, rows = self.transmit_segments(
                [leaf.astype(jnp.float32).reshape(-1) for leaf in leaves], state_row, segs
            )
            decs = [d.reshape(lf.shape).astype(lf.dtype) for d, lf in zip(decs, leaves)]
            return jax.tree_util.tree_unflatten(treedef, decs), rows

        vec = tree_flatten_to_vector(delta_tree)
        self.segment_map(vec.shape[0])
        decs, rows = self.transmit_segments(segs.split(vec), state_row, segs)
        return (
            tree_unflatten_from_vector(jnp.concatenate([d.reshape(-1) for d in decs]), delta_tree),
            rows,
        )

    def transmit_segments(self, vecs, state_row, segs: SegmentMap):
        """One client's uplink for every segment: -> (decoded vecs, new
        state tuple); by default each segment on its own
        (``transmit_segment``)."""
        out = [
            self.transmit_segment(v, row, seg) for v, row, seg in zip(vecs, state_row, segs)
        ]
        return [d for d, _ in out], tuple(row for _, row in out)

    def transmit_segment(self, vec: jnp.ndarray, state_row, seg: Segment):
        """One client's uplink for ONE segment: (vec (seg.size,), row) ->
        (decoded (seg.size,), new row).  ``state_row`` is ``()`` for a
        stateless segment."""
        stateful = not isinstance(state_row, tuple)
        eff = vec + state_row if stateful else vec
        enc = self.encode_segment(eff, seg)
        dec = self.decode_segment(enc, seg)
        return dec, (eff - dec if stateful else ())

    # ---- per-segment wire hooks (defaults: the flat format per slice) ----
    def encode_segment(self, vec: jnp.ndarray, seg: Segment):
        return self.encode(vec)

    def decode_segment(self, enc, seg: Segment) -> jnp.ndarray:
        return self.decode(enc)

    def encode_structured(self, delta_vec: jnp.ndarray) -> StructuredUpdate:
        """Flat (n_params,) delta -> per-segment payloads (protocol path)."""
        segs = self.segment_map(int(delta_vec.shape[0]))
        return StructuredUpdate(
            segs,
            tuple(
                self.encode_segment(part, seg)
                for part, seg in zip(segs.split(delta_vec), segs)
            ),
        )

    def decode_structured(self, su: StructuredUpdate) -> jnp.ndarray:
        """Dense (n_params,) fp32 decode of a ``StructuredUpdate``."""
        return jnp.concatenate([
            self.decode_segment(p, seg).reshape(-1).astype(jnp.float32)
            for seg, p in zip(su.segments, su.payloads)
        ])

    # ---- wire serialization hooks (protocol.CompressedParameters) ----
    def wire_payload(self, enc) -> dict:
        """The exact fields that cross the wire (arrays + python scalars)."""
        return dict(enc)

    def from_wire(self, payload: dict) -> dict:
        """Rebuild the decodable payload from ``wire_payload`` fields."""
        return dict(payload)

    def segment_wire_payload(self, payload, seg: Segment) -> dict:
        """Wire fields for ONE segment's payload (protocol layer namespaces
        them ``s{i}.<key>``)."""
        return self.wire_payload(payload)

    def segment_from_wire(self, fields: dict, seg: Segment):
        return self.from_wire(fields)

    # ---- uplink accounting ----
    def _wire_bytes_scalar(self, n_params: int) -> int:
        raise NotImplementedError

    def segment_wire_bytes(self, seg: Segment) -> int:
        """Uplink bytes for ONE segment (the flat format on its slice by
        default; structure-aware codecs restate this per segment)."""
        return self._wire_bytes_scalar(seg.size)

    def wire_bytes(self, n_params):
        """Uplink bytes for an ``n_params``-sized update.

        Accepts an int (homogeneous fleet) or a sequence of per-client sizes
        (heterogeneous accounting) and returns an int or list respectively.
        Under a segment map the scalar is the sum of per-segment wire sizes.
        """
        if self.segments is not None:
            total = sum(self.segment_wire_bytes(seg) for seg in self.segments)
            if isinstance(n_params, (list, tuple, np.ndarray)):
                ns = np.asarray(n_params).reshape(-1)
                for n in ns:
                    self.segment_map(int(n))
                return [total] * len(ns)
            self.segment_map(int(n_params))
            return total
        if isinstance(n_params, (list, tuple, np.ndarray)):
            return [self._wire_bytes_scalar(int(n)) for n in np.asarray(n_params).reshape(-1)]
        return self._wire_bytes_scalar(int(n_params))


@dataclass(frozen=True)
class NullCodec(UpdateCodec):
    """Identity codec: full-precision fp32 wire (the uncompressed baseline).

    Stateless: ``init_client_state`` is empty, ``transmit_tree`` is the
    identity on the delta pytree (no flatten — sharded sequential/fsdp
    models keep their layout), and ``aggregate_batch`` is exactly the fused
    weighted reduce of the uncompressed engine.
    """

    segments: Any = None

    def _wire_bytes_scalar(self, n_params: int) -> int:
        return 4 * n_params

    def _init_flat_state(self, n_clients: int, n_params: int) -> PyTree:
        return ()

    def aggregate_updates(self, client_params, global_params, weights, state):
        """Leafwise fp32 weighted mean — the fp32 wire loses nothing, so the
        uncompressed path never flattens the model into one (C, N) matrix
        (same reasoning as the identity ``transmit_tree``).  Each leaf's
        (C, leaf size) client params go through the fedavg reduce kernel,
        centered on the global leaf: the mean delta comes out in fp32 and
        no (C, leaf size) delta matrix is written."""

        def leaf_mean(xs, g):
            gf = g.astype(jnp.float32)
            mean = ops.fedavg_reduce(xs.reshape(xs.shape[0], -1), weights,
                                     gf.reshape(-1))
            return (gf + mean.reshape(g.shape)).astype(g.dtype)

        # state passes through unchanged (() flat; a tuple of ()s segmented)
        # so the scan carry keeps one stable structure across rounds
        return jax.tree.map(leaf_mean, client_params, global_params), state

    def _aggregate_batch_flat(self, deltas, weights, state):
        return self.reduce(self.encode_batch(deltas), weights), state

    def transmit_tree(self, delta_tree, state_row):
        return delta_tree, state_row

    def encode(self, delta_vec: jnp.ndarray):
        return {"delta": delta_vec.astype(jnp.float32), "n": delta_vec.shape[0]}

    def decode(self, enc) -> jnp.ndarray:
        return enc["delta"]

    def encode_batch(self, deltas: jnp.ndarray):
        return {"delta": deltas.astype(jnp.float32), "n": deltas.shape[1]}

    def decode_batch(self, enc) -> jnp.ndarray:
        return enc["delta"]

    def reduce(self, enc, weights: jnp.ndarray, *, interpret: bool = False):
        return ops.fedavg_reduce(enc["delta"], weights, interpret=interpret)


@dataclass(frozen=True)
class Int8Codec(UpdateCodec):
    block: int = 256
    segments: Any = None

    def _n_scales(self, n_params: int) -> int:
        return -(-n_params // self.block)  # ceil: encode pads to a block multiple

    def _wire_bytes_scalar(self, n_params: int) -> int:
        # int8 payload (pad blocks need not cross the wire: the receiver
        # re-pads from n) + one fp32 scale per ceil(n/block) block
        return n_params + 4 * self._n_scales(n_params)

    def encode(self, delta_vec: jnp.ndarray):
        n = delta_vec.shape[0]
        pad = (-n) % self.block
        padded = jnp.pad(delta_vec, (0, pad))
        q, scale = ops.quantize_int8(padded, block=self.block)
        return {"q": q, "scale": scale, "n": n}

    def decode(self, enc) -> jnp.ndarray:
        vec = ops.dequantize_int8(enc["q"], enc["scale"], block=self.block)
        return vec[: enc["n"]]

    def wire_payload(self, enc) -> dict:
        # pad int8s never cross the wire: trim to n, the receiver re-pads
        return {"q": enc["q"][: enc["n"]], "scale": enc["scale"], "n": enc["n"]}

    def from_wire(self, payload: dict) -> dict:
        n = payload["n"]
        q = jnp.asarray(payload["q"])
        return {
            "q": jnp.pad(q, (0, (-n) % self.block)),
            "scale": jnp.asarray(payload["scale"]),
            "n": n,
        }

    # ---- batched (C, N) wire path used inside the jitted round step ----
    def encode_batch(self, deltas: jnp.ndarray):
        """(C, N) -> q (C, Np) int8 + scales (C, Np/block); Np = padded N.

        Rows are padded to a block multiple, so every quantization block
        stays inside one client row; the kernel quantizes the (C, Np)
        matrix in its own layout.
        """
        n = deltas.shape[1]
        padded = jnp.pad(deltas, ((0, 0), (0, (-n) % self.block)))
        q, scale = ops.quantize_int8(padded, block=self.block)
        return {"q": q, "scale": scale, "n": n}

    def decode_batch(self, enc) -> jnp.ndarray:
        vec = ops.dequantize_int8(enc["q"], enc["scale"], block=self.block)
        return vec[:, : enc["n"]]

    def reduce(self, enc, weights: jnp.ndarray, *, interpret: bool = False):
        """Weighted-mean decode straight off the int8 payload (fused kernel)."""
        avg = ops.dequant_reduce(
            enc["q"], enc["scale"], weights, block=self.block, interpret=interpret
        )
        return avg[: enc["n"]]


@dataclass(frozen=True)
class TopKCodec(UpdateCodec):
    """Keep the k largest-|.| entries; the residual feeds back next round.

    Wire contract (load-bearing for the O(C·k) reduce):

    - selection is DETERMINISTIC: magnitudes tie-break toward the lower
      index (raw ``lax.top_k`` tie order is lowering-dependent), so a given
      delta produces bit-identical payloads under jit and eager alike; the
      k are found by an exact magnitude threshold (``ops.topk_select_rows``),
      a few counting passes over the row instead of a sort of it;
    - payload indices are canonically sorted ascending — reproducible wire
      bytes, and the scatter kernel walks VMEM monotonically;
    - this encoder emits distinct indices, but every consumer treats
      duplicate indices as ACCUMULATE (scatter-add), so foreign payloads
      mean the same thing on all paths;
    - ``reduce`` consumes (idx, val) directly through the scatter-
      accumulate kernel — O(C·k) time and memory, no dense (C, N) matrix;
      ``decode_batch`` remains the explicit densify fallback for callers
      that want the per-client dense matrix (nothing on the reduce or
      error-feedback path does).
    - under a segment map each segment keeps its own k = k_of(seg.size)
      coordinates, and the scatter kernel's VMEM-budget dispatch sees
      seg.size — not the whole model — per reduce call.
    """

    frac: float = 0.01
    segments: Any = None

    def k_of(self, n_params: int) -> int:
        # math.floor, not int(): n_params is static, but this method is
        # jit-reachable and a py-cast here would read as tracer concretization
        return max(1, math.floor(n_params * self.frac))

    def _wire_bytes_scalar(self, n_params: int) -> int:
        return self.k_of(n_params) * 8  # int32 index + fp32 value

    def _encode_residual(self, *effs: jnp.ndarray):
        """Leaves (..., n) -> [(payload, the leaf with the transmitted
        coordinates zeroed: the error-feedback residual)], every leaf's
        selection in one ``ops.topk_select_rows`` call."""
        ks = [self.k_of(e.shape[-1]) for e in effs]
        return [
            ({"idx": idx, "val": val, "n": e.shape[-1]}, residual)
            for e, (idx, val, residual) in zip(effs, ops.topk_select_rows(effs, ks))
        ]

    def encode(self, delta_vec: jnp.ndarray):
        return self._encode_residual(delta_vec)[0][0]

    def decode(self, enc) -> jnp.ndarray:
        # scatter-ADD: duplicate indices accumulate (kernel semantics)
        return jnp.zeros((enc["n"],), enc["val"].dtype).at[enc["idx"]].add(enc["val"])

    def encode_batch(self, deltas: jnp.ndarray):
        return self._encode_residual(deltas)[0][0]  # idx, val: (C, k)

    def decode_batch(self, enc) -> jnp.ndarray:
        """Densify fallback: the dense (C, n) matrix for callers that want
        it — the reduce and error-feedback paths never call this."""
        c = enc["idx"].shape[0]
        rows = jnp.arange(c)[:, None]
        return (
            jnp.zeros((c, enc["n"]), enc["val"].dtype)
            .at[rows, enc["idx"]]
            .add(enc["val"])
        )

    def _aggregate_batch_flat(self, deltas: jnp.ndarray, weights: jnp.ndarray, state):
        """O(C·k) end to end: encode, scatter-reduce straight off the
        payload, and zero the transmitted coordinates out of the error-
        feedback state — TopK transmits exact values, so
        ``eff - decode(enc) == eff`` zeroed at idx; no dense decode."""
        with jax.named_scope("fl.encode"):
            [(enc, new_state)] = self._encode_residual(deltas + state)
        return self.reduce(enc, weights), new_state

    def aggregate_segment_blocks(self, blocks, weights, state, segs: SegmentMap):
        """Every segment's selection in one pass, then each segment's
        scatter reduce: one selection kernel for the whole model."""
        with jax.named_scope("fl.encode"):
            sent = self._encode_residual(*(b + row for b, row in zip(blocks, state)))
        return [self.reduce(enc, weights) for enc, _ in sent], tuple(r for _, r in sent)

    def transmit_segment(self, vec: jnp.ndarray, state_row, seg: Segment):
        """Per-client path (mesh shard_map / sequential scan): the decode
        stays per-client (seg.size,) — never (C, N) — and the next state
        row zeroes the transmitted coordinates."""
        [(enc, new_row)] = self._encode_residual(vec + state_row)
        return self.decode_segment(enc, seg), new_row

    def transmit_segments(self, vecs, state_row, segs: SegmentMap):
        """The per-client path for every segment: one selection call."""
        sent = self._encode_residual(*(v + row for v, row in zip(vecs, state_row)))
        decs = [self.decode_segment(enc, seg) for (enc, _), seg in zip(sent, segs)]
        return decs, tuple(row for _, row in sent)

    def encode_structured(self, delta_vec: jnp.ndarray) -> StructuredUpdate:
        """Per-segment payloads (protocol path), one selection call."""
        segs = self.segment_map(int(delta_vec.shape[0]))
        sent = self._encode_residual(*segs.split(delta_vec))
        return StructuredUpdate(segs, tuple(enc for enc, _ in sent))

    def reduce(self, enc, weights: jnp.ndarray, *, interpret: bool = False):
        # sparse scatter-accumulate straight off the (idx, val) payload
        return ops.topk_scatter_reduce(
            enc["idx"], enc["val"], weights, enc["n"], interpret=interpret
        )


@dataclass(frozen=True)
class LoRACodec(UpdateCodec):
    """Low-rank factor wire for matrix segments; fallback codec elsewhere.

    The wire format is documented in the module docstring ("The LoRA wire
    format").  Config:

    - ``rank``: the rank budget; each matrix segment uses the effective
      rank ``min(rank, m, n)`` of its folded ``matrix_shape``.
    - ``factor_codec``: the codec applied to each factor's flat vector on
      the wire (``Int8Codec`` composes int8 quantization on the factors;
      ``NullCodec`` ships fp32 factors).
    - ``fallback``: the codec that owns non-matrix segments wholesale —
      encode, per-segment state, and wire accounting all delegate.
    - ``power_iters``: subspace iterations of the PowerSGD-style
      factorization (1 = project, orthonormalize, project back).
    - ``seed``: the deterministic projection seed; the per-segment key is
      ``fold_in(key(seed), seg.offset)``, shared by every client and the
      server, so the random basis never crosses the wire.

    This codec is segment-structured by construction: build it with a
    ``SegmentMap`` (``LoRACodec(...).with_segments(SegmentMap.from_tree(params))``).
    The flat-vector surface raises — there is no meaningful rank structure
    in one anonymous flat vector.
    """

    rank: int = 8
    factor_codec: UpdateCodec = NullCodec()
    fallback: UpdateCodec = Int8Codec()
    power_iters: int = 1
    seed: int = 0
    segments: Any = None

    def __post_init__(self):
        assert self.rank >= 1, f"rank must be >= 1, got {self.rank}"
        assert self.power_iters >= 1
        assert self.factor_codec.segments is None, "factor_codec is flat-per-factor"
        assert self.fallback.segments is None, "fallback inherits LoRA's segments"

    # ---- which segments get the low-rank wire ----
    def _eff_rank(self, seg: Segment) -> int:
        m, n = seg.matrix_shape
        return min(self.rank, m, n)

    def _use_lora(self, seg: Segment) -> bool:
        """Low-rank wins when the segment has a matrix view and the factor
        wire is strictly smaller than the dense fallback wire."""
        if seg.ndim < 2:
            return False
        m, n = seg.matrix_shape
        r = min(self.rank, m, n)
        return (
            self.factor_codec._wire_bytes_scalar(m * r)
            + self.factor_codec._wire_bytes_scalar(r * n)
            < self.fallback.segment_wire_bytes(seg)
        )

    def _seg_key(self, seg: Segment):
        return jax.random.fold_in(jax.random.key(self.seed), seg.offset)

    # ---- the factorization (PowerSGD-style, deterministic basis) ----
    def _factorize(self, x: jnp.ndarray, key):
        """(m, n) fp32 -> A (m, r) orthonormal, B (r, n) = A.T @ x."""
        m, n = x.shape
        r = min(self.rank, m, n)
        q = jax.random.normal(key, (n, r), jnp.float32)
        p = x @ q
        for _ in range(self.power_iters - 1):
            p = jnp.linalg.qr(p)[0]
            p = x @ (x.T @ p)
        a = jnp.linalg.qr(p)[0]
        return a, a.T @ x

    # ---- per-segment wire ----
    def encode_segment(self, vec: jnp.ndarray, seg: Segment):
        if not self._use_lora(seg):
            return self.fallback.encode_segment(vec, seg)
        m, n = seg.matrix_shape
        a, b = self._factorize(
            vec.reshape(m, n).astype(jnp.float32), self._seg_key(seg)
        )
        return {
            "a": self.factor_codec.encode(a.reshape(-1)),
            "b": self.factor_codec.encode(b.reshape(-1)),
        }

    def decode_segment(self, enc, seg: Segment) -> jnp.ndarray:
        if not self._use_lora(seg):
            return self.fallback.decode_segment(enc, seg)
        m, n = seg.matrix_shape
        r = self._eff_rank(seg)
        a = self.factor_codec.decode(enc["a"]).reshape(m, r)
        b = self.factor_codec.decode(enc["b"]).reshape(r, n)
        return (a @ b).reshape(-1)

    # ---- per-segment state: residual rows on lora segments, fallback's otherwise ----
    def init_segment_state(self, n_clients: int, seg: Segment) -> PyTree:
        if self._use_lora(seg):
            return jnp.zeros((n_clients, seg.size), jnp.float32)
        return self.fallback.init_segment_state(n_clients, seg)

    # ---- batched aggregation: factorize per client, reduce reconstructions ----
    def aggregate_segment_batch(self, deltas, weights, state, seg: Segment):
        if not self._use_lora(seg):
            return self.fallback.aggregate_segment_batch(deltas, weights, state, seg)
        c = deltas.shape[0]
        m, n = seg.matrix_shape
        r = self._eff_rank(seg)
        with jax.named_scope("fl.encode"):
            eff = deltas.astype(jnp.float32) + state
            x = eff.reshape(c, m, n)
            key = self._seg_key(seg)  # one shared basis: clients and server agree
            a, b = jax.vmap(lambda xi: self._factorize(xi, key))(x)
            # factor wire round-trip (what the server can actually see)
            fa = self.factor_codec.decode_batch(
                self.factor_codec.encode_batch(a.reshape(c, m * r))
            ).reshape(c, m, r)
            fb = self.factor_codec.decode_batch(
                self.factor_codec.encode_batch(b.reshape(c, r * n))
            ).reshape(c, r, n)
            dec = jnp.einsum("cmr,crn->cmn", fa, fb)
        wf = weights.astype(jnp.float32)
        mean = jnp.einsum("c,cmn->mn", wf, dec) / safe_weight_sum(wf)
        return mean.reshape(-1), eff - dec.reshape(c, -1)

    # ---- per-segment serialization: factor payloads namespaced a./b. ----
    def segment_wire_payload(self, payload, seg: Segment) -> dict:
        if not self._use_lora(seg):
            return self.fallback.segment_wire_payload(payload, seg)
        out = {}
        for fk in ("a", "b"):
            for k, v in self.factor_codec.wire_payload(payload[fk]).items():
                out[f"{fk}.{k}"] = v
        return out

    def segment_from_wire(self, fields: dict, seg: Segment):
        if not self._use_lora(seg):
            return self.fallback.segment_from_wire(fields, seg)
        def sub(prefix):
            return self.factor_codec.from_wire({
                k[len(prefix):]: v for k, v in fields.items() if k.startswith(prefix)
            })
        return {"a": sub("a."), "b": sub("b.")}

    # ---- wire accounting: restated per segment (factors, not dense) ----
    def segment_wire_bytes(self, seg: Segment) -> int:
        if not self._use_lora(seg):
            return self.fallback.segment_wire_bytes(seg)
        m, n = seg.matrix_shape
        r = self._eff_rank(seg)
        return (
            self.factor_codec._wire_bytes_scalar(m * r)
            + self.factor_codec._wire_bytes_scalar(r * n)
        )

    # ---- the flat-vector surface is meaningless for a structured codec ----
    def _no_flat_surface(self, name: str):
        raise TypeError(
            f"LoRACodec.{name}: the low-rank wire needs matrix shapes — build "
            "the codec with a SegmentMap (with_segments(SegmentMap.from_tree(params)))"
        )

    def _wire_bytes_scalar(self, n_params: int) -> int:
        self._no_flat_surface("wire_bytes")

    def _init_flat_state(self, n_clients: int, n_params: int):
        self._no_flat_surface("init_client_state")

    def encode(self, delta_vec):
        self._no_flat_surface("encode")

    def decode(self, enc):
        self._no_flat_surface("decode")

    def encode_batch(self, deltas):
        self._no_flat_surface("encode_batch")

    def decode_batch(self, enc):
        self._no_flat_surface("decode_batch")

    def reduce(self, enc, weights, *, interpret: bool = False):
        self._no_flat_surface("reduce")


@dataclass(frozen=True)
class MixedCodec(UpdateCodec):
    """Shape-static per-client codec bank — mixed fleets in ONE jitted round.

    ``codecs`` is the bank (one entry per group); ``assignment`` maps each
    client to a bank index and is *static python data*, so the round step
    partitions the client axis into per-codec groups at trace time (see the
    module docstring's mixed-batch group semantics).  Build one from the
    fleet's measured hardware with ``MixedCodec.from_policy``.

    The batched aggregation surfaces (``aggregate_updates`` /
    ``aggregate_batch``) gather each group's rows with static indices, run
    the group codec's own encode + reduce kernel path, and combine the
    groups' partial weighted sums under a single ``safe_weight_sum``
    denominator.  The per-client surfaces (``encode`` / ``transmit_tree``)
    are deliberately absent: a single client belongs to exactly one group,
    so callers must dispatch through ``groups()`` (the sequential round
    engine does).

    Segment maps thread through group construction: bank codecs may carry
    segment maps (``with_segments`` maps the whole bank), and each group's
    state/encode/reduce then runs that codec's segmented path — a LoRA
    group and an Int8 group coexist in one fleet.  Conflicting explicit
    maps are rejected at build time.

    Population mode is out of scope by construction: the static
    ``assignment`` binds codecs to client-axis *slots*, while a population
    round resamples which client occupies each slot every round —
    ``CohortState`` and the population ``Server`` both reject a MixedCodec
    (per-device codec choice there goes through ``BandwidthCodecPolicy``).
    """

    codecs: tuple = ()
    assignment: tuple = ()

    def __post_init__(self):
        assert self.codecs, "MixedCodec needs a non-empty codec bank"
        assert all(
            0 <= int(g) < len(self.codecs) for g in self.assignment
        ), f"assignment {self.assignment} out of range for {len(self.codecs)} codecs"
        # tuples, not lists: the codec is a static field of RoundSpec and a
        # jit-closure constant, so it must stay hashable
        object.__setattr__(self, "codecs", tuple(self.codecs))
        object.__setattr__(
            self, "assignment", tuple(int(g) for g in self.assignment)
        )
        maps = {c.segments for c in self.codecs if c.segments is not None}
        if len(maps) > 1:
            raise ValueError(
                "MixedCodec bank codecs carry conflicting segment maps — the "
                "client axis shares one model, so every segmented group must "
                "use the same leaf layout (use MixedCodec.with_segments)"
            )

    def with_segments(self, segments: SegmentMap) -> "MixedCodec":
        """Thread one segment map through every group codec in the bank."""
        return dataclasses.replace(
            self, codecs=tuple(c.with_segments(segments) for c in self.codecs)
        )

    @classmethod
    def from_policy(cls, policy, fleet) -> "MixedCodec":
        """Static group assignment from per-device facts.

        ``fleet``: one ``ClientProperties`` / ``DeviceProfile`` (anything
        with ``.uplink_mbps``) per client, in client order; ``policy``: a
        ``BandwidthCodecPolicy``-shaped object.  Equal codecs dedupe into
        one bank entry (frozen dataclasses compare by config)."""
        bank: list = []
        assignment = []
        for props in fleet:
            codec = policy.codec_for(props)
            if codec not in bank:
                bank.append(codec)
            assignment.append(bank.index(codec))
        return cls(codecs=tuple(bank), assignment=tuple(assignment))

    @property
    def n_clients(self) -> int:
        return len(self.assignment)

    def groups(self):
        """-> [(bank_index, codec, client-index list)] for every NON-EMPTY
        group, in bank order.  The index lists are static python data (the
        assignment is a trace-time constant): under jit they become constant
        gathers, so every group is shape-static."""
        return [
            (g, codec, idx)
            for g, codec in enumerate(self.codecs)
            if (idx := [i for i, a in enumerate(self.assignment) if a == g])
        ]

    # ---- per-client state: one entry per bank codec ----
    def init_client_state(self, n_clients: int, n_params: int) -> PyTree:
        assert n_clients == self.n_clients, (
            f"MixedCodec assigns {self.n_clients} clients, got {n_clients}"
        )
        assign = np.asarray(self.assignment, np.int64)
        return tuple(
            codec.init_client_state(int((assign == g).sum()), n_params)
            for g, codec in enumerate(self.codecs)
        )

    # ---- batched pytree surface: the vmap-parallel round step ----
    def aggregate_updates(self, client_params, global_params, weights, state):
        """Group-wise aggregation of vmapped client params.

        Each group's rows are gathered with static indices and aggregated by
        the group's own codec (TopK never densifies its payload, Null never
        flattens the model, a segmented group runs its per-segment path);
        the group means are scaled back to partial weighted sums and
        combined under one fleet-wide denominator."""
        assert weights.shape[0] == self.n_clients, (
            f"batch carries {weights.shape[0]} clients, MixedCodec assigns "
            f"{self.n_clients}"  # a static gather would silently clamp
        )
        wf = weights.astype(jnp.float32)
        wsum = safe_weight_sum(wf)
        total = jax.tree.map(
            lambda g: jnp.zeros(g.shape, jnp.float32), global_params
        )
        new_states = list(state)
        for g, codec, idx in self.groups():
            ia = jnp.asarray(idx)  # static rows -> constant gather under jit
            params_g = jax.tree.map(lambda x: x[ia], client_params)
            avg_g, new_states[g] = codec.aggregate_updates(
                params_g, global_params, wf[ia], state[g]
            )
            wsum_g = jnp.sum(wf[ia])  # group mean * mass = partial sum
            total = jax.tree.map(
                lambda t, a, gp: t
                + (a.astype(jnp.float32) - gp.astype(jnp.float32)) * wsum_g,
                total, avg_g, global_params,
            )
        new_global = jax.tree.map(
            lambda gp, t: (gp.astype(jnp.float32) + t / wsum).astype(gp.dtype),
            global_params, total,
        )
        return new_global, tuple(new_states)

    # ---- batched flat surface ----
    def aggregate_batch(self, deltas: jnp.ndarray, weights: jnp.ndarray, state):
        assert deltas.shape[0] == self.n_clients, (
            f"batch carries {deltas.shape[0]} clients, MixedCodec assigns "
            f"{self.n_clients}"  # a static gather would silently clamp
        )
        wf = weights.astype(jnp.float32)
        total = jnp.zeros((deltas.shape[1],), jnp.float32)
        new_states = list(state)
        for g, codec, idx in self.groups():
            ia = jnp.asarray(idx)
            mean_g, new_states[g] = codec.aggregate_batch(
                deltas[ia], wf[ia], state[g]
            )
            total = total + mean_g.astype(jnp.float32) * jnp.sum(wf[ia])
        return total / safe_weight_sum(wf), tuple(new_states)

    # ---- per-group wire accounting ----
    def wire_bytes(self, n_params):
        """One uplink size per client (its group's codec), in client order.

        Accepts an int (every client ships an ``n_params``-sized update) or
        a per-client vector of sizes; always returns a per-client list —
        a mixed fleet has no single scalar wire size.  Dispatches through
        each group codec's own ``wire_bytes`` so segmented group codecs
        (LoRA) account their structured wire correctly."""
        ns = np.asarray(n_params).reshape(-1)
        if ns.size == 1:
            ns = np.full(self.n_clients, int(ns[0]))
        assert len(ns) == self.n_clients, (
            f"per-client size vector ({len(ns)}) != clients ({self.n_clients})"
        )
        return [
            self.codecs[g].wire_bytes(int(n))
            for g, n in zip(self.assignment, ns)
        ]

    def _wire_bytes_scalar(self, n_params: int) -> int:
        raise TypeError("MixedCodec has no scalar wire size; use wire_bytes")

    def _no_per_client_surface(self, name: str):
        raise TypeError(
            f"MixedCodec.{name}: per-client codec surfaces are group-owned; "
            "dispatch through groups()"
        )

    def encode(self, delta_vec):
        self._no_per_client_surface("encode")

    def decode(self, enc):
        self._no_per_client_surface("decode")

    def encode_batch(self, deltas):
        self._no_per_client_surface("encode_batch")

    def decode_batch(self, enc):
        self._no_per_client_surface("decode_batch")

    def reduce(self, enc, weights, *, interpret: bool = False):
        self._no_per_client_surface("reduce")

    def transmit_tree(self, delta_tree, state_row):
        self._no_per_client_surface("transmit_tree")


@dataclass(frozen=True)
class BandwidthCodecPolicy:
    """Per-device codec selection from the client's measured uplink.

    The Strategy consults this in ``configure_fit`` (the paper's system-cost
    quantification driving an algorithmic decision): slow phone-class
    uplinks get TopK sparsification, mid-tier edge boards get Int8, and
    datacenter-class backbone links ship the full-precision wire.
    """

    topk_below_mbps: float = 30.0       # Pixel-class cellular uplinks
    null_above_mbps: float = 100_000.0  # TPU-class datacenter backbone
    topk: TopKCodec = TopKCodec(frac=0.01)
    int8: Int8Codec = Int8Codec()
    null: NullCodec = NullCodec()

    def codec_for(self, properties) -> UpdateCodec:
        """properties: protocol.ClientProperties (or any .uplink_mbps owner)."""
        if properties.uplink_mbps >= self.null_above_mbps:
            return self.null
        if properties.uplink_mbps < self.topk_below_mbps:
            return self.topk
        return self.int8


# ---------------- compressed collective: the mesh psum wire ----------------
@dataclass(frozen=True)
class CompressedPsum:
    """int8 wire-compressed hierarchical psum for the mesh round path.

    The mesh round's cross-device reduce moves each device's *partial
    weighted sum*; this class is the wire format of that collective —
    the analogue, one layer down, of what ``Int8Codec`` is to the client
    uplink.  Per (segment-shaped) operand:

    1. fold in the per-device error-feedback residual: ``eff = wx + r``;
    2. per-256-block absmax of ``eff``, then ``lax.pmax`` over the client
       axes — a tiny fp32 sidecar (4 bytes per block) that makes the scale
       a COLLECTIVE decision: every device rounds against the same grid,
       so quantization commutes with the sum;
    3. ``kernels.ops.collective_pack``: quantize to int8-valued payloads
       in an int32 container (the accumulator dtype; |q| <= 127, so the
       int32 psum provably cannot overflow below a 2**31/127 ~= 16.9M
       fan-in — any real mesh);
    4. hierarchical ``lax.psum`` of the int payload (pod-inner ordering,
       same hop structure as the fp32 path);
    5. one fused ``collective_unpack`` after the last hop recovers the
       fp32 sum; the weight denominator psums alongside as a 4-byte fp32
       sidecar (the caller's existing ``wsum`` reduce).

    The residual ``eff - unpack(pack(eff))`` stays on the device that
    produced it, so the quantized psum telescopes across rounds exactly
    like the uplink codecs' error feedback.
    """

    block: int = 256

    def shared_scales(self, eff: jnp.ndarray, axes) -> jnp.ndarray:
        """Per-block scales agreed across the reducing devices: pmax of the
        local per-block absmax over every client axis, /127, zero -> 1."""
        absmax = jnp.max(jnp.abs(eff).reshape(-1, self.block), axis=1)
        for ax in reversed(tuple(axes)):
            absmax = jax.lax.pmax(absmax, ax)
        return jnp.where(absmax == 0.0, 1.0, absmax / 127.0)

    def psum(self, wx: jnp.ndarray, residual: jnp.ndarray, axes):
        """One operand's compressed hierarchical psum, inside shard_map.

        ``wx``: (n,) fp32 — this device's partial weighted sum.
        ``residual``: (n,) fp32 — this device's error-feedback carry
        (pass zeros when already folded, or a masked row: the caller owns
        participation semantics).

        Returns ``(total, new_residual)``: the fp32 sum of every device's
        quantized ``wx + residual`` and this device's next residual.
        """
        n = wx.shape[0]
        pad = (-n) % self.block
        eff = wx + residual
        effp = jnp.pad(eff, (0, pad)) if pad else eff
        scales = self.shared_scales(effp, axes)
        q = ops.collective_pack(effp, scales, block=self.block)
        # local dequant: what THIS device's payload contributes to the sum;
        # the gap is next round's residual (error feedback telescopes)
        sent = ops.collective_unpack(q, scales, block=self.block)[:n]
        for ax in reversed(tuple(axes)):
            q = jax.lax.psum(q, ax)
        total = ops.collective_unpack(q, scales, block=self.block)[:n]
        return total, eff - sent

    # ---- collective wire accounting (audited by fedlint) ----
    def collective_bytes(self, n: int) -> int:
        """Physical bytes ONE device moves across ONE hop for an n-element
        operand: int8 payload (1 B/elem) + the fp32 per-block scale sidecar
        (rides the pmax) + the 4-byte fp32 weight denominator.  The int32
        container is accumulator dtype, not wire format — the wire carries
        one byte per element.  ``CostModel.collective_bytes`` multiplies
        this by the mesh's hop/tier structure."""
        return int(n) + 4 * math.ceil(int(n) / self.block) + 4


def fp32_collective_bytes(n: int) -> int:
    """The uncompressed counterpart of ``CompressedPsum.collective_bytes``:
    fp32 payload + the same 4-byte weight-denominator sidecar per hop."""
    return 4 * int(n) + 4


@contextmanager
def ban_topk_densify():
    """Guard for the O(C·k) reduce contract: within the block, ANY call to
    ``TopKCodec.decode_batch`` (the explicit densify fallback) raises.
    Tests and the compression benchmark wrap aggregation paths in this to
    prove the sparse scatter reduce never regresses to densify-then-reduce.
    """
    def _boom(self, enc):
        raise AssertionError(
            "TopKCodec.decode_batch called on the aggregation path — the "
            "O(C·k) scatter reduce has regressed to densify"
        )

    orig = TopKCodec.decode_batch
    TopKCodec.decode_batch = _boom
    try:
        yield
    finally:
        TopKCodec.decode_batch = orig


def _init_residual_rows(codec, segs: SegmentMap):
    return tuple(
        jnp.zeros((seg.size,), jnp.float32) if codec.segment_stateful(seg) else ()
        for seg in segs
    )


def compress_update(
    codec, new_params: PyTree, global_params: PyTree, residual=None
) -> tuple[Any, PyTree]:
    """-> (wire_payload, new_residual) for error feedback.

    ``residual`` is the client's carried error-feedback state (folded into
    the delta before encoding); None means no carried state.  Flat codecs
    take/return one (n_params,) vector; segmented codecs take/return a
    tuple of per-segment rows and emit a ``StructuredUpdate``.
    """
    if codec.segments is not None:
        segs = codec.segments
        delta_tree = tree_sub(new_params, global_params)
        leaves, _ = jax.tree_util.tree_flatten(delta_tree)
        if segs.matches_leaves(leaves):
            vecs = [leaf.astype(jnp.float32).reshape(-1) for leaf in leaves]
        else:
            flat = tree_flatten_to_vector(delta_tree)
            codec.segment_map(int(flat.shape[0]))
            vecs = segs.split(flat)
        if residual is None:
            residual = _init_residual_rows(codec, segs)
        encs, new_res = [], []
        for vec, res, seg in zip(vecs, residual, segs):
            stateful = not isinstance(res, tuple)
            eff = vec + res if stateful else vec
            enc = codec.encode_segment(eff, seg)
            encs.append(enc)
            new_res.append(eff - codec.decode_segment(enc, seg) if stateful else ())
        return StructuredUpdate(segs, tuple(encs)), tuple(new_res)

    delta = tree_flatten_to_vector(tree_sub(new_params, global_params))
    if residual is not None:
        delta = delta + residual
    enc = codec.encode(delta)
    new_residual = delta - codec.decode(enc)
    return enc, new_residual


def decompress_update(codec, enc, global_params: PyTree) -> PyTree:
    if isinstance(enc, StructuredUpdate):
        delta = codec.decode_structured(enc)
    else:
        delta = codec.decode(enc)
    flat_global = tree_flatten_to_vector(global_params)
    return tree_unflatten_from_vector(flat_global + delta, global_params)
