"""Sharding rules: map logical parameter/activation dims to mesh axes.

Two FL execution modes (DESIGN.md §4):

- parallel:   the `data` mesh axis indexes *clients*; params get a leading
              client dim (added by core.rounds, P(data_axes)) and are
              tensor-parallel over `model` only.
- sequential: one client occupies the whole mesh; params are 2D-sharded
              (FSDP-style over `data` + tensor-parallel over `model`),
              batch is sharded over (`pod`, `data`).

Spec helpers return None (replicate) for any dim not divisible by its axis —
divisibility is checked against the actual mesh shape so every assigned
architecture lowers on both the 256-chip and 512-chip meshes.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def shard_map_compat(f, mesh, in_specs, out_specs, axis_names):
    """``jax.shard_map``, manual over ``axis_names`` (the client axes) and
    automatic over every other mesh axis (the model axes) — the ONE place
    the round engine's mesh path, launch-side mesh drivers and the
    sharded-client-state tests build their shard_maps.  A model axis of
    size 1 splits nothing and is made manual too: a Pallas kernel (a codec
    or collective kernel in the region) cannot be partitioned
    automatically, so it may only run where no axis is automatic."""
    manual = set(axis_names) | {a for a, n in mesh.shape.items() if n == 1}
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names=manual, check_vma=False,
    )


@dataclass(frozen=True)
class ShardRules:
    """parallel: clients on `data`, TP on `model`.
    sequential: FSDP on `data` + TP on `model`, batch on (pod, data).
    fsdp: pure ZeRO — weights AND batch over ALL mesh axes, no TP (right
    regime for mid-size MoE: activations per chip shrink by the full mesh)."""

    mode: str = "parallel"              # "parallel" | "sequential" | "fsdp"
    data_axis: str = "data"
    pod_axis: str | None = None         # "pod" on the multi-pod mesh
    axis_sizes: tuple[tuple[str, int], ...] = (("data", 16), ("model", 16))

    def size(self, axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, (tuple, list)):
            n = 1
            for a in axis:
                n *= self.size(a)
            return n
        return dict(self.axis_sizes).get(axis, 1)

    # ---- logical axis resolution ----
    @property
    def all_axes(self) -> tuple[str, ...]:
        axes = ("data", "model")
        if self.pod_axis:
            axes = ("pod",) + axes
        return axes

    @property
    def model_axis(self):
        """Tensor-parallel axis (None in pure-FSDP mode)."""
        return None if self.mode == "fsdp" else "model"

    @property
    def fsdp(self):
        """Axis (or axes) FSDP-sharding the params.

        fsdp mode shards weights over the in-pod 256 chips; on the multi-pod
        mesh the pod axis is a data-parallel replica (hybrid FSDP+DP), since
        a 256-sequence global batch cannot split 512 ways."""
        if self.mode == "sequential":
            return self.data_axis
        if self.mode == "fsdp":
            return ("data", "model")
        return None

    @property
    def client_axes(self):
        """Mesh axes that enumerate clients (parallel mode)."""
        axes = (self.data_axis,)
        if self.pod_axis:
            axes = (self.pod_axis, self.data_axis)
        return axes

    @property
    def batch_axes(self):
        """Axes sharding the (per-client or global) batch dim."""
        if self.mode == "sequential":
            axes = (self.data_axis,)
            if self.pod_axis:
                axes = (self.pod_axis, self.data_axis)
            return axes
        if self.mode == "fsdp":
            if self.pod_axis:
                return (self.pod_axis, self.data_axis)  # 32-way, 8 seq/chip
            return ("data", "model")                    # 256-way, 1 seq/chip
        return None  # parallel: batch dim is per-client, unsharded

    def spec(self, *dims, dim_sizes: tuple[int, ...] | None = None) -> P:
        """Build a PartitionSpec; drop any axis that does not divide its dim.

        dims entries: None | axis-name | tuple of axis-names.
        """
        out = []
        for i, d in enumerate(dims):
            if d is None:
                out.append(None)
                continue
            if dim_sizes is not None:
                need = self.size(d)
                if need == 0 or dim_sizes[i] % max(1, need) != 0:
                    out.append(None)
                    continue
            out.append(d)
        return P(*out)


def client_state_specs(rules: ShardRules, segments) -> tuple:
    """PartitionSpecs laying each segment's ``(C, seg.size)`` codec
    client-state rows out along the mesh (fsdp archs).

    The client dim stays whole (row i is one client's residual — gather/
    scatter and the sequential scan index it); the *parameter* dim shards
    over the rules' fsdp axes, so per-device state memory drops by the
    full fsdp factor and the residual never materializes replicated.
    Segments whose size the axes do not divide replicate (P(None, None)) —
    same divisibility contract as ``ShardRules.spec``.  ``segments`` is a
    ``SegmentMap`` (or any iterable of objects with ``.size``).
    """
    ax = rules.fsdp
    return tuple(
        rules.spec(None, ax, dim_sizes=(1, seg.size)) for seg in segments
    )


def client_state_shardings(mesh, rules: ShardRules, segments) -> tuple:
    """``client_state_specs`` bound to a concrete mesh: one NamedSharding
    per segment, the layout ``CohortState(shardings=...)`` gathers into and
    ``shard_client_state`` pins an existing state pytree to."""
    return tuple(
        NamedSharding(mesh, spec)
        for spec in client_state_specs(rules, segments)
    )


def shard_client_state(state, mesh, rules: ShardRules, segments=None):
    """Lay an existing codec client state out along the mesh.

    ``state`` is whatever ``codec.init_client_state`` returned: a flat
    ``(C, n_params)`` block, or the per-segment tuple of ``(C, seg.size)``
    blocks (``()`` entries for stateless segments pass through).  Values
    are unchanged — only placement moves (``jax.device_put`` with the
    ``client_state_shardings`` layout), so sharded and unsharded rounds
    stay bitwise-identical.  With ``segments=None`` the flat block is
    treated as one full-width segment.
    """
    class _Flat:
        def __init__(self, size):
            self.size = size

    leaves = state if isinstance(state, (tuple, list)) else (state,)
    if segments is None:
        # stateless () entries get a placeholder segment; never placed
        segs = [_Flat(x.shape[1] if hasattr(x, "shape") else 1) for x in leaves]
    else:
        segs = list(segments)
        assert len(segs) == len(leaves), (
            f"state has {len(leaves)} entries, segment map has {len(segs)}"
        )
    specs = client_state_specs(rules, segs)
    out = tuple(
        jax.device_put(x, NamedSharding(mesh, spec))
        if hasattr(x, "shape") else x
        for x, spec in zip(leaves, specs)
    )
    return out if isinstance(state, (tuple, list)) else out[0]


def serve_rules(mesh, multi_pod: bool) -> ShardRules:
    """Serving always FSDP/TP-shards (no client axis)."""
    sizes = tuple((n, s) for n, s in zip(mesh.axis_names, mesh.devices.shape))
    return ShardRules(
        mode="sequential",
        pod_axis="pod" if multi_pod else None,
        axis_sizes=sizes,
    )


def train_rules(mesh, multi_pod: bool, execution_mode: str) -> ShardRules:
    sizes = tuple((n, s) for n, s in zip(mesh.axis_names, mesh.devices.shape))
    return ShardRules(
        mode=execution_mode,
        pod_axis="pod" if multi_pod else None,
        axis_sizes=sizes,
    )
