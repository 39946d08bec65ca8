"""Compressed-collective benchmark: int8 vs fp32 mesh psum wire.

The mesh shard_map round moves every device's partial weighted sum across
the interconnect — per psum hop, per device, a full model in fp32.
``RoundSpec(collective="int8")`` (``CompressedPsum``) shrinks that to one
byte per element plus a small scale sidecar.  This harness runs the SAME
schedule through both collectives on a real 8-device host-platform mesh
(2 "pods" x 4 "data", hierarchical cross-pod psum) with the reduced head
model and reports:

- cross-link collective bytes per round, fp32 vs int8, from the
  ``CostModel`` tier accounting (tiers derived from the actual mesh via
  ``launch.mesh.collective_tiers`` — the same formula the round billing
  uses, so the bench cannot drift from the shipped accounting);
- final eval loss of both runs — the byte reduction must come at MATCHED
  accuracy, not by under-training;
- wall time per round for both (CPU psums: directional only);
- the sharded client-state memory story: per-device addressable bytes of
  a ``shard_client_state``-laid-out (C, n) residual block vs unsharded.

Rows print CSV-style like the other benches; ``--out`` (default
``BENCH_mesh.json``) captures everything machine-readably.

``--smoke`` is the CI guard and asserts the ISSUE-10 acceptance criteria:

- int8 collective moves >= 3x fewer cross-link bytes than fp32, and
- int8 final loss within 5% of fp32 (matched accuracy), and
- sharded client state is resident at ~1/n_devices per device.

  PYTHONPATH=src python -m benchmarks.mesh_bench [--smoke] [--out F]
"""
from __future__ import annotations

import os
import sys

if "jax" not in sys.modules:  # must land before jax initializes
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import argparse
import json
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import (
    FedAvg, PROFILES, RoundSpec, init_collective_residual, make_round_step,
)
from repro.core.cost_model import CostModel
from repro.launch.mesh import collective_tiers, make_local_mesh
from repro.models import build_model
from repro.models.sharding import ShardRules, shard_client_state
from repro.optim import sgd
from repro.utils.pytree import tree_size

C, STEPS, B, ROUNDS = 8, 2, 16, 15
AXES = ("pod", "data")


def _model():
    """The REDUCED head: the bench measures wire accounting and parity,
    not head-size FLOPs."""
    arch = replace(get_config("mobilenet-head-office31"),
                   name="mobilenet-head-office31-reduced")
    return build_model(arch)


def _mesh():
    if len(jax.devices()) < 8:
        raise SystemExit(
            "mesh_bench needs 8 devices (XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before jax imports)"
        )
    return make_local_mesh(pod=2, data=4)


def _setup(seed=0):
    m = _model()
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 1.0, size=(m.cfg.num_classes, m.cfg.feature_dim))

    def batch_of(n, s):
        r = np.random.default_rng(s)
        y = r.integers(0, m.cfg.num_classes, n)
        x = centers[y] + 0.4 * r.normal(size=(n, m.cfg.feature_dim))
        return x.astype(np.float32), y.astype(np.int32)

    xs, ys = zip(*[batch_of(STEPS * B, 100 + c) for c in range(C)])
    train = {
        "x": jnp.asarray(np.stack(xs).reshape(C, STEPS, B, -1)),
        "y": jnp.asarray(np.stack(ys).reshape(C, STEPS, B)),
    }
    ex, ey = batch_of(512, 999)
    eval_batch = {"x": jnp.asarray(ex), "y": jnp.asarray(ey)}
    return m, m.init(jax.random.key(seed)), train, eval_batch


def run_collective(collective: str, mesh, *, rounds=ROUNDS, seed=0) -> dict:
    """One full mesh training run under the given collective wire."""
    m, params, train, eval_batch = _setup(seed)
    n = tree_size(params)
    spec = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                     collective=collective)
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat, spec, mesh=mesh, client_axes=AXES,
    ))
    cstate = spec.codec.init_client_state(C, n)
    if collective == "int8":
        cstate = (cstate, init_collective_residual(params, C))
    w = jnp.ones(C)
    bud = jnp.full((C,), STEPS, jnp.int32)
    p, state = params, strat.init_state(params)
    p, state, cstate, _ = rs(p, state, cstate, train, w, bud, 0)  # compile
    p, state = params, strat.init_state(params)
    cstate = spec.codec.init_client_state(C, n)
    if collective == "int8":
        cstate = (cstate, init_collective_residual(params, C))
    t0 = time.perf_counter()
    for rnd in range(rounds):
        p, state, cstate, met = rs(p, state, cstate, train, w, bud, rnd)
    jax.block_until_ready(p)
    wall = time.perf_counter() - t0
    loss, _ = m.loss_fn(p, eval_batch)

    cm = CostModel(
        profiles=[PROFILES["tpu-v5e-chip"]], update_bytes=4 * n,
        mesh_tiers=collective_tiers(mesh, AXES), collective=collective,
    )
    return {
        "collective": collective,
        "n_params": int(n),
        "rounds": rounds,
        "final_loss": float(loss),
        "us_per_round": wall / rounds * 1e6,
        "collective_bytes_per_round": int(cm.collective_bytes(n)),
        "collective_bytes_by_tier": {
            k: int(v) for k, v in cm.collective_bytes_by_tier(n).items()
        },
    }


def sharded_state_memory(mesh, n: int = 1 << 14) -> dict:
    """Per-device resident bytes of a (C, n) client-state block laid out by
    ``shard_client_state`` over all 8 mesh devices (fsdp rules) vs the
    replicated layout."""
    rules = ShardRules(mode="fsdp",
                       axis_sizes=tuple(zip(mesh.axis_names,
                                            mesh.devices.shape)))
    block = jnp.zeros((C, n), jnp.float32)
    sharded = shard_client_state(block, mesh, rules)
    per_dev = int(sharded.addressable_shards[0].data.nbytes)
    return {
        "n_elems": n,
        "total_bytes": int(block.nbytes),
        "per_device_bytes": per_dev,
        "reduction": block.nbytes / per_dev,
        "n_devices": int(mesh.devices.size),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: acceptance asserts")
    ap.add_argument("--out", default="BENCH_mesh.json")
    args = ap.parse_args()

    mesh = _mesh()
    runs = {c: run_collective(c, mesh, rounds=args.rounds)
            for c in ("fp32", "int8")}
    for r in runs.values():
        print(
            f"mesh[collective={r['collective']}],{r['us_per_round']:.0f},"
            f"link_bytes={r['collective_bytes_per_round']};"
            f"loss={r['final_loss']:.4f}"
        )
    ratio = (runs["fp32"]["collective_bytes_per_round"]
             / runs["int8"]["collective_bytes_per_round"])
    print(f"mesh[wire_reduction],0,int8_vs_fp32={ratio:.2f}x")

    # fsdp-style state sharding is orthogonal to the collective axes: use a
    # pure fsdp mesh over the same 8 devices for the memory story
    fsdp_mesh = make_local_mesh(data=4, model=2)
    memory = sharded_state_memory(fsdp_mesh)
    print(
        f"mesh[sharded_state],0,per_device_bytes={memory['per_device_bytes']};"
        f"reduction={memory['reduction']:.1f}x"
    )

    with open(args.out, "w") as f:
        json.dump({
            "bench": "mesh",
            "mesh": {"shape": [2, 4], "axes": list(AXES)},
            "runs": runs,
            "wire_reduction": ratio,
            "sharded_state": memory,
        }, f, indent=2, default=float)
    print(f"mesh[json] wrote {args.out}")

    if args.smoke:
        l_fp, l_i8 = (runs[c]["final_loss"] for c in ("fp32", "int8"))
        assert ratio >= 3.0, (
            f"int8 collective only {ratio:.2f}x below fp32 wire (< 3x)"
        )
        assert abs(l_i8 - l_fp) <= 5e-2 * abs(l_fp), (
            f"int8 loss {l_i8:.4f} not matched to fp32 {l_fp:.4f}"
        )
        assert memory["reduction"] >= 0.9 * memory["n_devices"], (
            f"sharded state resident at 1/{memory['reduction']:.1f}, "
            f"expected ~1/{memory['n_devices']}"
        )
        print(f"mesh[guards] OK: {ratio:.2f}x fewer link bytes at matched "
              f"loss ({l_i8:.4f} vs {l_fp:.4f}); state at "
              f"1/{memory['reduction']:.0f} per device")


if __name__ == "__main__":
    main()
