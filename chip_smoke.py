"""Smoke run of the federated round on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh path on a four-chip host

One process, no subprocesses.  Each phase prints one line with its
seconds; any failed check raises, so the script exits non-zero and never
prints the result line.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
where ``count`` is the number of devices the run used.

One chip runs these phases:

- device: refuses anything but a TPU, and a ``REPRO_KERNEL_IMPL`` other
  than ``auto`` (that switch would route the kernels away from the chip);
- kernels: every main-path Pallas kernel on arrays made from ``--seed`` at
  ResNet-18 round widths plus a tail that is not a tile multiple, against
  its ``ref.py`` oracle — int8 payloads exactly, floats within ``TOL``;
- resnet18: ``resnet18-cifar10`` at its published widths (11,173,962
  parameters), C=8 clients of B=32 CIFAR-shaped images, through
  ``jax.jit(make_round_step(...))`` in parallel mode, 3 rounds under each
  of Null, Int8 and TopK: finite falling losses, the codec's kernels
  present in the compiled program, and round 1 equal to the same round
  compiled with the ``ref.py`` oracles (``ops.set_impl("reference")``);
- flower: ``Server.run`` with ``JaxClient``s on ``mobilenet-head-office31``
  at full width, then ``Server.run_scanned``, 2 rounds each.

``--chips 4`` runs only the mesh path and what it is compared with:
``resnet18-cifar10`` with one client per chip on ``make_local_mesh(data=4)``
under the fp32 and the int8 collective, against the one-device parallel
round of the same clients: fp32 params within ``MESH_TOL`` of it per leaf
(and, as a control, a round with one client dropped outside it), the int8
eval loss within 5% of it.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from contextlib import contextmanager

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Float tolerances of the kernel parity phase (rtol, atol), against the
# oracles in ref.py.  Elementwise kernels (dequantize, unpack, the quantize
# scales) compute the same fp32 product or quotient as the oracle: 1e-6
# allows the last-bit difference of a division.  The reduces sum C clients
# (or scatter C·k entries) in another order than the oracle's einsum or
# scatter-add: 1e-5 relative, 1e-6 absolute for sums that cancel.
TOL = {"elementwise": (1e-6, 0.0), "reduce": (1e-5, 1e-6)}
# Round 1 of the Pallas program against the reference program: the two run
# the same local training and differ only in how the server reduces, so
# their global params may differ by summation order, and an int8 payload by
# a rounding tie of one client.  One int8 level of one of C=8 clients is at
# most 1/(127 * 8) ~ 1e-3 of the largest update; allow that, per leaf.
ROUND_TOL = 1e-3

# The fp32 mesh round against the one-device round, after every round: per
# leaf, max|mesh - one| / max|one|.  Each chip trains its client unbatched,
# the one device trains the clients vmapped, and the two round differently
# even at HIGHEST precision; a ReLU that flips then moves a GroupNorm bias,
# whose values are no bigger than its updates, by about a percent.  On a
# TPU v5e the clients trained unbatched on one device came within 1.2e-2
# of the vmapped round over 3 rounds, the vmapped round at default
# precision (bf16 passes) 1.1e-1 to 1.4e-1 away, and a round with one of
# four clients dropped 0.45 to 0.58 away.
MESH_TOL = 3e-2

CLIENTS, BATCH, STEPS, ROUNDS = 8, 32, 2, 3
RESNET_PARAMS = 11_173_962
# Local SGD step size of the ResNet-18 rounds: at 0.1 the full-width model
# diverges from its random init within a round; 0.003 falls steadily.
RESNET_LR = 0.003


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"[{name}] done in {time.perf_counter() - t0:.1f} s", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def device_phase(chips: int):
    impl = os.environ.get("REPRO_KERNEL_IMPL", "auto").strip()
    if impl != "auto":
        sys.exit(f"chip_smoke: REPRO_KERNEL_IMPL={impl!r} would route kernels "
                 "away from the chip; unset it")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devices[0].platform})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX sees {len(devices)}")
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"[device] {devices[0].device_kind} x{len(devices)}, "
          f"compile cache {cache}", flush=True)
    return devices[0]


# ---------------------------------------------------------------- kernels
def _close(name, got, want, kind):
    import numpy as np

    rtol, atol = TOL[kind]
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite output")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=name)


def _exact(name, got, want):
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    bad = int((got != want).sum())
    check(bad == 0, f"{name}: {bad} of {got.size} int entries differ")


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.collective_quant import collective_pack, collective_unpack
    from repro.kernels.dequant_reduce import dequant_reduce
    from repro.kernels.fedavg_reduce import fedavg_reduce
    from repro.kernels.quantize import dequantize_int8, quantize_int8
    from repro.kernels.scatter_reduce import topk_scatter_reduce

    c = CLIENTS
    n_pad = -(-RESNET_PARAMS // 256) * 256      # the Int8 round's row width
    leaf = 3 * 3 * 512 * 512                    # ResNet-18's largest leaf
    tail = 3 * 32768 + 9216                     # not a multiple of any tile
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def normal(shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    w = jax.random.uniform(next(keys), (c,), jnp.float32, 0.1, 1.1)
    for n in (RESNET_PARAMS, tail):
        u = normal((c, n), 1e-2)
        _close(f"fedavg_reduce[{c}x{n}]", fedavg_reduce(u, w),
               ref.fedavg_reduce(u, w), "reduce")
    for shape in ((c, n_pad), (c, tail), (tail,)):
        x = normal(shape, 1e-2)
        q, s = quantize_int8(x)
        q_ref, s_ref = ref.quantize_int8(x)
        _exact(f"quantize_int8{shape}.q", q, q_ref)
        _close(f"quantize_int8{shape}.scale", s, s_ref, "elementwise")
        _close(f"dequantize_int8{shape}", dequantize_int8(q_ref, s_ref),
               ref.dequantize_int8(q_ref, s_ref), "elementwise")
        if len(shape) == 2:
            _close(f"dequant_reduce{shape}", dequant_reduce(q_ref, s_ref, w),
                   ref.dequant_reduce(q_ref, s_ref, w), "reduce")
    for n in (leaf, tail):
        x = normal((n,), 1e-2)
        s = jnp.max(jnp.abs(normal((n,), 1e-2)).reshape(-1, 256), axis=1) / 127.0
        q = collective_pack(x, s)
        _exact(f"collective_pack[{n}]", q, ref.collective_pack(x, s))
        _close(f"collective_unpack[{n}]", collective_unpack(q, s),
               ref.collective_unpack(q, s), "elementwise")
    for n in (leaf, tail):
        k = n // 100                            # TopKCodec(frac=0.01)
        idx = jax.random.randint(next(keys), (c, k), 0, n, jnp.int32)
        idx = idx.at[:, 0].set(n - 1)           # the last element lands too
        val = normal((c, k))
        _close(f"topk_scatter_reduce[{c}x{k}->{n}]",
               topk_scatter_reduce(idx, val, w, n),
               ref.topk_scatter_reduce(idx, val, w, n), "reduce")


# ---------------------------------------------------------------- resnet18
# the jitted wrapper each codec's round must show as a tpu_custom_call
NEEDED_KERNELS = {
    "null": ("fedavg_reduce",),
    "int8": ("quantize_int8", "dequant_reduce"),
    "topk": ("topk_scatter_reduce",),
}


def kernel_counts(hlo_text: str) -> dict[str, int]:
    """tpu_custom_calls in compiled HLO, by the jitted kernel wrapper that
    issued them (``jit(<name>)/pallas_call`` in the op metadata)."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line:
            names = re.findall(r"jit\((\w+)\)/pallas_call", line)
            name = names[-1] if names else "?"
            counts[name] = counts.get(name, 0) + 1
    return counts


def cifar_round_data(cfg, n_clients: int, steps: int, batch: int, seed: int):
    """Per-client CIFAR-shaped batches (C, steps, B, H, W, 3) drawn around
    one random center per class, as the compression benchmark makes them."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    shape = (cfg.image_size, cfg.image_size, cfg.channels)
    centers = rng.normal(0.0, 0.8, size=(cfg.num_classes, *shape))
    y = rng.integers(0, cfg.num_classes, (n_clients, steps, batch))
    x = centers[y] + 0.5 * rng.normal(size=(n_clients, steps, batch, *shape))
    return {"x": jnp.asarray(x, jnp.float32), "y": jnp.asarray(y, jnp.int32)}


def _max_leaf_diff_over_update(a, b, p0) -> float:
    """max over leaves of max|a - b| / max|b - p0|: how far round 1 of one
    program is from the other's, relative to the update itself."""
    import jax
    import numpy as np

    worst = 0.0
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b), jax.tree.leaves(p0)):
        x, y, z = (np.asarray(v, np.float32) for v in (x, y, z))
        upd = float(np.max(np.abs(y - z)))
        worst = max(worst, float(np.max(np.abs(x - y))) / max(upd, 1e-30))
    return worst


def resnet_phase(model, params, seed: int, codecs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FedAvg, RoundSpec, make_round_step
    from repro.kernels import ops
    from repro.optim import sgd
    from repro.utils.pytree import tree_size

    n_params = tree_size(params)
    batches = cifar_round_data(model.cfg, CLIENTS, STEPS, BATCH, seed)
    w = jnp.ones((CLIENTS,), jnp.float32)
    bud = jnp.full((CLIENTS,), STEPS, jnp.int32)
    strat = FedAvg()
    for name, codec in codecs.items():
        t0 = time.perf_counter()
        spec = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec)
        step = jax.jit(make_round_step(model.loss_fn, sgd(RESNET_LR), strat, spec))
        state = strat.init_state(params)
        cstate = codec.init_client_state(CLIENTS, n_params)
        args = (params, state, cstate, batches, w, bud, 0)
        compiled = step.lower(*args).compile()
        counts = kernel_counts(compiled.as_text())
        print(f"[resnet18/{name}] tpu_custom_call by kernel: {counts}", flush=True)
        for kernel in NEEDED_KERNELS[name]:
            check(counts.get(kernel, 0) > 0,
                  f"{name}: no {kernel} kernel in the compiled round")

        losses, p, round1 = [], params, None
        for rnd in range(ROUNDS):
            p, state, cstate, met = compiled(p, state, cstate, batches, w, bud, rnd)
            losses.append(float(met["client_loss_mean"]))
            if rnd == 0:
                round1 = p
        print(f"[resnet18/{name}] losses {losses}", flush=True)
        check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
        check(losses[-1] < losses[0], f"{name}: loss did not fall {losses}")

        ops.set_impl("reference")
        try:
            ref_step = jax.jit(make_round_step(model.loss_fn, sgd(RESNET_LR), strat, spec))
            ref_compiled = ref_step.lower(*args).compile()
        finally:
            ops.set_impl("auto")
        check(not kernel_counts(ref_compiled.as_text()),
              f"{name}: the reference round still holds Pallas kernels")
        state = strat.init_state(params)
        cstate = codec.init_client_state(CLIENTS, n_params)
        ref1, *_ = ref_compiled(params, state, cstate, batches, w, bud, 0)
        diff = _max_leaf_diff_over_update(round1, ref1, params)
        print(f"[resnet18/{name}] round 1 vs reference program: max leaf "
              f"|diff| / |update| = {diff:.3e} (limit {ROUND_TOL:g}); "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        check(diff <= ROUND_TOL, f"{name}: round 1 differs from the reference")


# ---------------------------------------------------------------- flower
def flower_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import FedAvg, JaxClient, RoundSpec, Server
    from repro.core.server import make_cost_model_for
    from repro.core.cost_model import PROFILES
    from repro.data.federated import dirichlet_partition
    from repro.data.synthetic import make_features
    from repro.models import build_model
    from repro.optim import sgd

    model = build_model("mobilenet-head-office31")
    data = make_features(n=2000, num_classes=31, feature_dim=model.cfg.feature_dim,
                         seed=seed)
    shards = dirichlet_partition(data, n_clients=5, alpha=1.0, seed=seed)
    params = model.init(jax.random.key(seed))
    mask = model.trainable_mask(params)
    clients = [
        JaxClient(client_id=s.client_id, loss_fn=model.loss_fn, dataset=s,
                  batch_size=32, trainable_mask=mask, device_profile="pixel-4")
        for s in shards
    ]
    cost_model = make_cost_model_for(params, [PROFILES["pixel-4"]] * 5)
    server = Server(strategy=FedAvg(local_epochs=2, local_lr=0.1),
                    clients=clients, cost_model=cost_model)
    server.logger.quiet = True
    _, history = server.run(params, num_rounds=2)
    run_losses = [r.train_loss for r in history.rounds]
    print(f"[flower/run] train losses {run_losses}, "
          f"accuracy {history.final_accuracy()}", flush=True)
    check(len(run_losses) == 2 and all(np.isfinite(run_losses)),
          f"Server.run losses {run_losses}")

    rng = np.random.default_rng(seed)
    rounds, steps, batch = 2, 2, 32
    x = rng.normal(size=(rounds, 5, steps, batch, model.cfg.feature_dim))
    y = rng.integers(0, model.cfg.num_classes, (rounds, 5, steps, batch))
    batches = {"x": np.asarray(x, np.float32), "y": np.asarray(y, np.int32)}
    _, history, _ = server.run_scanned(
        params, rounds, loss_fn=model.loss_fn, opt=sgd(0.1),
        spec=RoundSpec(max_steps=steps, execution_mode="parallel"),
        batches=jax.tree.map(jax.numpy.asarray, batches), trainable_mask=mask,
    )
    scan_losses = [r.train_loss for r in history.rounds]
    print(f"[flower/run_scanned] train losses {scan_losses}", flush=True)
    check(len(scan_losses) == 2 and all(np.isfinite(scan_losses)),
          f"Server.run_scanned losses {scan_losses}")


# ---------------------------------------------------------------- mesh
def allreduce_types(hlo_text: str) -> dict[str, int]:
    """Elements all-reduced per element type in compiled HLO text, summed
    over the all-reduce ops' results (``{"s32": 11173962, ...}``)."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(.*?)\sall-reduce(?:-start)?\(", line)
        if not m:
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            out[dtype] = out.get(dtype, 0) + n
    return out


def leaf_gap(a, b) -> tuple[float, str]:
    """The worst leaf of max|a - b| / max|b|, and its path."""
    import jax
    import numpy as np

    worst, where = 0.0, ""
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        gap = float(np.max(np.abs(x - y))) / max(float(np.max(np.abs(y))), 1e-30)
        if gap >= worst:
            worst, where = gap, jax.tree_util.keystr(path)
    return worst, where


def mesh_phase(model, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FedAvg, RoundSpec, init_collective_residual, make_round_step
    from repro.core.rounds import make_client_update
    from repro.launch.mesh import make_local_mesh
    from repro.optim import sgd

    n_chips = 4
    mesh = make_local_mesh(data=n_chips)
    # the mesh path maps clients 1:1 onto the client axis: one per chip
    batches = cifar_round_data(model.cfg, n_chips, STEPS, BATCH, seed)
    w = jnp.ones((n_chips,), jnp.float32)
    bud = jnp.full((n_chips,), STEPS, jnp.int32)
    strat = FedAvg()
    spec = RoundSpec(max_steps=STEPS, execution_mode="parallel")
    eval_batch = jax.tree.map(lambda a: a[0, 0], batches)

    def run(step, cstate, weights):
        """ROUNDS rounds -> (client losses, final eval loss, params after
        each round on the host)."""
        p, state = params, strat.init_state(params)
        losses, ps = [], []
        for rnd in range(ROUNDS):
            p, state, cstate, met = step(p, state, cstate, batches, weights, bud, rnd)
            losses.append(float(met["client_loss_mean"]))
            ps.append(jax.device_get(p))
        return losses, float(model.loss_fn(p, eval_batch)[0]), ps

    def gaps(ps):
        """The worst leaf gap to the one-device run over all rounds."""
        return max(leaf_gap(p, q) for p, q in zip(ps, p_one))

    def unbatched():
        """The round's clients trained one by one on one device, without
        vmap, as each chip trains its client, and their deltas averaged ->
        params after each round on the host."""
        update = jax.jit(make_client_update(model.loss_fn, sgd(RESNET_LR), spec))
        p, state, ps = params, strat.init_state(params), []
        for rnd in range(ROUNDS):
            news = [update(p, jax.tree.map(lambda x: x[i], batches), bud[i])[0]
                    for i in range(n_chips)]
            avg = jax.tree.map(lambda g, *ns: g + sum(n - g for n in ns) / n_chips,
                               p, *news)
            p, state = strat.server_update(avg, p, state, rnd)
            ps.append(jax.device_get(p))
        return ps

    t0 = time.perf_counter()
    one = jax.jit(make_round_step(model.loss_fn, sgd(RESNET_LR), strat, spec))
    l_one, e_one, p_one = run(one, (), w)
    print(f"[mesh/one-device] losses {l_one}, eval loss {e_one:.6f}; "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(all(np.isfinite(l_one)), f"one-device losses {l_one}")
    p_unbatched = unbatched()

    for collective in ("fp32", "int8"):
        t0 = time.perf_counter()
        cspec = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                          collective=collective)
        step = jax.jit(make_round_step(model.loss_fn, sgd(RESNET_LR), strat, cspec,
                                       mesh=mesh, client_axes=("data",)))
        cstate = () if collective == "fp32" else (
            (), init_collective_residual(params, n_chips))
        args = (params, strat.init_state(params), cstate, batches, w, bud, 0)
        text = step.lower(*args).compile().as_text()
        types = allreduce_types(text)
        counts = kernel_counts(text)
        losses, ev, ps = run(step, cstate, w)
        gap, leaf = gaps(ps)
        print(f"[mesh/{collective}] elements all-reduced by type {types}; "
              f"tpu_custom_call by kernel {counts}; losses {losses}, eval loss "
              f"{ev:.6f}; params vs one-device: worst leaf max|diff| / max|param| "
              f"= {gap:.3e} at {leaf}; {time.perf_counter() - t0:.1f} s", flush=True)
        check(all(np.isfinite(losses)), f"{collective}: losses {losses}")
        if collective == "fp32":
            # where the gap comes from: the same clients trained unbatched
            # on one device (printed, not checked)
            gap_u, leaf_u = max(leaf_gap(p, q) for p, q in zip(ps, p_unbatched))
            gap_uo, leaf_uo = gaps(p_unbatched)
            print(f"[mesh/fp32] unbatched one-device clients: vs mesh {gap_u:.3e} "
                  f"at {leaf_u}; vs the vmapped one-device round {gap_uo:.3e} "
                  f"at {leaf_uo}", flush=True)
            check(gap <= MESH_TOL, f"fp32: params differ from one device "
                  f"({gap:.3e} > {MESH_TOL:g} at {leaf})")
            # control: the same program with one client's weight zeroed
            # must fail the comparison, or the comparison proves nothing
            _, _, ps_drop = run(step, cstate, w.at[-1].set(0.0))
            gap_drop, leaf_drop = gaps(ps_drop)
            print(f"[mesh/fp32] control, one client dropped: worst leaf "
                  f"{gap_drop:.3e} at {leaf_drop}", flush=True)
            check(gap_drop > MESH_TOL, "fp32: the comparison misses a dropped client")
        else:
            check(counts.get("collective_pack", 0) > 0
                  and counts.get("collective_unpack", 0) > 0,
                  "int8: no collective pack/unpack kernels in the mesh round")
            # the bound tests/test_collective.py holds the int8 wire to
            check(abs(ev - e_one) <= 5e-2 * abs(e_one),
                  f"int8 eval loss {ev} vs one-device {e_one}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    with phase("device"):
        dev = device_phase(args.chips)
    import jax

    from repro.core import Int8Codec, NullCodec, SegmentMap, TopKCodec
    from repro.models import build_model
    from repro.utils.pytree import tree_size

    with phase("resnet18-init"):
        model = build_model("resnet18-cifar10")
        params = model.init(jax.random.key(args.seed))
        check(tree_size(params) == RESNET_PARAMS,
              f"resnet18-cifar10 has {tree_size(params)} params")

    if args.chips == 4:
        # fp32 convolutions on both sides: the comparison is then about the
        # mesh path, not about bf16 passes that vmapped and per-chip
        # convolutions may round differently
        with phase("mesh"), jax.default_matmul_precision("highest"):
            mesh_phase(model, params, args.seed)
    else:
        with phase("kernels"):
            kernel_phase(args.seed)
        # TopK reduces leaf by leaf: the flat 11.2M-entry accumulator is over
        # the scatter kernel's VMEM budget, every ResNet-18 leaf is under it
        codecs = {
            "null": NullCodec(),
            "int8": Int8Codec(),
            "topk": TopKCodec(frac=0.01, segments=SegmentMap.from_tree(params)),
        }
        with phase("resnet18"):
            resnet_phase(model, params, args.seed, codecs)
        with phase("flower"):
            flower_phase(args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": args.chips,
    }}))


if __name__ == "__main__":
    main()
