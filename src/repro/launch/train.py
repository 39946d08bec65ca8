"""FL training driver — the end-to-end example entry point.

Runs real federated training at CPU scale (reduced configs) or assembles the
pod-scale jitted round step for any assigned architecture:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \\
      --clients 4 --rounds 5 --epochs 2 --strategy fedavg

The reduced path exercises the identical code the dry-run lowers for the
production mesh: model -> loss -> make_round_step -> strategy aggregation.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import (
    Int8Codec, NullCodec, RoundSpec, STRATEGIES, TopKCodec, make_round_step,
)
from repro.core.cost_model import AWS_DEVICE_FARM, PROFILES, CostModel
from repro.data.loader import lm_round_batch
from repro.models import build_model
from repro.optim import sgd
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.logging import MetricsLogger
from repro.utils.pytree import tree_bytes, tree_size


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--epochs", type=int, default=1, help="local epochs E")
    ap.add_argument("--steps-per-epoch", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--strategy", default="fedavg", choices=sorted(STRATEGIES))
    ap.add_argument("--tau-steps", type=int, default=0,
                    help="cutoff step budget per round (0 = no cutoff)")
    ap.add_argument("--codec", default="fp32", choices=("fp32", "int8", "topk"),
                    help="uplink wire codec for the compressed round path")
    ap.add_argument("--scan", action="store_true",
                    help="compile the whole run into one lax.scan "
                         "(Server.run_scanned) instead of the per-round loop")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    logger = MetricsLogger("train")

    key = jax.random.key(args.seed)
    params = model.init(key)
    logger.log("init", arch=cfg.name, params=tree_size(params),
               bytes_mb=tree_bytes(params) / 1e6)

    strategy = STRATEGIES[args.strategy]()
    steps = args.epochs * args.steps_per_epoch
    codec = {"fp32": NullCodec(), "int8": Int8Codec(),
             "topk": TopKCodec(frac=0.01)}[args.codec]
    spec = RoundSpec(max_steps=steps, execution_mode="parallel", codec=codec)

    cost = CostModel(
        profiles=[PROFILES[AWS_DEVICE_FARM[i % len(AWS_DEVICE_FARM)]]
                  for i in range(args.clients)],
        update_bytes=tree_bytes(params),
    )

    weights = jnp.ones((args.clients,), jnp.float32)
    budget = args.tau_steps if args.tau_steps > 0 else steps
    budgets = jnp.full((args.clients,), budget, jnp.int32)

    def round_batch(rnd: int):
        batch = lm_round_batch(
            n_clients=args.clients, steps=steps, batch_size=args.batch,
            seq_len=args.seq, vocab_size=cfg.vocab_size,
            # tuple seeding (never seed*K+rnd arithmetic): affine seed maps
            # collide across (seed, round) pairs, correlating "independent"
            # runs — enforced by fedlint's rng-discipline rule
            seed=(args.seed, rnd),
        )
        if cfg.frontend_tokens:
            fd = cfg.frontend_dim or cfg.d_model
            rng = np.random.default_rng((args.seed, rnd))
            batch["frontend"] = rng.normal(
                size=(args.clients, steps, args.batch, cfg.frontend_tokens, fd)
            ).astype(np.float32)
        return batch

    if args.scan:
        # rounds-as-scan: the SAME per-round batches, stacked (R, C, ...),
        # one compiled program for the whole run, History decoded at the end
        from repro.core import Server

        stacked = jax.tree.map(
            lambda *xs: np.stack(xs),
            *[round_batch(r) for r in range(1, args.rounds + 1)],
        )
        srv = Server(strategy=strategy, clients=[], cost_model=cost)
        srv.logger.quiet = True
        _, hist, _ = srv.run_scanned(
            params, args.rounds, loss_fn=model.loss_fn, opt=sgd(args.lr),
            spec=spec, batches=stacked, weights=weights, step_budgets=budgets,
        )
        for rec in hist.rounds:
            logger.log(
                "round", rnd=rec.rnd, loss=rec.train_loss, steps=rec.steps,
                wall_s=rec.wall_time_s, energy_kj=rec.energy_j / 1e3,
            )
        print(f"final loss: {hist.rounds[-1].train_loss:.4f}")
        return

    round_step = jax.jit(make_round_step(model.loss_fn, sgd(args.lr),
                                         strategy, spec))
    server_state = strategy.init_state(params)
    client_state = codec.init_client_state(args.clients, tree_size(params))
    uplink = codec.wire_bytes([tree_size(params)] * args.clients)

    for rnd in range(1, args.rounds + 1):
        batch = round_batch(rnd)
        params, server_state, client_state, metrics = round_step(
            params, server_state, client_state, batch, weights, budgets, rnd
        )
        costs = cost.round_costs(
            [int(budgets[i]) for i in range(args.clients)], uplink_bytes=uplink
        )
        logger.log(
            "round", rnd=rnd,
            loss=float(metrics["client_loss_mean"]),
            steps=int(metrics["steps_total"]),
            wall_s=cost.round_wall_time(costs),
            energy_kj=cost.round_energy(costs) / 1e3,
        )

    print(f"final loss: {float(metrics['client_loss_mean']):.4f}")


if __name__ == "__main__":
    main()
