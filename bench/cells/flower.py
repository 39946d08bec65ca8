"""Runner of the cells whose window calls ``Server.run``: the Flower API
path, one ``JaxClient.fit`` after another on the host.

Traffic keys: ``clients``, ``shard_sizes`` (one per client; every seed
deals the same sizes out in another order), ``label_alpha`` (each
client's labels follow its own Dirichlet(alpha) class mix), ``batch``,
``epochs``, ``lr``, ``device_profile`` (the ``CostModel`` profile),
``check_rounds`` and ``data`` (class centers: ``center_std``,
``noise_std``, both over sqrt(feature_dim) where ``scale_by_sqrt_dim``).

Round boundaries come from a ``FedAvg`` subclass whose ``configure_fit``
runs once per round; each client's ``fit`` runs inside a delegating
wrapper that records its span.  No evaluation runs inside the window.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.cells import common
from bench.harness import Window
from bench.reference import compare, fedavg


class WindowClosed(Exception):
    """Raised at the start of the first round after the window's end."""


def make_shards(cfg: dict, traffic: dict, seed: int):
    """[(x, y)] per client, as numpy arrays made from the seed."""
    rng = np.random.default_rng(seed)
    dim, k = cfg["feature_dim"], cfg["num_classes"]
    d = traffic["data"]
    scale = 1.0 / np.sqrt(dim) if d.get("scale_by_sqrt_dim") else 1.0
    centers = rng.normal(0.0, d["center_std"] * scale, size=(k, dim))
    sizes = rng.permutation(np.asarray(traffic["shard_sizes"]))
    shards = []
    for n in sizes:
        mix = rng.dirichlet(np.full(k, traffic["label_alpha"]))
        y = rng.choice(k, size=int(n), p=mix).astype(np.int32)
        x = centers[y] + rng.normal(0.0, d["noise_std"] * scale, size=(int(n), dim))
        shards.append((x.astype(np.float32), y))
    return shards


class CyclicBatches:
    """The order in which a Flower client's dataset hands out batches:
    a permutation drawn from ``default_rng(1000 + client id)``, walked
    cyclically, with a fresh permutation from the same generator each time
    it runs out."""

    def __init__(self, client_id: int, x, y):
        self.x, self.y = x, y
        self.rng = np.random.default_rng(1000 + client_id)
        self.order, self.pos = self.rng.permutation(len(y)), 0

    def take(self, batch: int) -> np.ndarray:
        idx = []
        while len(idx) < batch:
            n = min(batch - len(idx), len(self.order) - self.pos)
            idx.extend(self.order[self.pos:self.pos + n])
            self.pos += n
            if self.pos >= len(self.order):
                self.order, self.pos = self.rng.permutation(len(self.y)), 0
        return np.asarray(idx)

    def round(self, steps: int, batch: int) -> dict:
        idx = np.stack([self.take(batch) for _ in range(steps)])
        return {"x": self.x[idx], "y": self.y[idx]}


class Recorder:
    """Round and fit spans, and the set-up rounds' globals for the check."""

    def __init__(self, keep_rounds: int):
        self.keep_rounds = keep_rounds
        self.deadline = None            # perf_counter time; None in set-up
        self.kept: list = []            # host copies of the first globals
        self.reset()

    def reset(self):
        self.starts: list[float] = []
        self.fit_s: list[float] = []
        self.fit_in_round: list[float] = []
        self.attempted = self.absorbed = self.samples = 0
        self._round_ann = None
        self._fit_sum = 0.0

    def _end_round(self):
        if self._round_ann is not None:
            self._round_ann.__exit__(None, None, None)
            self.fit_in_round.append(self._fit_sum)
        self._round_ann, self._fit_sum = None, 0.0

    def round_start(self, global_params):
        now = time.perf_counter()
        if self.deadline is not None and self.starts and now >= self.deadline:
            jax.block_until_ready(global_params)
            self.t_end = time.perf_counter()
            self._end_round()
            raise WindowClosed
        self._end_round()
        self.starts.append(now)
        self._round_ann = jax.profiler.TraceAnnotation("bench.round")
        self._round_ann.__enter__()

    def fit(self, client, ins):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.fit"):
            res = client.fit(ins)
        dt = time.perf_counter() - t0
        self.fit_s.append(dt)
        self._fit_sum += dt
        self.attempted += 1
        return res

    def aggregated(self, results, new_global, batch: int):
        ok = [r for _, r in results if np.isfinite(r.metrics.get("loss", np.nan))]
        self.absorbed += len(ok)
        self.samples += sum(r.metrics["steps_done"] * batch for r in ok)
        if self.deadline is None and len(self.kept) < self.keep_rounds:
            self.kept.append(jax.device_get(new_global))


class SpannedClient:
    """Delegates to a program client; its ``fit`` runs inside a span."""

    def __init__(self, inner, recorder: Recorder):
        self._inner, self._rec = inner, recorder

    def fit(self, ins):
        return self._rec.fit(self._inner, ins)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _strategy(recorder: Recorder, batch: int, unweighted: bool, **kw):
    from repro.core import FedAvg

    @dataclass
    class RecordedFedAvg(FedAvg):
        def configure_fit(self, rnd, global_params, client_ids, client_properties=None):
            recorder.round_start(global_params)
            return super().configure_fit(rnd, global_params, client_ids,
                                         client_properties=client_properties)

        def aggregate_fit(self, rnd, results, global_params):
            new = super().aggregate_fit(rnd, results, global_params)
            recorder.aggregated(results, new, batch)
            return new

        def _fit_weights(self, results):
            if unweighted:
                return jnp.ones((len(results),), jnp.float32)
            return super()._fit_weights(results)

    return RecordedFedAvg(**kw)


class Runner:
    def __init__(self, cell, seed: int, devices, faults=()):
        self.cell, self.seed, self.devices, self.faults = cell, seed, devices, faults
        self.check_rounds = cell.traffic["check_rounds"]

    def setup(self):
        from repro.core import JaxClient, Server
        from repro.core.cost_model import PROFILES
        from repro.core.server import make_cost_model_for
        from repro.data.federated import ClientDataset

        cfg, t = self.cell.config, self.cell.traffic
        model = common.program_model(cfg)
        k_params, _ = common.keys(self.seed)
        params = common.init_params(self.cell, k_params, model)
        mask = model.trainable_mask(params)
        self.leaf_sizes = [x.size for x in jax.tree.leaves(params)]
        self.rec = Recorder(self.check_rounds)
        loss_fn = common.planted_loss(model.loss_fn, self.faults)
        clients = [
            SpannedClient(JaxClient(
                client_id=c, loss_fn=loss_fn, dataset=ClientDataset(client_id=c, x=x, y=y),
                batch_size=t["batch"], trainable_mask=mask,
                device_profile=t["device_profile"]), self.rec)
            for c, (x, y) in enumerate(make_shards(cfg, t, self.seed))
        ]
        strategy = _strategy(self.rec, t["batch"], "unweighted" in self.faults,
                             local_epochs=t["epochs"], local_lr=t["lr"])
        cost = make_cost_model_for(params, [PROFILES[t["device_profile"]]] * len(clients))
        # eval_every past any round count: no evaluation inside the window
        self.server = Server(strategy=strategy, clients=clients, cost_model=cost,
                             eval_every=1 << 62)
        self.server.logger.quiet = True
        # the first rounds through Server.run itself: they compile (or load)
        # one local-training program per distinct step count, and the check
        # compares them
        self.g, history = self.server.run(params, self.check_rounds)
        self.readings = {"losses": [r.train_loss for r in history.rounds],
                         "first": self.rec.kept[0], "last": self.rec.kept[-1]}
        jax.block_until_ready(self.g)

    def window(self, seconds: float) -> Window:
        rec = self.rec
        rec.reset()
        t0 = time.perf_counter()
        rec.deadline = t0 + seconds
        try:
            self.server.run(self.g, 1 << 62)
        except WindowClosed:
            pass
        ends = rec.starts[1:] + [rec.t_end]
        round_s = [b - a for a, b in zip(rec.starts, ends)]
        return Window(
            t_start=t0, t_end=rec.t_end, rounds=len(round_s),
            attempted=rec.attempted, absorbed=rec.absorbed, samples=rec.samples,
            round_s=round_s,
            spans={"fit": rec.fit_s,
                   "round_self": [r - f for r, f in zip(round_s, rec.fit_in_round)]})

    def release(self):
        for name in ("g", "server"):
            self.__dict__.pop(name, None)

    def reference_rounds(self, dtype=jnp.float32):
        cfg, t = self.cell.config, self.cell.traffic
        ref = self.cell.reference
        k_params, _ = common.keys(self.seed)
        p0 = jax.jit(lambda k: ref.init_params(cfg, k))(k_params)
        shards = make_shards(cfg, t, self.seed)
        feeds = [CyclicBatches(c, x, y) for c, (x, y) in enumerate(shards)]

        def batches(rnd, c):
            steps = t["epochs"] * max(1, len(shards[c][1]) // t["batch"])
            return feeds[c].round(steps, t["batch"])

        out = fedavg.run_rounds(
            ref, cfg, p0, batches, [len(y) for _, y in shards], lr=t["lr"],
            rounds=self.check_rounds, dtype=dtype)
        return jax.device_get(p0), {"losses": out["losses"], "first": out["globals"][0],
                                    "last": out["globals"][-1]}

    def check(self) -> dict[str, float]:
        p0, ref = self.reference_rounds()
        return compare.training_numbers(p0, self.readings, ref)
