"""Runner of the cells whose window calls ``Server.run_scanned``: the
cohort's local training vmapped in one compiled program, its rounds in
chunks of ``rounds_per_call``, the clients' batches reused each round.

Traffic keys: ``clients``, ``examples_per_client``, ``batch``, ``epochs``,
``lr``, ``codec`` (``{"name": "null"}`` or ``{"name": "topk", "frac": f,
"leafwise": true}``), ``rounds_per_call``, ``check_rounds`` and ``data``
(class centers: ``center_std``, ``noise_std``).  Every client holds
``examples_per_client`` images made on the device from the seed and
trains ``epochs`` passes over them, each pass in a new order drawn from
the seed, in batches of ``batch``: every batch's rows differ.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.cells import common
from bench.harness import Refused, Window
from bench.reference import compare, fedavg


def make_batches(cfg: dict, traffic: dict, key):
    """(C, steps, B, H, W, ch) images and (C, steps, B) labels."""
    c, n, b, e = (traffic[k] for k in ("clients", "examples_per_client", "batch", "epochs"))
    if n % b:
        raise Refused("bench: examples_per_client must be a multiple of batch")
    hw, ch, k = cfg["image_size"], cfg["channels"], cfg["num_classes"]
    d = traffic["data"]
    kc, ky, kx, kp = jax.random.split(key, 4)
    centers = d["center_std"] * jax.random.normal(kc, (k, hw, hw, ch), jnp.float32)
    y = jax.random.randint(ky, (c, n), 0, k, jnp.int32)
    x = centers[y] + d["noise_std"] * jax.random.normal(kx, (c, n, hw, hw, ch), jnp.float32)
    perm = jax.vmap(lambda kk: jax.random.permutation(kk, n))(
        jax.random.split(kp, c * e)).reshape(c, e * n // b, b)
    take = jax.vmap(lambda a, i: a[i])
    return {"x": take(x, perm), "y": take(y, perm)}


class Runner:
    def __init__(self, cell, seed: int, devices, faults=()):
        self.cell, self.seed, self.devices, self.faults = cell, seed, devices, faults
        t = cell.traffic
        self.clients = t["clients"]
        self.steps = t["epochs"] * t["examples_per_client"] // t["batch"]
        self.per_call = t["rounds_per_call"]
        self.check_rounds = t["check_rounds"]
        if self.check_rounds % self.per_call:
            raise Refused("bench: check_rounds must be whole calls")

    # ---------------------------------------------------------------- set-up
    def _codec(self, params):
        from repro.core import NullCodec, SegmentMap, TopKCodec

        spec = self.cell.traffic["codec"]
        if spec["name"] == "null":
            return NullCodec()
        if spec["name"] == "topk":
            seg = SegmentMap.from_tree(params) if spec.get("leafwise") else None
            return TopKCodec(frac=spec["frac"], segments=seg)
        raise Refused(f"bench: unknown codec {spec['name']!r}")

    def setup(self):
        from repro.core import FedAvg, RoundSpec, Server
        from repro.optim import sgd

        cfg, t = self.cell.config, self.cell.traffic
        model = common.program_model(cfg)
        k_params, k_data = common.keys(self.seed)
        params = common.init_params(self.cell, k_params, model)
        self.batches = jax.jit(lambda k: make_batches(cfg, t, k))(k_data)
        self.leaf_sizes = [x.size for x in jax.tree.leaves(params)]
        self.codec = self._codec(params)
        self.server = Server(strategy=FedAvg(), clients=[])
        self.server.logger.quiet = True
        self.call_kw = dict(
            loss_fn=common.planted_loss(model.loss_fn, self.faults),
            opt=sgd(t["lr"]),
            spec=RoundSpec(max_steps=self.steps, execution_mode="parallel",
                           codec=self.codec),
            batches=self.batches, stacked_batches=False,
        )
        # the first rounds, through the window's own call: round 1 compiles
        # (or loads the cache) and warms up; the check compares them
        self.readings = {"losses": []}
        g = params
        for i in range(self.check_rounds // self.per_call):
            g, _, stacked = self._call(g)
            self.readings["losses"] += [float(v) for v in stacked["client_loss_mean"]]
            if "residual_norm_mean" in stacked:
                self.readings.setdefault("resid", []).extend(
                    float(v) for v in stacked["residual_norm_mean"])
            if i == 0:
                # the global after round 1 exists only where a call is a round
                self.readings["first"] = jax.device_get(g) if self.per_call == 1 else None
        self.readings["last"] = jax.device_get(g)
        self.g = g

    def _call(self, g):
        with jax.profiler.TraceAnnotation("bench.run_scanned"):
            g, history, stacked = self.server.run_scanned(g, self.per_call, **self.call_kw)
            jax.block_until_ready(g)
        return g, history, stacked

    # ---------------------------------------------------------------- window
    def window(self, seconds: float) -> Window:
        g = self.g
        t0 = t = time.perf_counter()
        win = Window(t0, t0, 0, 0, 0, 0)
        while True:
            g, _, stacked = self._call(g)
            now = time.perf_counter()
            win.round_s += [(now - t) / self.per_call] * self.per_call
            t = now
            win.rounds += self.per_call
            win.attempted += int(np.sum(stacked["dispatched"]))
            ok = np.isfinite(stacked["client_loss_mean"])
            absorbed = int(np.sum(np.where(ok, stacked["participants"], 0)))
            win.absorbed += absorbed
            win.samples += absorbed * self.steps * self.cell.traffic["batch"]
            if t - t0 >= seconds:
                break
        win.t_end = t
        self.g = g
        return win

    def release(self):
        for name in ("g", "batches", "server", "call_kw"):
            self.__dict__.pop(name, None)

    # ----------------------------------------------------------------- check
    def reference_rounds(self, dtype=jnp.float32):
        """The plain reference over the checked rounds, from the seed."""
        cfg, t = self.cell.config, self.cell.traffic
        ref = self.cell.reference
        k_params, k_data = common.keys(self.seed)
        p0 = jax.jit(lambda k: ref.init_params(cfg, k))(k_params)
        batches = jax.jit(lambda k: make_batches(cfg, t, k))(k_data)
        codec = t["codec"]
        frac = None
        if codec["name"] == "topk":
            frac = codec["frac"]
        out = fedavg.run_rounds(
            ref, cfg, p0, lambda r, c: jax.tree.map(lambda a: a[c], batches),
            np.ones(self.clients), lr=t["lr"], rounds=self.check_rounds,
            dtype=dtype, topk_frac=frac)
        return jax.device_get(p0), {
            "losses": out["losses"], "first": out["globals"][0],
            "last": out["globals"][-1], "resid": out["resid_norms"]}

    def check(self) -> dict[str, float]:
        p0, ref = self.reference_rounds()
        return compare.training_numbers(p0, self.readings, ref)
