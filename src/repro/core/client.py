"""FL clients — the paper's §4 on-device trainers, as JAX processes.

``Client`` mirrors the Flower client surface the paper describes (§4.1):
``get_weights`` / ``fit`` / ``evaluate`` / ``properties``.  ``JaxClient``
owns a local dataset shard + device profile and runs jitted local SGD; it
honors the server's config knobs: ``epochs``, the cutoff step budget
``max_steps`` (tau), and the uplink ``codec``.  When a codec is configured
the client ships a ``CompressedParameters`` delta payload (the actual
encoded wire, not an fp32 pytree) and carries its error-feedback residual
across rounds, mirroring the jitted engine's codec-owned client state.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.federated import ClientDataset
from repro.optim import Optimizer, sgd
from repro.utils.pytree import (
    tree_bytes, tree_size, tree_sq_norm, tree_sub, tree_where,
)

from .compression import compress_update
from .cost_model import PROFILES
from .protocol import (
    ClientProperties, EvaluateIns, EvaluateRes, FitIns, FitRes,
    compress_to_wire,
)

PyTree = Any

# jitted local-training fns shared across clients (same loss/steps/config ->
# same program; per-instance caches would recompile for every client)
_GLOBAL_FIT_CACHE: dict = {}


class Client:
    """Protocol-level client interface (paper §4.1)."""

    def get_weights(self, config: dict) -> PyTree:
        raise NotImplementedError

    def fit(self, ins: FitIns) -> FitRes:
        raise NotImplementedError

    def evaluate(self, ins: EvaluateIns) -> EvaluateRes:
        raise NotImplementedError

    def properties(self) -> ClientProperties:
        """Device/network facts the server's codec + tau policies consume."""
        return ClientProperties(client_id=-1)

    def reset_state(self) -> None:
        """Drop per-trajectory carry (e.g. error-feedback residuals).

        The Server calls this at the start of every ``run`` so reused client
        objects do not leak one experiment's compression state into the
        next."""

    def discard_update(self) -> None:
        """The scheduler discarded this client's last ``fit`` (deadline
        drop / staleness expiry): roll back any state that assumed the
        update was delivered.  ``fit`` commits the error-feedback residual
        as if the wire reached the server; an update that never did must
        leave the residual exactly as it entered the round — the same
        contract as the jitted engine's participation mask.  One level of
        rollback suffices: a client has at most one fit in flight (the
        Server never re-samples a busy client)."""

    def export_state(self):
        """Round-to-round carry as one flat fp32 row (or, for a segmented
        codec, a tuple of per-segment rows), or None if there is none —
        what ``LazyClientPool`` spills into a ``CohortState`` when it
        evicts this client (core/population.py's eviction contract)."""
        return None

    def import_state(self, state) -> None:
        """Rehydrate a previously ``export_state``-ed row on a freshly
        materialized client."""


@dataclass
class JaxClient(Client):
    client_id: int
    loss_fn: Callable                    # (params, batch) -> (loss, metrics)
    dataset: ClientDataset
    batch_size: int = 32
    optimizer: Optimizer | None = None
    trainable_mask: PyTree | None = None
    device_profile: str = "generic"
    _params: PyTree = None
    _fit_cache: dict = field(default_factory=dict, repr=False)
    _residual: Any = field(default=None, repr=False)  # error-feedback carry
    # pre-fit residual, kept until the scheduler's verdict: discard_update
    # rolls back to it when the arrival is dropped/expired
    _residual_prev: Any = field(default=None, repr=False)

    def __post_init__(self):
        if self.optimizer is None:
            self.optimizer = sgd(0.05)

    def get_weights(self, config: dict) -> PyTree:
        return self._params

    def properties(self) -> ClientProperties:
        prof = PROFILES.get(self.device_profile)
        return ClientProperties(
            client_id=self.client_id,
            device_profile=self.device_profile,
            uplink_mbps=prof.uplink_mbps if prof else 20.0,
            downlink_mbps=prof.downlink_mbps if prof else 50.0,
        )

    def reset_state(self) -> None:
        self._residual = None
        self._residual_prev = None

    def discard_update(self) -> None:
        self._residual = self._residual_prev

    def export_state(self):
        if self._residual is None:
            return None
        if isinstance(self._residual, tuple):  # segmented: leafwise rows
            return tuple(
                r if isinstance(r, tuple) else np.asarray(r)
                for r in self._residual
            )
        return np.asarray(self._residual)

    def import_state(self, state) -> None:
        if isinstance(state, (tuple, list)):  # segmented: leafwise rows
            row = tuple(
                r if isinstance(r, tuple) else jnp.asarray(r, jnp.float32)
                for r in state
            )
        else:
            row = jnp.asarray(state, jnp.float32)
        self._residual = row
        # the rollback point is the rehydrated row: a discard_update right
        # after re-materialization must be a no-op, not a reset to None
        self._residual_prev = row

    def steps_per_epoch(self) -> int:
        return self.dataset.steps_per_epoch(self.batch_size)

    @staticmethod
    def _comm_time_s(ins: FitIns, cfg: dict, prof) -> float:
        """This round's transfer time on the device's own links: the full
        global model down, the codec's wire (or the full model) up.  The
        downlink is always a raw pytree on the in-process transport."""
        codec = cfg.get("codec")
        down_b = tree_bytes(ins.parameters)
        up_b = (
            codec.wire_bytes(tree_size(ins.parameters))
            if codec is not None else down_b
        )
        return prof.comm_time_s(up_b, down_b)

    def _build_fit(self, n_steps: int, mu: float, lr: float):
        opt = sgd(lr) if lr else self.optimizer
        mask = self.trainable_mask

        def total_loss(params, batch, global_params):
            with jax.named_scope("fl.local.loss"):
                loss, metrics = self.loss_fn(params, batch)
                if mu > 0:
                    loss = loss + 0.5 * mu * tree_sq_norm(tree_sub(params, global_params))
            return loss, metrics

        @jax.jit
        def fit_steps(global_params, batches, budget):
            opt_state = opt.init(global_params)

            def step(carry, batch):
                params, opt_state, i = carry
                (loss, _), grads = jax.value_and_grad(total_loss, has_aux=True)(
                    params, batch, global_params
                )
                with jax.named_scope("fl.local.update"):
                    new_params, new_opt = opt.update(grads, params, opt_state, i)
                    if mask is not None:
                        new_params = jax.tree.map(
                            lambda n, o, m: n if m else o, new_params, params, mask
                        )
                    live = i < budget
                    params = tree_where(live, new_params, params)
                    opt_state = tree_where(live, new_opt, opt_state)
                    loss = jnp.where(live, loss, 0.0)
                return (params, opt_state, i + 1), loss

            (params, _, _), losses = jax.lax.scan(
                step, (global_params, opt_state, jnp.zeros((), jnp.int32)), batches
            )
            n_steps_done = jnp.minimum(budget, losses.shape[0])
            return params, jnp.sum(losses) / jnp.maximum(1, n_steps_done)

        return fit_steps

    def fit(self, ins: FitIns) -> FitRes:
        self._residual_prev = self._residual  # rollback point (discard_update)
        cfg = ins.config
        epochs = int(cfg.get("epochs", 1))
        spe = self.steps_per_epoch()
        full_steps = epochs * spe
        budget = int(cfg.get("max_steps", full_steps))
        # on-device deadline enforcement: a client that knows its own step
        # time AND link speeds truncates local work so compute + comm fit
        # the round cutoff, instead of being dropped by the scheduler (the
        # server-side FedTau budget is compute-only; this closes the gap
        # for comm-heavy rounds and covers strategies shipping only the
        # deadline).  If even one step + comm cannot fit, the client tries
        # anyway — the scheduler will judge it.
        deadline = float(cfg.get("deadline_s", 0.0))
        prof = PROFILES.get(self.device_profile)
        if deadline > 0.0 and prof is not None:
            budget = max(
                1, min(budget, prof.steps_in_budget(
                    max(0.0, deadline - self._comm_time_s(ins, cfg, prof))
                ))
            )
        mu = float(cfg.get("mu", 0.0))
        lr = float(cfg.get("lr", 0.0))

        with jax.profiler.TraceAnnotation("fl.fit.batch"):
            batches = [self.dataset.next_batch(self.batch_size) for _ in range(full_steps)]
            stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}

        # lr == 0.0 means the built closure captures self.optimizer, so the
        # optimizer's identity must be part of the key — without it, two
        # clients sharing a loss_fn but constructed with different
        # optimizers (e.g. different SGD momenta) would silently share the
        # first client's update rule
        cache_key = (
            id(self.loss_fn), id(self.trainable_mask), full_steps, mu, lr,
            None if lr else id(self.optimizer),
        )
        if cache_key not in _GLOBAL_FIT_CACHE:
            _GLOBAL_FIT_CACHE[cache_key] = self._build_fit(full_steps, mu, lr)
        fit_steps = _GLOBAL_FIT_CACHE[cache_key]
        with jax.profiler.TraceAnnotation("fl.fit.step"):
            params, mean_loss = fit_steps(
                ins.parameters, stacked, jnp.asarray(budget, jnp.int32)
            )
        self._params = params
        with jax.profiler.TraceAnnotation("fl.fit.sync"):
            loss = float(mean_loss)
        metrics = {
            "loss": loss,
            "steps_done": min(budget, full_steps),
            "device_profile": self.device_profile,
        }

        codec = cfg.get("codec")
        if codec is not None:
            # compressed uplink: encode the delta (plus the carried error-
            # feedback residual) and ship the actual wire payload
            n_params = tree_size(params)
            residual = self._residual
            if codec.segments is not None:
                # segmented carry is a tuple of per-segment rows; anything
                # else (fresh client, codec switch) re-inits inside
                # compress_update
                if not isinstance(residual, tuple) or len(residual) != len(
                    codec.segments
                ):
                    residual = None
            elif (
                residual is None
                or isinstance(residual, tuple)
                or residual.shape != (n_params,)
            ):
                residual = jnp.zeros((n_params,), jnp.float32)
            with jax.profiler.TraceAnnotation("fl.fit.encode"):
                enc, self._residual = compress_update(
                    codec, params, ins.parameters, residual=residual
                )
                wire = compress_to_wire(codec, enc, n_params)
            metrics["wire_bytes"] = wire.num_bytes
            return FitRes(
                parameters=wire, num_examples=len(self.dataset), metrics=metrics,
            )

        return FitRes(
            parameters=params, num_examples=len(self.dataset), metrics=metrics,
        )

    def evaluate(self, ins: EvaluateIns) -> EvaluateRes:
        n = min(len(self.dataset), 512)
        batch = {"x": self.dataset.x[:n], "y": self.dataset.y[:n]}
        loss, metrics = jax.jit(self.loss_fn)(ins.parameters, batch)
        return EvaluateRes(
            loss=float(loss),
            num_examples=n,
            metrics={k: float(v) for k, v in metrics.items()},
        )
