"""The FL loop — Flower's server architecture (paper §3, Figure 1).

``Server`` orchestrates rounds and delegates all decisions to the Strategy;
the CostModel plays the role of the physical fleet, charging wall-time and
energy for every client's compute and communication.  History captures the
paper's evaluation axes: accuracy / convergence time / energy per round.

``Server.run`` is a thin driver over the **virtual-clock scheduler**
(core/scheduler.py): every dispatched client becomes an ``Arrival`` event
on a simulated timeline, and the configured ``RoundPolicy`` — lockstep
``SyncAll`` (the default, reproducing the classic synchronous loop),
``Deadline(tau)`` straggler cutoffs, or ``BufferedAsync`` staleness-tolerant
aggregation — decides which arrivals each round consumes.  Wall time is the
clock's elapsed virtual time, idle burn comes from the actual wait
intervals the policy induced, and ``History`` records who participated and
how stale their updates were.  An ``AvailabilityTrace`` adds seeded
dropout/late-join churn and step-time jitter on top.

**Population mode** (``population`` + ``cohort_size`` set): the same loop
at fleet scale.  Nothing per-round is O(N): the cohort is sampled id-first
from the packed ``Population`` (``Strategy.sample_cohort``), availability
and jitter are *streamed* over just those ids, client objects come from a
``LazyClientPool`` that materializes on demand, properties/eval touch only
the round's cohort, and the uplink fallback is one scalar (``MixedCodec``
is rejected — its static client-slot assignment cannot follow a resampled
cohort).  With N == cohort_size, no churn, and the same strategy seed, the
population round is bitwise the legacy round (pinned in
tests/test_population.py).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np

from repro.utils.logging import MetricsLogger
from repro.utils.pytree import tree_add, tree_bytes, tree_size, tree_sub

from .cost_model import AvailabilityTrace, CostModel
from .protocol import (
    CompressedParameters, EvaluateIns, Parameters, parameters_to_pytree,
)
from .scheduler import Arrival, Deadline, RoundPolicy, SyncAll, VirtualClock
from .strategy.base import Strategy

PyTree = Any


@dataclass
class RoundRecord:
    rnd: int
    train_loss: float
    eval_loss: float | None
    eval_acc: float | None
    wall_time_s: float       # simulated fleet wall-clock for the round
    energy_j: float          # simulated fleet energy
    comm_bytes: int
    steps: int
    # virtual-clock participation record: how many updates this round's
    # aggregation consumed, how many arrivals it discarded (deadline drops
    # + staleness expiries), and the mean staleness of what it kept
    participants: int = 0
    dropped: int = 0
    staleness_mean: float = 0.0


@dataclass
class History:
    rounds: list[RoundRecord] = field(default_factory=list)

    def add(self, rec: RoundRecord) -> None:
        self.rounds.append(rec)

    @property
    def total_time_s(self) -> float:
        return sum(r.wall_time_s for r in self.rounds)

    @property
    def total_energy_j(self) -> float:
        return sum(r.energy_j for r in self.rounds)

    def final_accuracy(self) -> float | None:
        for r in reversed(self.rounds):
            if r.eval_acc is not None:
                return r.eval_acc
        return None

    def accuracy_series(self) -> list[tuple[int, float]]:
        return [(r.rnd, r.eval_acc) for r in self.rounds if r.eval_acc is not None]

    def time_to_accuracy(self, target: float) -> float | None:
        """Simulated convergence time (paper: 'Convergence Time (mins)')."""
        t = 0.0
        for r in self.rounds:
            t += r.wall_time_s
            if r.eval_acc is not None and r.eval_acc >= target:
                return t
        return None


class _UniformUplink:
    """O(1) stand-in for the per-client uplink-fallback list in population
    mode: every client of a non-mixed codec ships the same wire size, so
    indexing by any client id answers the one scalar."""

    def __init__(self, nbytes: int):
        self.nbytes = int(nbytes)

    def __getitem__(self, client_id: int) -> int:
        return self.nbytes


@dataclass
class Server:
    strategy: Strategy
    clients: Any                         # list[Client] | population.LazyClientPool
    cost_model: CostModel | None = None
    eval_fn: Callable | None = None      # (params) -> dict (centralized eval)
    eval_every: int = 1
    codec: Any = None                    # UpdateCodec: uplink charged at
                                         # codec.wire_bytes, not tree_bytes
    policy: RoundPolicy | None = None    # None -> SyncAll (lockstep FedAvg)
    availability: AvailabilityTrace | None = None
    # population mode: a packed Population plus an explicit per-round cohort
    # size; `clients` is then typically a LazyClientPool over the same ids
    population: Any = None
    cohort_size: int | None = None
    logger: MetricsLogger = field(default_factory=lambda: MetricsLogger("server"))
    # compiled-program memo for run_scanned: without it every call builds a
    # fresh closure and jax.jit re-traces/re-compiles the WHOLE R-round
    # program (sweeps and benchmarks pay full compile per run)
    _scan_fns: dict = field(default_factory=dict, repr=False, compare=False)

    def run(self, global_params: PyTree, num_rounds: int) -> tuple[PyTree, History]:
        policy = self.policy if self.policy is not None else SyncAll()
        clock = VirtualClock()
        history = History()
        pop = self.population
        if pop is not None:
            # population mode: nothing O(N) per run or per round — no id
            # list, no all-client properties dict, no all-client reset loop
            if not self.cohort_size:
                raise ValueError("population mode needs an explicit cohort_size")
            from .compression import MixedCodec

            if isinstance(self.codec, MixedCodec):
                raise TypeError(
                    "MixedCodec binds codecs to static client slots; a "
                    "population cohort is resampled every round — use "
                    "BandwidthCodecPolicy for per-device codec choice"
                )
            client_ids = None
            reset_all = getattr(self.clients, "reset_state", None)
            if callable(reset_all):  # LazyClientPool: one call, not N
                reset_all()
            else:
                for c in self.clients:
                    c.reset_state()
        else:
            client_ids = list(range(len(self.clients)))
            client_props = {
                cid: self.clients[cid].properties() for cid in client_ids
            }
            for c in self.clients:  # fresh trajectory: no residual carry-over
                c.reset_state()
        # fresh server trajectory too: FedOpt moments must not leak from a
        # previous run, but DO accumulate across this run's rounds
        self.strategy.reset_server_state()

        # per-client uplink fallback for raw-pytree payloads under a
        # server-level codec (static across the run: the model shape is);
        # population mode charges one scalar — a non-mixed codec ships the
        # same wire size from every client, and an O(N) list would defeat
        # the packed representation
        if self.cost_model is None:
            uplink_fallback = None
        elif pop is not None:
            uplink_fallback = (
                None if self.codec is None else _UniformUplink(
                    self.codec.wire_bytes(tree_size(global_params))
                )
            )
        else:
            uplink_fallback = CostModel.fleet_uplink_bytes(
                self.codec, tree_size(global_params), len(self.clients)
            )

        # the cutoff rides in FitIns config ONLY when a Deadline policy will
        # actually enforce it: clients that know their own step time + links
        # then truncate local work to make the cutoff instead of being
        # dropped.  Under SyncAll nothing is ever dropped, so shipping a
        # deadline there would silently shrink step budgets (diverging from
        # the paper's compute-only tau semantics) for zero scheduling gain.
        deadline_cfg = None
        if isinstance(policy, Deadline):
            tau = policy.resolve_tau(self.strategy)
            deadline_cfg = tau if np.isfinite(tau) else None

        pending: list[Arrival] = []  # in-flight arrivals (BufferedAsync carry)
        for rnd in range(1, num_rounds + 1):
            with jax.profiler.StepTraceAnnotation("fl.round", step_num=rnd):
                # ---- dispatch: sampled ∩ available ∩ not already in flight ----
                busy = {a.client_id for a in pending}
                if pop is not None:
                    # cohort first, availability streamed over candidates only
                    # (inside sample_cohort) — then per-cohort properties and
                    # per-dispatch streamed jitter: all O(cohort), never O(N)
                    eligible = self.strategy.sample_cohort(
                        rnd, pop, self.cohort_size, exclude=busy,
                        availability=self.availability,
                        cost_model=self.cost_model, deadline_s=deadline_cfg,
                    )
                    # heavy churn can leave the bounded redraw short — or empty.
                    # A short/empty cohort follows the legacy empty-round path
                    # below: zero dispatches, the policy still advances the
                    # clock, nothing aggregates, the round records participants=0
                    # with NaN train_loss (pinned by tests/test_population.py
                    # ::test_forced_churn_short_and_empty_cohorts)
                    client_props = {
                        cid: self.clients[cid].properties() for cid in eligible
                    }
                    jitter = None
                else:
                    # one trace draw per round (it is a deterministic function
                    # of (seed, rnd)), not one full-fleet draw per client
                    up = (
                        self.availability.available(rnd)
                        if self.availability is not None else None
                    )
                    eligible = [
                        cid for cid in client_ids
                        if cid not in busy and (up is None or up[cid])
                    ]
                    jitter = (
                        self.availability.step_jitter(rnd)
                        if self.availability is not None else None
                    )
                with jax.profiler.TraceAnnotation("fl.configure_fit"):
                    fit_ins = self.strategy.configure_fit(
                        rnd, global_params, eligible, client_properties=client_props
                    ) if eligible else []
                jitter_by_cid = {}
                if pop is not None and self.availability is not None and fit_ins:
                    cids = [cid for cid, _ in fit_ins]
                    jitter_by_cid = dict(zip(
                        cids, self.availability.step_jitter_for(rnd, cids).tolist()
                    ))

                launch_steps = 0
                for cid, ins in fit_ins:
                    if deadline_cfg is not None:
                        ins.config.setdefault("deadline_s", deadline_cfg)
                    with jax.profiler.TraceAnnotation("fl.fit"):
                        res = self.clients[cid].fit(ins)
                    steps = int(res.metrics.get("steps_done", 1))
                    launch_steps += steps
                    cost = None
                    up_bytes = self._uplink_bytes_one(res, cid, uplink_fallback)
                    if self.cost_model is not None:
                        if jitter is not None:
                            jit_c = float(jitter[cid])
                        else:
                            jit_c = float(jitter_by_cid.get(cid, 1.0))
                        cost = self.cost_model.client_round_cost(
                            cid, steps, uplink_bytes=up_bytes, jitter=jit_c,
                        )
                        # the cost record owns the arrival time; the scheduler
                        # event (Arrival.finish_t) is derived from it below
                        cost.t_arrival_s = clock.now + cost.t_total_s
                    # keep the launch global only when a stale rebase could need
                    # it: compressed payloads are deltas (global-independent), so
                    # pinning a full model snapshot per in-flight arrival would
                    # be O(pending x model) of provably dead memory
                    launch_ref = (
                        None if isinstance(res.parameters, CompressedParameters)
                        else global_params
                    )
                    pending.append(Arrival(
                        client_id=cid, launch_rnd=rnd, launch_t=clock.now,
                        finish_t=cost.t_arrival_s if cost is not None else clock.now,
                        cost=cost, payload=(res, launch_ref), uplink_bytes=up_bytes,
                    ))

                # ---- the policy's verdict on everything in flight ----
                with jax.profiler.TraceAnnotation("fl.policy"):
                    outcome = policy.plan(clock, pending, rnd, strategy=self.strategy)
                    pending = list(outcome.carried)
                    clock.advance_to(outcome.round_end)

                    # a discarded update never reached the aggregate: the client
                    # must roll back any state (error-feedback residual) that its
                    # fit() committed assuming delivery — the python-path twin of
                    # the jitted mask's carry-residual-unchanged contract
                    for a in (*outcome.dropped, *outcome.expired):
                        self.clients[a.client_id].discard_update()

                results = []
                for a in outcome.reported:
                    res, launch_global = a.payload
                    res.staleness = a.staleness_at(rnd)
                    if res.staleness > 0:
                        self._rebase_stale(res, launch_global, global_params)
                    results.append((a.client_id, res))

                if results:  # an empty round advances the clock, aggregates nothing
                    with jax.profiler.TraceAnnotation("fl.aggregate_fit"):
                        global_params = self.strategy.aggregate_fit(
                            rnd, results, global_params
                        )

                # ---- system-cost accounting (the paper's §5 measurement) ----
                # wall time is the clock's elapsed virtual time for this round;
                # idle burn charges the actual wait each reporter endured; a
                # deadline-dropped client charges its (wasted) compute up to the
                # cutoff; uplink is charged at each reporter's wire size while
                # the downlink stays the full-precision global per dispatch.
                wall, energy, comm = outcome.wall_time_s, 0.0, 0
                if self.cost_model is not None:
                    down = self.cost_model.update_bytes
                    energy = self._outcome_energy(outcome)
                    # expired arrivals that LANDED did cross the network (they
                    # arrived, then aged out) — their bytes count like their
                    # comm energy does; cancelled-in-flight expiries and
                    # deadline-dropped clients never completed an uplink
                    comm = down * len(fit_ins) + sum(
                        down if a.uplink_bytes is None else a.uplink_bytes
                        for a in (*outcome.reported, *outcome.expired)
                        if a.finish_t <= outcome.round_end
                    )

                losses = [r.metrics.get("loss", 0.0) for _, r in results]
                ns = [r.num_examples for _, r in results]
                # all-zero example counts (empty shards / failed reads) must not
                # crash np.average with a ZeroDivisionError: unweighted fallback;
                # an empty round has no losses at all -> NaN, not a crash
                if not losses:
                    train_loss = float("nan")
                else:
                    train_loss = float(
                        np.average(losses, weights=ns) if sum(ns) > 0 else np.mean(losses)
                    )

                eval_loss = eval_acc = None
                if rnd % self.eval_every == 0:
                    # population mode restricts eval_fn-less federated eval to
                    # the round's cohort: evaluating N clients would be the
                    # O(N) loop this mode exists to avoid
                    with jax.profiler.TraceAnnotation("fl.evaluate"):
                        eval_loss, eval_acc = self._evaluate(
                            global_params,
                            eval_ids=eligible if pop is not None else None,
                        )

                rec = RoundRecord(
                    rnd=rnd, train_loss=train_loss, eval_loss=eval_loss,
                    eval_acc=eval_acc, wall_time_s=wall, energy_j=energy,
                    comm_bytes=comm, steps=launch_steps,
                    participants=len(results),
                    dropped=len(outcome.dropped) + len(outcome.expired),
                    staleness_mean=outcome.mean_staleness,
                )
                history.add(rec)
                self.logger.log(
                    "round", rnd=rnd, loss=train_loss,
                    acc=-1.0 if eval_acc is None else eval_acc,
                    wall_s=wall, energy_kj=energy / 1e3,
                    clients=len(results), stale=outcome.mean_staleness,
                )

        # arrivals still in flight when the run ends are abandoned: their
        # clients roll back (the update never landed), and the wasted work
        # is charged to the final round — otherwise BufferedAsync's cost
        # totals would silently omit exactly its stragglers' burn
        self._abandon_pending(pending, clock, history)
        return global_params, history

    # ---- rounds-as-scan driver (PR 8) ----

    def run_scanned(
        self,
        global_params: PyTree,
        num_rounds: int,
        *,
        loss_fn: Callable,
        opt,
        spec,
        batches,
        weights=None,
        step_budgets=None,
        stacked_batches: bool = True,
        trainable_mask: PyTree | None = None,
        reference: bool = False,
        donate: bool = True,
    ) -> tuple[PyTree, History, dict]:
        """Run ``num_rounds`` rounds as ONE compiled ``lax.scan`` over the
        jitted engine (``rounds.make_multi_round_step``) instead of
        re-entering python every round.

        The whole run's schedule — availability churn, step jitter, cohort
        priorities, per-client finish times — is precomputed host-side as
        (R, C) matrices from the same seeded draws ``Server.run`` makes,
        then the scan computes each round's dispatch mask, the policy's
        pure-array verdict, and the round step on device; per-round
        metrics stack on device and decode to a ``History`` once at the
        end.  Cost accounting (energy/comm/steps) replays the CostModel's
        arithmetic over the returned masks post-hoc, so nothing syncs
        mid-run.  Differences from ``run``, by construction: evaluation
        happens once, on the final global (``eval_fn`` only — a per-round
        eval would reintroduce the per-round host sync this driver
        removes), ``train_loss`` is the engine's weights-weighted
        ``client_loss_mean``, and deadline stragglers are dropped rather
        than offered a truncated step budget.

        ``reference=True`` runs the SAME schedule, verdict helpers, and
        jitted ``round_step`` through a per-round python loop with a host
        sync each round — the bitwise-parity reference (and the rounds/sec
        baseline ``benchmarks/scan_bench.py`` measures against).

        ``batches`` leaves are (R, C, max_steps, ...) when
        ``stacked_batches``, else (C, max_steps, ...) reused every round
        (closed over as a scan constant — device memory stays flat in R).
        With ``donate`` the carry buffers (global/server/client state) are
        donated to the compiled program; inputs are copied first so the
        caller's arrays stay valid.

        Returns ``(final_global, history, stacked)`` where ``stacked`` is
        the numpy dict of per-round device outputs (metrics plus
        ``participation_mask``/``dispatch_mask``/``round_wall_s``/
        ``participants``/``dispatched``).
        """
        import jax.numpy as jnp

        from repro.utils.pytree import tree_size as _tree_size

        from .rounds import (
            cohort_dispatch_mask, make_multi_round_step, make_round_step,
        )

        if self.population is not None:
            raise NotImplementedError(
                "run_scanned needs a static client axis; population-mode "
                "cohort gather/scatter is host-side — use Server.run"
            )
        policy = self.policy if self.policy is not None else SyncAll()
        tau = (
            policy.resolve_tau(self.strategy)
            if isinstance(policy, Deadline) else None
        )

        R = int(num_rounds)
        leaf = jax.tree.leaves(batches)[0]
        C = int(leaf.shape[1] if stacked_batches else leaf.shape[0])
        if stacked_batches and int(leaf.shape[0]) != R:
            raise ValueError(
                f"stacked batches carry {int(leaf.shape[0])} rounds, "
                f"run asked for {R}"
            )
        w = (
            jnp.ones((C,), jnp.float32) if weights is None
            else jnp.asarray(weights)
        )
        bud = (
            jnp.full((C,), spec.max_steps, jnp.int32) if step_budgets is None
            else jnp.asarray(step_budgets, jnp.int32)
        )
        n_params = _tree_size(global_params)
        with jax.profiler.TraceAnnotation("fl.scan.schedule"):
            sched = self._scan_schedule(spec, R, C, np.asarray(bud), n_params)
        avail = jnp.asarray(sched["avail"])
        t_verdict = jnp.asarray(sched["t_verdict"])
        pri = jnp.asarray(sched["pri"])

        self.strategy.reset_server_state()
        server_state = self.strategy.init_state(global_params)
        client_state = spec.codec.init_client_state(C, n_params)

        # memoize the jitted program: closures are fresh objects, so
        # without this every call re-traces AND re-compiles the whole
        # R-round scan (id()s are kept alive by the value tuple)
        key = (
            "ref" if reference else "scan", R, C, stacked_batches, donate,
            repr(spec), repr(policy), tau, self.cohort_size,
            id(loss_fn), id(opt), id(trainable_mask),
        )
        cached = self._scan_fns.get(key)

        if not reference:
            if cached is None:
                multi = make_multi_round_step(
                    loss_fn, opt, self.strategy, spec, R, policy=policy,
                    tau=tau, cohort_size=self.cohort_size,
                    trainable_mask=trainable_mask,
                    stacked_batches=stacked_batches,
                )
                fn = (
                    jax.jit(multi, donate_argnums=(0, 1, 2)) if donate
                    else jax.jit(multi)
                )
                self._scan_fns[key] = (fn, (loss_fn, opt, trainable_mask))
            else:
                fn = cached[0]
            with jax.profiler.TraceAnnotation("fl.scan.run"):
                if donate:
                    # donated buffers alias in-place across the scan carry —
                    # copy first so the CALLER's arrays stay valid
                    global_params = jax.tree.map(jnp.array, global_params)
                g, _, _, stacked = fn(
                    global_params, server_state, client_state, batches, w, bud,
                    avail, t_verdict, pri,
                )
            with jax.profiler.TraceAnnotation("fl.scan.fetch"):
                stacked = jax.device_get(stacked)
        else:
            if cached is None:
                round_step = jax.jit(make_round_step(
                    loss_fn, opt, self.strategy, spec, trainable_mask
                ))
                self._scan_fns[key] = (
                    round_step, (loss_fn, opt, trainable_mask)
                )
            else:
                round_step = cached[0]
            g, ss, cs = global_params, server_state, client_state
            rows = []
            for r in range(R):
                if self.cohort_size is None:
                    dispatch_mask = avail[r]
                else:
                    dispatch_mask = cohort_dispatch_mask(
                        pri[r], avail[r], self.cohort_size
                    )
                mask, round_end = policy.plan_arrays(
                    dispatch_mask, t_verdict[r], tau=tau
                )
                batch_r = (
                    jax.tree.map(lambda x: x[r], batches)
                    if stacked_batches else batches
                )
                g, ss, cs, met = round_step(
                    g, ss, cs, batch_r, w, bud, jnp.int32(r + 1), mask
                )
                # the python driver's defining cost: one host round-trip
                # per round (Server.run pulls metrics exactly like this)
                rows.append(jax.device_get({
                    **met,
                    "participation_mask": mask,
                    "dispatch_mask": dispatch_mask,
                    "round_wall_s": round_end,
                    "participants": jnp.sum(jnp.where(mask > 0, 1.0, 0.0)),
                    "dispatched": jnp.sum(
                        jnp.where(dispatch_mask > 0, 1.0, 0.0)
                    ),
                }))
            stacked = {
                k: np.stack([row[k] for row in rows]) for k in rows[0]
            }

        eval_final = (
            self._evaluate(g) if self.eval_fn is not None else None
        )
        with jax.profiler.TraceAnnotation("fl.scan.history"):
            history = self._decode_scan_history(
                stacked, sched, np.asarray(bud), eval_final
            )
        self.logger.log(
            "scanned", rounds=R, driver="python" if reference else "scan",
            loss=history.rounds[-1].train_loss if history.rounds else -1.0,
            wall_s=history.total_time_s,
        )
        return g, history, stacked

    def _scan_schedule(
        self, spec, R: int, C: int, budgets: np.ndarray, n_params: int
    ) -> dict:
        """Host-side precompute of the whole run's (R, C) schedule.

        Rows reuse the exact per-round seeded draws ``run`` makes
        (``available``/``step_jitter`` stacked), plus stream-4 cohort
        priorities; finish times come from ``CostModel.fleet_time_matrix``
        (same arithmetic as ``client_round_cost``).  ``t_verdict`` is the
        float32 copy both drivers schedule against — the verdict must be
        computed at ONE precision or scanned/reference could disagree on
        a client landing exactly at tau.
        """
        rounds = range(1, R + 1)
        trace = self.availability
        if trace is None:
            avail = np.ones((R, C), np.float32)
            jitter = np.ones((R, C), np.float64)
        else:
            avail = trace.available_matrix(rounds)
            jitter = trace.step_jitter_matrix(rounds)
        if self.cohort_size is not None:
            pri_trace = trace if trace is not None else AvailabilityTrace.full(C)
            pri = pri_trace.cohort_priority_matrix(rounds)
        else:
            pri = np.zeros((R, C), np.float32)
        out = {"avail": avail, "pri": pri, "cols": None, "t_compute": None}
        if self.cost_model is None:
            out["t_verdict"] = np.zeros((R, C), np.float32)
            return out
        up = CostModel.fleet_uplink_bytes(spec.codec, n_params, C)
        cols = self.cost_model.fleet_columns(C, uplink_bytes=up)
        t_compute = (
            (np.asarray(budgets, np.float64) * cols["step_time_s"])[None, :]
            * jitter
        )
        out["cols"] = cols
        out["t_compute"] = t_compute
        out["t_verdict"] = np.asarray(
            t_compute + cols["t_comm_s"][None, :], np.float32
        )
        return out

    def _decode_scan_history(
        self, stacked: dict, sched: dict, budgets: np.ndarray, eval_final
    ) -> History:
        """Stacked device outputs -> History, once, after the run.

        Energy replays ``_outcome_energy``'s rules vectorized: reporters
        charge full compute+comm plus idle burn until round end; deadline-
        dropped dispatches charge ``wasted_energy``'s phase split
        (downlink radio, then compute, then uplink radio) within the round
        window; comm charges the downlink per dispatch and the codec wire
        uplink per reporter.
        """
        R, C = stacked["participation_mask"].shape
        cm = self.cost_model
        cols = sched["cols"]
        history = History()
        for r in range(R):
            reported = stacked["participation_mask"][r] > 0
            dispatched = stacked["dispatch_mask"][r] > 0
            wall = float(stacked["round_wall_s"][r])
            energy, comm = 0.0, 0
            if cm is not None:
                t_compute = sched["t_compute"][r]
                t_total = t_compute + cols["t_comm_s"]
                e_total = (
                    t_compute * cols["active_power_w"]
                    + cols["t_comm_s"] * cm.comm_power_w
                )
                idle = (
                    np.clip(wall - t_total, 0.0, None) * cols["idle_power_w"]
                )
                t_down = cols["t_down_s"]
                wasted = np.where(
                    wall >= t_total,
                    e_total,
                    np.minimum(wall, t_down) * cm.comm_power_w
                    + np.clip(wall - t_down, 0.0, t_compute)
                    * cols["active_power_w"]
                    + np.clip(wall - t_down - t_compute, 0.0, None)
                    * cm.comm_power_w,
                )
                per_client = np.where(reported, e_total + idle, wasted)
                energy = float(np.sum(per_client[dispatched]))
                comm = int(
                    cm.update_bytes * int(dispatched.sum())
                    + np.sum(cols["up_bytes"][reported])
                )
            eval_loss = eval_acc = None
            if r == R - 1 and eval_final is not None:
                eval_loss, eval_acc = eval_final
            history.add(RoundRecord(
                rnd=r + 1,
                train_loss=float(stacked["client_loss_mean"][r]),
                eval_loss=eval_loss, eval_acc=eval_acc, wall_time_s=wall,
                energy_j=energy, comm_bytes=comm,
                steps=int(np.sum(budgets[dispatched])),
                participants=int(reported.sum()),
                dropped=int(dispatched.sum() - reported.sum()),
            ))
        return history

    def _abandon_pending(self, pending, clock, history) -> None:
        for a in pending:
            self.clients[a.client_id].discard_update()
        if not pending or not history.rounds or self.cost_model is None:
            return
        rec = history.rounds[-1]
        down = self.cost_model.update_bytes
        for a in pending:
            if a.cost is None:
                continue
            # downlink-then-compute burn for the window that fit before the
            # experiment ended; uplink bytes only if the upload finished
            # (the downlink bytes were already counted at dispatch time)
            rec.energy_j += self._wasted_energy(a, clock.now)
            if a.finish_t <= clock.now:
                rec.comm_bytes += (
                    down if a.uplink_bytes is None else a.uplink_bytes
                )

    @staticmethod
    def _uplink_bytes_one(res, cid: int, fallback) -> int | None:
        """One client's uplink charge: the actual serialized wire size for
        wire-format payloads, the server-level codec's size for raw pytrees
        under a codec (a per-client list, or ``_UniformUplink`` in
        population mode), else None (the full-precision default)."""
        p = res.parameters
        if isinstance(p, (Parameters, CompressedParameters)):
            return p.num_bytes
        return None if fallback is None else fallback[cid]

    def _outcome_energy(self, outcome) -> float:
        """Fleet energy for one scheduled round.

        Reporters charge their full compute+comm plus idle burn for the
        wait between their arrival and the round end; deadline-dropped
        clients charge what they actually burned before the cutoff (the
        downlink happens FIRST on the arrival timeline, then compute —
        radio power for the downlink window, active power for whatever
        compute fit after it) and never uplink; staleness-expired arrivals
        completed their (wasted) work in full.  Each arrival is charged in
        the round that resolves it.
        """
        e = 0.0
        for a in outcome.reported:
            p = self._profile(a.client_id)
            e += a.cost.e_total_j
            e += max(0.0, outcome.round_end - a.finish_t) * p.idle_power_w
        for a in outcome.dropped:
            e += self._wasted_energy(a, outcome.round_end)
        for a in outcome.expired:
            # landed expiries burned their full cost; one still in flight
            # was cancelled at round end — only the window's burn happened
            e += self._wasted_energy(a, outcome.round_end)
        return e

    def _profile(self, cid: int):
        return self.cost_model.profile_for(cid)

    def _wasted_energy(self, a: Arrival, until: float) -> float:
        """Burn of an abandoned arrival inside its [launch_t, until) window
        (the CostModel owns the phase-split arithmetic)."""
        return self.cost_model.wasted_energy(
            a.cost, max(0.0, until - a.launch_t)
        )

    @staticmethod
    def _rebase_stale(res, launch_global: PyTree, global_params: PyTree) -> None:
        """Apply a stale update's *delta* to the current global.

        ``CompressedParameters`` already IS a delta wire (decoded against
        whatever global the aggregation holds), so it needs no rebase; raw
        parameter payloads trained from an older global are rewritten as
        ``current + (params - launch_global)`` — FedBuff's update rule.
        """
        p = res.parameters
        if isinstance(p, CompressedParameters):
            return
        if isinstance(p, Parameters):
            p = parameters_to_pytree(p, launch_global)
        res.parameters = tree_add(global_params, tree_sub(p, launch_global))

    def _evaluate(
        self, global_params, eval_ids=None
    ) -> tuple[float | None, float | None]:
        if self.eval_fn is not None:
            m = self.eval_fn(global_params)
            return m.get("loss"), m.get("acc")
        # federated evaluation: average client-side evaluate() — over the
        # whole fleet (legacy), or over `eval_ids` (population mode hands
        # the round's cohort; an empty cohort evaluates nothing)
        ids = range(len(self.clients)) if eval_ids is None else eval_ids
        losses, accs, ns = [], [], []
        for cid in ids:
            res = self.clients[cid].evaluate(
                EvaluateIns(parameters=global_params)
            )
            losses.append(res.loss)
            accs.append(res.metrics.get("acc", np.nan))
            ns.append(res.num_examples)
        if not losses:
            return None, None
        w = np.asarray(ns, np.float64)
        return float(np.average(losses, weights=w)), float(np.average(accs, weights=w))


def make_cost_model_for(params: PyTree, profiles: list, **kw) -> CostModel:
    return CostModel(profiles=profiles, update_bytes=tree_bytes(params), **kw)
