"""Virtual-clock execution layer (core/scheduler.py + the participation
mask in core/rounds.py + the scheduler-driven Server).

ISSUE-5 acceptance criteria asserted here:
- ``Deadline(tau=inf)`` + full availability reproduces today's synchronous
  results BITWISE — on all three round_step execution modes (all-ones mask
  == no mask) and end-to-end through ``Server.run``;
- a masked (dropped) client provably leaves its error-feedback residual
  row and the aggregate untouched (its data is garbled and nothing moves);
- ``BufferedAsync`` ends rounds earlier than ``SyncAll`` on a straggler-
  heavy fleet while FedBuff keeps learning, with staleness recorded.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AvailabilityTrace, BufferedAsync, Client, Deadline, FedAvg,
    FedBuffStrategy, FedTau, FitRes, JaxClient, PROFILES, RoundSpec, Server,
    Strategy, SyncAll, VirtualClock, make_round_step,
)
from repro.core.compression import Int8Codec, MixedCodec, NullCodec, TopKCodec
from repro.core.scheduler import Arrival
from repro.core.server import make_cost_model_for
from repro.data.federated import dirichlet_partition
from repro.data.synthetic import make_features
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.optim import sgd
from repro.utils.pytree import tree_size


# ---------------- policies on a synthetic timeline ----------------
def _arr(cid, launch_rnd=1, launch_t=0.0, dur=1.0):
    return Arrival(client_id=cid, launch_rnd=launch_rnd, launch_t=launch_t,
                   finish_t=launch_t + dur, cost=None)


def test_syncall_waits_for_slowest():
    clock = VirtualClock()
    pending = [_arr(0, dur=1.0), _arr(1, dur=30.0), _arr(2, dur=5.0)]
    out = SyncAll().plan(clock, pending, 1)
    assert [a.client_id for a in out.reported] == [0, 2, 1]  # arrival order
    assert out.round_end == 30.0 and out.wall_time_s == 30.0
    assert not out.dropped and not out.carried and not out.expired


def test_deadline_drops_stragglers_and_waits_full_tau():
    clock = VirtualClock()
    pending = [_arr(0, dur=1.0), _arr(1, dur=30.0), _arr(2, dur=5.0)]
    out = Deadline(tau=10.0).plan(clock, pending, 1)
    assert [a.client_id for a in out.reported] == [0, 2]
    assert [a.client_id for a in out.dropped] == [1]
    assert out.round_end == 10.0  # a straggler exists: wait the full cutoff
    # no stragglers: the round ends with the last reporter, not the cutoff
    out2 = Deadline(tau=10.0).plan(clock, pending[:1] + pending[2:], 1)
    assert out2.round_end == 5.0 and not out2.dropped


def test_deadline_infinite_tau_matches_syncall():
    clock = VirtualClock()
    pending = [_arr(0, dur=1.0), _arr(1, dur=30.0), _arr(2, dur=5.0)]
    sync = SyncAll().plan(clock, pending, 1)
    inf = Deadline(tau=float("inf")).plan(clock, pending, 1)
    assert [a.client_id for a in inf.reported] == [a.client_id for a in sync.reported]
    assert inf.round_end == sync.round_end and not inf.dropped


def test_deadline_tau_none_reads_the_strategy_knob():
    """FedTau's tau and the scheduler's deadline are ONE knob."""
    assert Deadline().resolve_tau(FedTau(tau_s=5.0)) == 5.0
    assert Deadline().resolve_tau(FedTau(tau_s=0.0)) == float("inf")  # 0 = off
    assert Deadline().resolve_tau(FedAvg()) == float("inf")
    assert Deadline(tau=3.0).resolve_tau(FedTau(tau_s=5.0)) == 3.0  # explicit wins
    out = Deadline().plan(VirtualClock(), [_arr(0, dur=9.0)], 1, FedTau(tau_s=5.0))
    assert not out.reported and [a.client_id for a in out.dropped] == [0]


def test_buffered_async_takes_first_k_and_carries():
    clock = VirtualClock()
    pending = [_arr(0, dur=1.0), _arr(1, dur=30.0), _arr(2, dur=5.0)]
    out = BufferedAsync(buffer_size=2, max_staleness=4).plan(clock, pending, 1)
    assert [a.client_id for a in out.reported] == [0, 2]
    assert [a.client_id for a in out.carried] == [1]
    assert out.round_end == 5.0  # the K-th arrival ends the round
    # the carried straggler reports next round with staleness 1
    clock.advance_to(out.round_end)
    out2 = BufferedAsync(buffer_size=2, max_staleness=4).plan(
        clock, out.carried, 2
    )
    assert [a.client_id for a in out2.reported] == [1]
    assert out2.reported[0].staleness_at(2) == 1
    assert out2.round_end == 30.0


def test_buffered_async_expires_too_stale():
    clock = VirtualClock()
    old = _arr(0, launch_rnd=1, dur=2.0)
    out = BufferedAsync(buffer_size=2, max_staleness=2).plan(clock, [old], 9)
    assert not out.reported and [a.client_id for a in out.expired] == [0]


def test_buffered_async_expired_do_not_consume_buffer_slots():
    """Stale junk is flushed up front: the K buffer slots go to USABLE
    arrivals, so a burst of expiries cannot starve the aggregation."""
    clock = VirtualClock()
    stale = [_arr(i, launch_rnd=1, dur=0.5 + 0.1 * i) for i in range(3)]
    fresh = [_arr(10, launch_rnd=9, dur=5.0), _arr(11, launch_rnd=9, dur=6.0)]
    out = BufferedAsync(buffer_size=3, max_staleness=2).plan(
        clock, stale + fresh, 9
    )
    assert [a.client_id for a in out.expired] == [0, 1, 2]
    assert [a.client_id for a in out.reported] == [10, 11]
    assert not out.carried
    assert out.round_end == 6.0  # the last usable reporter gates the round


def test_buffered_async_inflight_expiry_never_gates_the_round():
    """An expired straggler still in flight is cancelled, not waited for —
    waiting for a discarded update is the straggler wall async avoids."""
    clock = VirtualClock()
    clock.advance_to(10.0)
    slow_stale = _arr(0, launch_rnd=1, launch_t=0.0, dur=60.0)  # flies on
    fresh = _arr(1, launch_rnd=9, launch_t=10.0, dur=2.0)
    out = BufferedAsync(buffer_size=1, max_staleness=2).plan(
        clock, [slow_stale, fresh], 9
    )
    assert [a.client_id for a in out.reported] == [1]
    assert [a.client_id for a in out.expired] == [0]
    assert out.round_end == 12.0  # NOT 60: the cancelled straggler is ignored


def test_virtual_clock_is_monotone():
    clock = VirtualClock()
    clock.advance_to(5.0)
    clock.advance_to(5.0)  # no-op, not an error
    assert clock.now == 5.0
    with pytest.raises(AssertionError):
        clock.advance_to(1.0)


# ---------------- availability traces ----------------
def test_availability_trace_deterministic_and_seed_sensitive():
    profiles = [PROFILES["pixel-4"]] * 6 + [PROFILES["jetson-tx2-gpu"]] * 2
    t1 = AvailabilityTrace.from_profiles(profiles, seed=0, mobile_dropout=0.5)
    t2 = AvailabilityTrace.from_profiles(profiles, seed=0, mobile_dropout=0.5)
    t3 = AvailabilityTrace.from_profiles(profiles, seed=1, mobile_dropout=0.5)
    for rnd in range(1, 6):
        np.testing.assert_array_equal(t1.available(rnd), t2.available(rnd))
        np.testing.assert_allclose(t1.step_jitter(rnd), t2.step_jitter(rnd))
    assert any(
        not np.array_equal(t1.available(r), t3.available(r)) for r in range(1, 20)
    )


def test_availability_full_trace_is_always_up():
    t = AvailabilityTrace.full(5)
    for rnd in (1, 7, 100):
        assert t.available(rnd).all()
        np.testing.assert_array_equal(t.step_jitter(rnd), np.ones(5))


def test_from_profiles_battery_churns_more_than_plugged():
    profiles = [PROFILES["pixel-2"], PROFILES["jetson-tx2-gpu"]]
    t = AvailabilityTrace.from_profiles(
        profiles, mobile_dropout=0.4, plugged_dropout=0.01
    )
    assert t.dropout == (0.4, 0.01)  # pixel idles at 0.7 W (battery class)
    ups = np.stack([t.available(r) for r in range(1, 200)])
    assert ups[:, 0].mean() < ups[:, 1].mean()  # phone sits out more rounds


def test_from_profiles_late_join_benches_slowest():
    profiles = [PROFILES["tpu-v5e-chip"], PROFILES["pixel-2"], PROFILES["pixel-3"]]
    t = AvailabilityTrace.from_profiles(
        profiles, late_join=1, mobile_dropout=0.0, plugged_dropout=0.0
    )
    assert t.join_round == (1, 2, 1)  # pixel-2 is the slowest: joins late
    assert not t.available(1, 1) and t.available(2, 1)


def test_step_jitter_positive_and_spread():
    t = AvailabilityTrace(n_clients=64, seed=3, jitter_std=0.2)
    j = t.step_jitter(1)
    assert (j > 0).all() and j.std() > 0.01


# ---------------- round_step participation mask ----------------
CODECS = {
    "null": NullCodec(),
    "int8": Int8Codec(),
    "topk": TopKCodec(frac=0.05),
    "mixed": MixedCodec(
        codecs=(TopKCodec(frac=0.05), Int8Codec(), NullCodec()),
        assignment=(0, 1, 2, 0),
    ),
}


def _round_fixture(seed=0, C=4, steps=2, B=8):
    m = build_model("mobilenet-head-office31")
    rng = np.random.default_rng(seed)
    batch = {
        "x": rng.normal(size=(C, steps, B, m.cfg.feature_dim)).astype(np.float32),
        "y": rng.integers(0, m.cfg.num_classes, (C, steps, B)).astype(np.int32),
    }
    params = m.init(jax.random.key(seed))
    w = jnp.asarray([1.0, 2.0, 0.5, 1.5])
    bud = jnp.full((C,), steps, jnp.int32)
    return m, params, batch, w, bud


def _bitwise_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not (np.asarray(x) == np.asarray(y)).all():
            return False
    return True


@pytest.mark.parametrize("mode", ["parallel", "sequential"])
@pytest.mark.parametrize("codec_name", list(CODECS))
def test_all_ones_mask_is_bitwise_identity(mode, codec_name):
    """Full participation == today's synchronous round, bit for bit."""
    codec = CODECS[codec_name]
    m, params, batch, w, bud = _round_fixture()
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat,
        RoundSpec(max_steps=2, execution_mode=mode, codec=codec),
    ))
    cs = codec.init_client_state(4, tree_size(params))
    g0, _, cs0, met0 = rs(params, strat.init_state(params), cs, batch, w, bud, 0)
    g1, _, cs1, met1 = rs(params, strat.init_state(params), cs, batch, w, bud, 0,
                          jnp.ones((4,), jnp.float32))
    assert _bitwise_equal(g0, g1) and _bitwise_equal(cs0, cs1)
    for k in met0:
        assert float(met0[k]) == pytest.approx(float(met1[k]), rel=1e-6), k


def test_all_ones_mask_is_bitwise_identity_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices (see conftest.py)")
    mesh, axes = make_local_mesh(pod=2, data=2), ("pod", "data")
    codec = Int8Codec()
    m, params, batch, w, bud = _round_fixture()
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat,
        RoundSpec(max_steps=2, execution_mode="parallel", codec=codec),
        mesh=mesh, client_axes=axes,
    ))
    cs = codec.init_client_state(4, tree_size(params))
    g0, _, cs0, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 0)
    g1, _, cs1, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 0,
                       jnp.ones((4,), jnp.float32))
    assert _bitwise_equal(g0, g1) and _bitwise_equal(cs0, cs1)
    # masked diverged client on the mesh: NaN data, bit-identical aggregate
    garbled = {"x": np.array(batch["x"]), "y": batch["y"]}
    garbled["x"][1] = np.nan
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    g2, _, _, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 0, mask)
    g3, _, _, _ = rs(params, strat.init_state(params), cs, garbled, w, bud, 0, mask)
    assert _bitwise_equal(g2, g3)


@pytest.mark.parametrize("mode", ["parallel", "sequential"])
@pytest.mark.parametrize("codec_name", ["topk", "mixed"])
def test_masked_client_leaves_residual_and_aggregate_untouched(mode, codec_name):
    """ISSUE-5 acceptance: garble a dropped client's data — with NaNs, the
    worst case: a diverged client is exactly who gets dropped, and 0-weight
    alone would poison the reduce through 0 * NaN — the new global and
    every OTHER client's residual row must be bit-identical, and the
    dropped client's own residual row carries through unchanged."""
    codec = CODECS[codec_name]
    m, params, batch, w, bud = _round_fixture()
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat,
        RoundSpec(max_steps=2, execution_mode=mode, codec=codec),
    ))
    n = tree_size(params)
    # non-trivial carried state: run one full round first
    cs = codec.init_client_state(4, n)
    _, _, cs, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 0)
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])  # drop client 2

    g_a, _, cs_a, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 1, mask)
    garbled = dict(batch)
    garbled["x"] = np.array(batch["x"])
    garbled["x"][2] = np.nan  # the dropped client diverged
    g_b, _, cs_b, _ = rs(params, strat.init_state(params), cs, garbled, w, bud, 1, mask)

    assert _bitwise_equal(g_a, g_b)          # the aggregate never saw client 2
    assert _bitwise_equal(cs_a, cs_b)        # nor did anyone's residual state
    # and client 2's own residual row is exactly the row it entered with
    if codec_name == "topk":
        np.testing.assert_array_equal(np.asarray(cs_a)[2], np.asarray(cs)[2])
        assert not np.array_equal(np.asarray(cs_a)[0], np.asarray(cs)[0])
    else:  # mixed: client 2 is group 2 (Null, stateless); check a TopK drop
        mask2 = jnp.asarray([0.0, 1.0, 1.0, 1.0])  # client 0 -> TopK group row 0
        _, _, cs_c, _ = rs(params, strat.init_state(params), cs, batch, w, bud, 1,
                           mask2)
        np.testing.assert_array_equal(
            np.asarray(cs_c[0])[0], np.asarray(cs[0])[0]
        )
        assert not np.array_equal(np.asarray(cs_c[0])[1], np.asarray(cs[0])[1])


@pytest.mark.parametrize("mode", ["parallel", "sequential"])
def test_fully_masked_round_is_noop_with_nan_metrics(mode):
    """Everyone dropped: the global is untouched and the loss metrics are
    NaN (undefined), not a 0.0 that reads like convergence or a -inf max."""
    m, params, batch, w, bud = _round_fixture()
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat,
        RoundSpec(max_steps=2, execution_mode=mode),
    ))
    g, _, _, met = rs(params, strat.init_state(params), (), batch, w, bud, 0,
                      jnp.zeros((4,), jnp.float32))
    assert _bitwise_equal(g, params)
    assert np.isnan(float(met["client_loss_mean"]))
    assert np.isnan(float(met["client_loss_max"]))
    assert int(met["steps_total"]) == 0


def test_mask_equals_smaller_fleet():
    """Masking client j matches an aggregation in which only the other
    clients' weights carry mass (zero-weight equivalence on the wire)."""
    m, params, batch, w, bud = _round_fixture()
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat,
        RoundSpec(max_steps=2, execution_mode="parallel"),
    ))
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    g_mask, _, _, _ = rs(params, strat.init_state(params), (), batch, w, bud, 0, mask)
    g_zero, _, _, _ = rs(params, strat.init_state(params), (), batch, w * mask, bud, 0)
    assert _bitwise_equal(g_mask, g_zero)


# ---------------- the scheduler-driven Server ----------------
def _fl_setup(n_clients=4, seed=0, profile_names=None):
    m = build_model("mobilenet-head-office31")
    data = make_features(n=1200, num_classes=31, feature_dim=m.cfg.feature_dim,
                         seed=seed)
    shards = dirichlet_partition(data, n_clients=n_clients, alpha=100.0, seed=seed)
    params = m.init(jax.random.key(seed))
    mask = m.trainable_mask(params)
    clients = [
        JaxClient(client_id=c.client_id, loss_fn=m.loss_fn, dataset=c,
                  batch_size=32, trainable_mask=mask)
        for c in shards
    ]
    if profile_names:
        for c, name in zip(clients, profile_names):
            c.device_profile = name
        cm = make_cost_model_for(params, [PROFILES[p] for p in profile_names])
    else:
        cm = make_cost_model_for(params, [PROFILES["pixel-4"]] * n_clients)
    return m, params, clients, cm


def test_deadline_inf_full_availability_reproduces_sync_bitwise():
    """ISSUE-5 acceptance: the scheduler is a no-op until a policy bites."""
    m, params, clients, cm = _fl_setup()
    base = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                  clients=clients, cost_model=cm)
    base.logger.quiet = True
    p_base, h_base = base.run(params, num_rounds=3)

    # fresh clients: the dataset batch cursor is stateful across runs
    m, params2, clients2, cm2 = _fl_setup()
    sched = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                   clients=clients2, cost_model=cm2,
                   policy=Deadline(tau=float("inf")),
                   availability=AvailabilityTrace.full(len(clients2)))
    sched.logger.quiet = True
    p_sched, h_sched = sched.run(params, num_rounds=3)

    assert _bitwise_equal(p_base, p_sched)
    for rb, rs_ in zip(h_base.rounds, h_sched.rounds):
        assert rb.eval_acc == rs_.eval_acc and rb.train_loss == rs_.train_loss
        assert rb.wall_time_s == pytest.approx(rs_.wall_time_s)
        assert rb.energy_j == pytest.approx(rs_.energy_j)
        assert rb.comm_bytes == rs_.comm_bytes
        assert rs_.participants == len(clients) and rs_.dropped == 0
        assert rs_.staleness_mean == 0.0


def test_deadline_drops_stragglers_end_to_end():
    names = ["tpu-v5e-chip", "tpu-v5e-chip", "pixel-2", "pixel-2"]
    m, params, clients, cm = _fl_setup(profile_names=names)
    spe = clients[0].steps_per_epoch()
    # a cutoff the TPUs easily make and the pixels (0.37 s/step) cannot
    tau = 2.0 * spe * PROFILES["tpu-v5e-chip"].step_time_s + 1.0
    srv = Server(strategy=FedAvg(local_epochs=2, local_lr=0.1),
                 clients=clients, cost_model=cm, policy=Deadline(tau=tau))
    srv.logger.quiet = True
    _, hist = srv.run(params, num_rounds=2)
    for rec in hist.rounds:
        assert rec.participants == 2 and rec.dropped == 2
        assert rec.wall_time_s == pytest.approx(tau)  # waited the full cutoff
        assert rec.energy_j > 0
    # dropped stragglers never uplinked: comm < full fleet both ways
    assert hist.rounds[0].comm_bytes == 4 * cm.update_bytes + 2 * cm.update_bytes


def test_buffered_async_beats_syncall_on_straggler_fleet():
    """ISSUE-5 acceptance: FedBuff's clock runs ahead of lockstep."""
    names = ["tpu-v5e-chip", "jetson-tx2-gpu", "pixel-2", "pixel-2"]
    m, params, clients, cm = _fl_setup(profile_names=names)

    sync = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                  clients=clients, cost_model=cm)
    sync.logger.quiet = True
    _, h_sync = sync.run(params, num_rounds=4)

    strat = FedBuffStrategy(local_epochs=1, local_lr=0.1, buffer_size=2,
                            max_staleness=4)
    buf = Server(strategy=strat, clients=clients, cost_model=cm,
                 policy=strat.make_policy())
    buf.logger.quiet = True
    _, h_buf = buf.run(params, num_rounds=4)

    assert h_buf.total_time_s < h_sync.total_time_s
    # stragglers reported late at least once, and their staleness was logged
    assert any(r.staleness_mean > 0 for r in h_buf.rounds)
    accs = [a for _, a in h_buf.accuracy_series()]
    assert accs[-1] > accs[0]  # async aggregation still learns


def test_empty_rounds_are_graceful():
    """Total dropout: the clock advances, rounds record, nothing crashes."""
    m, params, clients, cm = _fl_setup()
    trace = AvailabilityTrace(n_clients=len(clients),
                              dropout=(1.0,) * len(clients))
    srv = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                 clients=clients, cost_model=cm, availability=trace)
    srv.logger.quiet = True
    final, hist = srv.run(params, num_rounds=2)
    assert len(hist.rounds) == 2
    for rec in hist.rounds:
        assert rec.participants == 0 and np.isnan(rec.train_loss)
        assert rec.wall_time_s == 0.0 and rec.energy_j == 0.0
    assert _bitwise_equal(final, params)  # nothing ever aggregated


def test_cost_model_empty_round_is_zero():
    from repro.core import CostModel
    cm = CostModel(profiles=[PROFILES["pixel-4"]], update_bytes=1000)
    assert cm.round_wall_time([]) == 0.0
    assert cm.round_energy([]) == 0.0


def test_partial_dropout_still_learns():
    m, params, clients, cm = _fl_setup()
    trace = AvailabilityTrace(n_clients=len(clients), seed=5,
                              dropout=(0.5, 0.0, 0.5, 0.0))
    srv = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                 clients=clients, cost_model=cm, availability=trace)
    srv.logger.quiet = True
    _, hist = srv.run(params, num_rounds=4)
    parts = [r.participants for r in hist.rounds]
    assert min(parts) < len(clients)  # somebody actually sat out
    accs = [a for _, a in hist.accuracy_series()]
    assert accs[-1] > accs[0]


def test_step_jitter_perturbs_cost_not_result():
    def one_run(trace):
        # fresh clients per run: the dataset batch cursor is stateful
        m, params, clients, cm = _fl_setup()
        s = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                   clients=clients, cost_model=cm, availability=trace)
        s.logger.quiet = True
        return s.run(params, num_rounds=2)

    p1, h1 = one_run(AvailabilityTrace.full(4))
    p2, h2 = one_run(AvailabilityTrace(n_clients=4, seed=2, jitter_std=0.3))
    assert _bitwise_equal(p1, p2)  # jitter is a cost phenomenon only
    assert h1.total_time_s != h2.total_time_s


# ---------------- strategy-side plumbing ----------------
def test_run_end_abandons_in_flight_arrivals():
    """Arrivals still flying when the run ends roll their clients back and
    charge their wasted burn to the final round — async totals must not
    silently omit exactly the stragglers they created."""
    names = ["tpu-v5e-chip", "pixel-2"]
    m, params, clients, cm = _fl_setup(n_clients=2, profile_names=names)
    discards = []
    clients[1].discard_update = lambda: discards.append(1)
    strat = FedBuffStrategy(local_epochs=1, local_lr=0.1, buffer_size=1)
    srv = Server(strategy=strat, clients=clients, cost_model=cm,
                 policy=strat.make_policy())
    srv.logger.quiet = True
    _, hist = srv.run(params, num_rounds=1)
    # K=1: the TPU reports, the pixel is still in flight at run end
    assert hist.rounds[0].participants == 1
    assert discards == [1]
    # the pixel's partial compute burn landed in the final record: more
    # than the TPU-only accounting could explain
    tpu_only = cm.client_round_cost(0, hist.rounds[0].steps // 2).e_total_j
    assert hist.rounds[0].energy_j > tpu_only


def test_sampling_is_seedable_and_streams_are_independent():
    ids = list(range(16))
    a, b = Strategy(fraction_fit=0.5, seed=1), Strategy(fraction_fit=0.5, seed=1)
    assert a.sample_clients(2, ids) == b.sample_clients(2, ids)
    c = Strategy(fraction_fit=0.5, seed=2)
    assert any(a.sample_clients(r, ids) != c.sample_clients(r, ids)
               for r in range(1, 10))
    # tuple seeding, not seed+rnd: seed k+1's rounds must NOT replay seed
    # k's rounds shifted by one (that correlation defeats an "independent"
    # control experiment)
    shifted = [
        Strategy(fraction_fit=0.5, seed=2).sample_clients(r, ids)
        == Strategy(fraction_fit=0.5, seed=1).sample_clients(r + 1, ids)
        for r in range(1, 12)
    ]
    assert not all(shifted)
    # dropout hardening: tiny eligible pools never crash the sampler
    assert Strategy(min_fit_clients=4).sample_clients(1, [7]) == [7]
    assert Strategy().sample_clients(1, []) == []


def test_fedbuff_staleness_discounts_weights():
    strat = FedBuffStrategy(alpha=0.5)
    results = [
        (0, FitRes(parameters=None, num_examples=100, staleness=0)),
        (1, FitRes(parameters=None, num_examples=100, staleness=3)),
    ]
    w = np.asarray(strat._fit_weights(results))
    assert w[0] == pytest.approx(100.0)
    assert w[1] == pytest.approx(100.0 / 2.0)  # (1+3)^0.5 = 2
    assert np.allclose(
        np.asarray(FedBuffStrategy(alpha=0.0)._fit_weights(results)), 100.0
    )


def test_fedbuff_takes_grouped_wire_path():
    assert FedBuffStrategy()._grouped_fit_compatible()


def test_client_honors_deadline_config():
    from repro.core import FitIns
    from repro.utils.pytree import tree_bytes

    m, params, clients, cm = _fl_setup()
    c = clients[0]
    c.device_profile = "pixel-2"  # 0.37 s/step
    prof = PROFILES["pixel-2"]
    # the client budgets compute + ITS OWN transfer time into the deadline
    t_comm = tree_bytes(params) * 8 * (
        1 / (prof.uplink_mbps * 1e6) + 1 / (prof.downlink_mbps * 1e6)
    )
    deadline = t_comm + 5 * prof.step_time_s + 1e-6
    res = c.fit(FitIns(parameters=params,
                       config={"epochs": 2, "deadline_s": deadline}))
    assert res.metrics["steps_done"] == 5
    # the truncated client actually makes the scheduler's cutoff
    assert t_comm + 5 * prof.step_time_s <= deadline
    res_full = c.fit(FitIns(parameters=params, config={"epochs": 2}))
    assert res_full.metrics["steps_done"] == 2 * c.steps_per_epoch()
    # an impossible deadline still tries one step (the scheduler judges it)
    res_min = c.fit(FitIns(parameters=params,
                           config={"epochs": 2, "deadline_s": 1e-6}))
    assert res_min.metrics["steps_done"] == 1


def test_discarded_update_rolls_back_residual():
    """A deadline-dropped compressed update must leave the client's error-
    feedback residual as it entered the round (python twin of the jitted
    mask contract) — fit() commits it optimistically, discard reverts."""
    from repro.core import FitIns
    from repro.core.compression import TopKCodec

    m, params, clients, cm = _fl_setup()
    c = clients[0]
    codec = TopKCodec(frac=0.05)
    c.fit(FitIns(parameters=params, config={"epochs": 1, "codec": codec}))
    r1 = np.asarray(c._residual).copy()
    c.fit(FitIns(parameters=params, config={"epochs": 1, "codec": codec}))
    assert not np.array_equal(np.asarray(c._residual), r1)
    c.discard_update()  # the scheduler threw the second update away
    np.testing.assert_array_equal(np.asarray(c._residual), r1)


def test_server_discards_dropped_clients_state():
    """Server.run notifies every dropped/expired arrival's client."""
    names = ["tpu-v5e-chip", "tpu-v5e-chip", "pixel-2", "pixel-2"]
    m, params, clients, cm = _fl_setup(profile_names=names)
    discards = []
    for c in clients:
        c.discard_update = (lambda cid=c.client_id: discards.append(cid))
    spe = clients[0].steps_per_epoch()
    tau = 1.25 * cm.client_round_cost(0, spe).t_total_s  # only TPUs make it
    srv = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1),
                 clients=clients, cost_model=cm, policy=Deadline(tau=tau))
    srv.logger.quiet = True
    _, hist = srv.run(params, num_rounds=2)
    assert sum(r.dropped for r in hist.rounds) == len(discards)
    assert set(discards) == {2, 3}  # exactly the pixel stragglers


def test_deadline_policy_ships_deadline_in_fit_config():
    """The cutoff rides to clients ONLY when a Deadline policy enforces it:
    under SyncAll nothing is dropped, so shipping one would silently shrink
    step budgets (breaking the paper's compute-only tau baselines)."""
    class _ConfigSpy(Client):
        def __init__(self):
            self.configs = []

        def fit(self, ins):
            self.configs.append(ins.config)
            return FitRes(parameters=ins.parameters, num_examples=1,
                          metrics={"loss": 1.0, "steps_done": 1})

        def evaluate(self, ins):
            from repro.core import EvaluateRes

            return EvaluateRes(loss=1.0, num_examples=1, metrics={"acc": 0.0})

    gp = {"w": jnp.zeros(2)}
    for policy, expect in (
        (Deadline(), 7.0),              # tau=None -> FedTau's knob
        (Deadline(tau=3.0), 3.0),       # explicit tau wins
        (None, None),                   # SyncAll: no deadline shipped
        (SyncAll(), None),
    ):
        spy = _ConfigSpy()
        srv = Server(strategy=FedTau(tau_s=7.0), clients=[spy], policy=policy)
        srv.logger.quiet = True
        srv.run(gp, num_rounds=1)
        assert spy.configs[0].get("deadline_s") == expect, (policy, expect)
