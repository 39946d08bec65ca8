"""MixedCodec — per-device mixed-codec batches inside the jitted engine.

ISSUE-4 tentpole acceptance, on the paper's heterogeneous fleet shape
(Pixel→TopK, Jetson→Int8, TPU→Null in ONE round):

- one jitted ``round_step`` aggregates all three groups, each on its own
  kernel path, with NO dense materialization of the TopK group's payload
  (``decode_batch`` is banned during the round);
- jitted MixedCodec round == sequential-scan round == python ``Server.run``
  aggregate within tolerance, round after round (error feedback included);
- per-client uplink bytes match each group codec's ``wire_bytes``;
- the per-group client state rides the uniform round_step signature;
- the mesh shard_map path rejects MixedCodec at build time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BandwidthCodecPolicy, CompressedParameters, FedAvg, Int8Codec, JaxClient,
    MixedCodec, NullCodec, RoundSpec, Server, TopKCodec, make_round_step,
    PROFILES,
)
from repro.core.cost_model import CostModel
from repro.core.server import make_cost_model_for
from repro.launch.mesh import make_local_mesh
from repro.data.federated import ClientDataset
from repro.models import build_model
from repro.optim import sgd
from repro.utils.pytree import tree_flatten_to_vector, tree_size

FLEET = ("pixel-4", "jetson-tx2-gpu", "tpu-v5e-chip")  # TopK / Int8 / Null


def _fleet_codec(profile_names=FLEET) -> MixedCodec:
    return MixedCodec.from_policy(
        BandwidthCodecPolicy(), [PROFILES[p] for p in profile_names]
    )


# ---------------- construction ----------------
def test_from_policy_assignment_and_bank():
    codec = _fleet_codec(("pixel-4", "jetson-tx2-gpu", "tpu-v5e-chip", "pixel-3"))
    kinds = [type(codec.codecs[g]) for g in codec.assignment]
    assert kinds == [TopKCodec, Int8Codec, NullCodec, TopKCodec]
    # equal-config codecs dedupe into one bank entry
    assert len(codec.codecs) == 3
    assert codec.n_clients == 4
    # groups are static index arrays in bank order
    groups = {type(c).__name__: list(idx) for _, c, idx in codec.groups()}
    assert groups == {"TopKCodec": [0, 3], "Int8Codec": [1], "NullCodec": [2]}


def test_assignment_out_of_range_rejected():
    with pytest.raises(AssertionError):
        MixedCodec(codecs=(NullCodec(),), assignment=(0, 1))


def test_init_client_state_per_group_rows():
    codec = _fleet_codec(("pixel-4", "pixel-3", "jetson-tx2-gpu", "tpu-v5e-chip"))
    state = codec.init_client_state(4, 100)
    assert isinstance(state, tuple) and len(state) == 3
    assert state[0].shape == (2, 100)   # TopK group: 2 residual rows
    assert state[1].shape == (1, 100)   # Int8 group: 1 residual row
    assert state[2] == ()               # Null group: stateless
    with pytest.raises(AssertionError):
        codec.init_client_state(3, 100)  # fleet size is part of the codec


def test_wire_bytes_is_per_client():
    codec = _fleet_codec()
    n = 4096
    wb = codec.wire_bytes(n)
    assert wb == [
        TopKCodec().wire_bytes(n), Int8Codec().wire_bytes(n),
        NullCodec().wire_bytes(n),
    ]
    # vector form: one size per client
    assert codec.wire_bytes([100, 200, 300]) == [
        TopKCodec().wire_bytes(100), Int8Codec().wire_bytes(200),
        NullCodec().wire_bytes(300),
    ]
    with pytest.raises(TypeError):
        codec._wire_bytes_scalar(n)


def test_per_client_surfaces_are_group_owned():
    codec = _fleet_codec()
    for call in (
        lambda: codec.encode(jnp.zeros(8)),
        lambda: codec.decode({}),
        lambda: codec.transmit_tree({"w": jnp.zeros(8)}, ()),
        lambda: codec.reduce({}, jnp.ones(3)),
    ):
        with pytest.raises(TypeError, match="group"):
            call()


# ---------------- flat-batch aggregation semantics ----------------
def test_aggregate_batch_matches_per_group_decode_reference():
    """Group partial sums under ONE denominator == flat weighted mean of the
    per-client decoded deltas (each client decoded by its own codec)."""
    rng = np.random.default_rng(3)
    codec = _fleet_codec(("pixel-4", "jetson-tx2-gpu", "tpu-v5e-chip", "pixel-3"))
    C, n = 4, 700
    deltas = jnp.asarray(rng.normal(size=(C, n)) * 0.01, jnp.float32)
    w = jnp.asarray(rng.random(C) + 0.1, jnp.float32)
    state = codec.init_client_state(C, n)

    avg, new_state = codec.aggregate_batch(deltas, w, state)

    dec_rows = []
    for c in range(C):
        cc = codec.codecs[codec.assignment[c]]
        dec_rows.append(cc.decode(cc.encode(deltas[c])))
    exp = jnp.einsum("c,cn->n", w, jnp.stack(dec_rows)) / jnp.sum(w)
    np.testing.assert_allclose(np.asarray(avg), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)
    # per-group error-feedback rows: what the wire dropped
    assert new_state[0].shape == (2, n) and new_state[1].shape == (1, n)
    np.testing.assert_allclose(   # Int8 row: delta - dequantized
        np.asarray(new_state[1][0]),
        np.asarray(deltas[1] - dec_rows[1]), atol=1e-6,
    )


def test_aggregate_batch_size_must_match_assignment():
    """A mismatched batch would silently clamp the static gather indices —
    the aggregation surfaces reject it up front like init_client_state."""
    codec = _fleet_codec()
    with pytest.raises(AssertionError, match="clients"):
        codec.aggregate_batch(
            jnp.ones((2, 64)), jnp.ones(2), codec.init_client_state(3, 64)
        )


def test_aggregate_batch_zero_weights_yield_zeros():
    codec = _fleet_codec()
    deltas = jnp.ones((3, 512), jnp.float32) * 0.01
    avg, _ = codec.aggregate_batch(
        deltas, jnp.zeros(3), codec.init_client_state(3, 512)
    )
    np.testing.assert_array_equal(np.asarray(avg), 0.0)


# ---------------- the jitted round engine ----------------
C, STEPS, B = 3, 2, 16


def _setup(seed=0):
    m = build_model("mobilenet-head-office31")
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
    return m, m.init(jax.random.key(seed)), train


def _run_engine(m, params, train, codec, mode, rounds=2, weights=None):
    strat = FedAvg()
    spec = RoundSpec(max_steps=STEPS, execution_mode=mode, codec=codec)
    rs = jax.jit(make_round_step(m.loss_fn, sgd(0.1), strat, spec))
    w = jnp.ones(C) if weights is None else weights
    bud = jnp.full((C,), STEPS, jnp.int32)
    p, state = params, strat.init_state(params)
    cstate = codec.init_client_state(C, tree_size(params))
    mets = []
    for rnd in range(rounds):
        p, state, cstate, met = rs(p, state, cstate, train, w, bud, rnd)
        mets.append(met)
    return p, cstate, mets


def test_mixed_round_uniform_signature_and_state():
    m, params, train = _setup()
    codec = _fleet_codec()
    p, cstate, mets = _run_engine(m, params, train, codec, "parallel")
    met = mets[-1]
    assert jax.tree.structure(p) == jax.tree.structure(params)
    assert isinstance(cstate, tuple) and len(cstate) == 3
    n = tree_size(params)
    assert cstate[0].shape == (1, n) and cstate[1].shape == (1, n)
    assert cstate[2] == ()
    assert {"client_loss_mean", "client_loss_max", "steps_total",
            "residual_norm_mean"} <= set(met)
    # the residual telemetry covers ALL stateful groups' rows
    assert float(met["residual_norm_mean"]) > 0.0


def test_mixed_round_no_dense_topk_materialization():
    """Acceptance: the TopK group's payload is never densified inside the
    jitted mixed round — decode_batch raises if anything calls it."""
    from repro.core.compression import ban_topk_densify

    m, params, train = _setup()
    codec = _fleet_codec()
    with ban_topk_densify():
        p, _, _ = _run_engine(m, params, train, codec, "parallel")
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(p))


def test_mixed_round_matches_manual_group_combination():
    """One mixed round == gathering each group and running its own codec,
    combining partial weighted sums under the fleet denominator."""
    m, params, train = _setup()
    codec = _fleet_codec()
    w = jnp.asarray([1.0, 2.0, 0.5])
    p_mixed, _, _ = _run_engine(m, params, train, codec, "parallel",
                                rounds=1, weights=w)

    # manual: train all clients, aggregate each group with its own codec
    from repro.core.rounds import make_client_update
    spec = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec)
    cu = make_client_update(m.loss_fn, sgd(0.1), spec)
    new_params, _, _ = jax.vmap(cu, in_axes=(None, 0, 0))(
        params, train, jnp.full((C,), STEPS, jnp.int32)
    )
    flat_global = tree_flatten_to_vector(params)
    deltas = jax.vmap(lambda p: tree_flatten_to_vector(p) - flat_global)(new_params)
    total = jnp.zeros_like(flat_global)
    for g, cc, idx in codec.groups():
        ia = jnp.asarray(idx)  # groups() yields static python index lists
        mean_g, _ = cc.aggregate_batch(
            deltas[ia], w[ia], cc.init_client_state(len(idx), flat_global.size)
        )
        total = total + mean_g * jnp.sum(w[ia])
    exp = flat_global + total / jnp.sum(w)
    np.testing.assert_allclose(   # atol: jit-vs-eager local-training noise
        np.asarray(tree_flatten_to_vector(p_mixed)), np.asarray(exp),
        atol=1e-4, rtol=1e-4,
    )


def test_mixed_sequential_matches_parallel():
    """The per-group scans land the same global and the same per-group
    state rows as the vmap path (bf16 sequential accumulator tolerance)."""
    m, params, train = _setup()
    codec = _fleet_codec()
    w = jnp.asarray([1.0, 2.0, 0.5])
    outs = {}
    for mode in ("parallel", "sequential"):
        outs[mode] = _run_engine(m, params, train, codec, mode,
                                 rounds=2, weights=w)
    p_p, cs_p, mets_p = outs["parallel"]
    p_s, cs_s, mets_s = outs["sequential"]
    for a, b in zip(jax.tree.leaves(p_p), jax.tree.leaves(p_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)
    for a, b in zip(jax.tree.leaves(cs_p), jax.tree.leaves(cs_s)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2, rtol=2e-2)
    # satellite: the SAME metric definition on every execution mode —
    # round 1 starts from identical globals, so the weighted means must
    # agree to fp noise (later rounds drift with the bf16 accumulator)
    assert float(mets_s[0]["client_loss_mean"]) == pytest.approx(
        float(mets_p[0]["client_loss_mean"]), rel=1e-4
    )


def test_mixed_mesh_path_rejected_at_build_time():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices (see conftest.py)")
    m, params, _ = _setup()
    mesh = make_local_mesh(pod=2, data=2)
    spec = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                     codec=_fleet_codec())
    with pytest.raises(NotImplementedError, match="MixedCodec"):
        make_round_step(m.loss_fn, sgd(0.1), FedAvg(), spec, mesh=mesh,
                        client_axes=("pod", "data"))


# ---------------- jitted engine == python Server parity ----------------
def test_mixed_fleet_jitted_matches_python_server():
    """Satellite acceptance: one heterogeneous fleet (Pixel→TopK,
    Jetson→Int8, TPU→Null), three assertions — jitted MixedCodec round ==
    sequential-scan round == python Server.run aggregate within tolerance,
    and per-client uplink bytes match each group codec's wire_bytes."""
    m, params, train = _setup()
    n = tree_size(params)
    policy = BandwidthCodecPolicy()
    codec = _fleet_codec()

    # python fleet: each client's shard is EXACTLY one full batch, so local
    # training (1 step of full-batch SGD) is permutation-invariant and
    # bitwise-comparable to the jitted engine fed the same rows
    clients = []
    for c, profile in enumerate(FLEET):
        x = np.asarray(train["x"][c]).reshape(STEPS * B, -1)
        y = np.asarray(train["y"][c]).reshape(STEPS * B)
        clients.append(JaxClient(
            client_id=c, loss_fn=m.loss_fn,
            dataset=ClientDataset(client_id=c, x=x, y=y),
            batch_size=STEPS * B, device_profile=profile,
        ))
    strat = FedAvg(local_epochs=1, local_lr=0.1, codec_policy=policy)
    cm = make_cost_model_for(params, [PROFILES[p] for p in FLEET])
    server = Server(strategy=strat, clients=clients, cost_model=cm)
    server.logger.quiet = True

    # jitted engine: same rows as ONE full-batch step per round
    flat_train = {
        "x": train["x"].reshape(C, 1, STEPS * B, -1),
        "y": train["y"].reshape(C, 1, STEPS * B),
    }
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), FedAvg(),
        RoundSpec(max_steps=1, execution_mode="parallel", codec=codec),
    ))
    rs_seq = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), FedAvg(),
        RoundSpec(max_steps=1, execution_mode="sequential", codec=codec),
    ))
    w = jnp.full((C,), float(STEPS * B))
    bud = jnp.ones((C,), jnp.int32)

    p_server, hist = server.run(params, num_rounds=2)
    p_jit, p_seq = params, params
    cs_jit = codec.init_client_state(C, n)
    cs_seq = codec.init_client_state(C, n)
    for rnd in range(2):
        p_jit, _, cs_jit, _ = rs(p_jit, (), cs_jit, flat_train, w, bud, rnd)
        p_seq, _, cs_seq, _ = rs_seq(p_seq, (), cs_seq, flat_train, w, bud, rnd)

    vec = {k: np.asarray(tree_flatten_to_vector(v))
           for k, v in (("server", p_server), ("jit", p_jit), ("seq", p_seq))}
    np.testing.assert_allclose(vec["jit"], vec["server"], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(vec["seq"], vec["jit"], atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(vec["seq"], vec["server"], atol=2e-3, rtol=2e-3)

    # per-client uplink: each client shipped its group codec's wire size,
    # and MixedCodec's per-client accounting agrees
    mixed_wb = codec.wire_bytes(n)
    props = {c.client_id: c.properties() for c in clients}
    for cid, ins in strat.configure_fit(1, params, [0, 1, 2],
                                        client_properties=props):
        res = clients[cid].fit(ins)
        assert isinstance(res.parameters, CompressedParameters)
        assert res.parameters.num_bytes == ins.config["codec"].wire_bytes(n)
        assert res.parameters.num_bytes == mixed_wb[cid]
    assert hist.rounds[0].comm_bytes == sum(mixed_wb) + C * cm.update_bytes


# ---------------- per-group cost accounting ----------------
def test_cost_model_fleet_uplink_bytes():
    cm = CostModel(profiles=[PROFILES[p] for p in FLEET], update_bytes=4_000_000)
    codec = _fleet_codec()
    n = 10_000
    ups = cm.fleet_uplink_bytes(codec, n, 3)
    assert ups == codec.wire_bytes(n)
    assert cm.fleet_uplink_bytes(Int8Codec(), n, 3) == [Int8Codec().wire_bytes(n)] * 3
    assert cm.fleet_uplink_bytes(None, n, 3) is None
    with pytest.raises(AssertionError):
        cm.fleet_uplink_bytes(codec, n, 5)  # fleet size mismatch
