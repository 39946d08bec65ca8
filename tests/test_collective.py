"""Compressed collectives + mesh-sharded client state (ISSUE-10).

- ``CompressedPsum`` wire kernels: ref vs Pallas-interpret parity, and the
  exact-summability identity the shared pre-pmax'd scale buys
  (``unpack(sum_d pack(x_d)) == sum_d unpack(pack(x_d))``);
- mesh round engine: ``collective="fp32"`` (the default) takes the exact
  pre-PR psum path; ``collective="int8"`` tracks it within tolerance with
  a bounded (telescoping) per-device error-feedback residual, and a masked
  device's residual row carries bitwise unchanged;
- sharded client state: ``shard_client_state`` / ``CohortState(shardings=)``
  move placement only — gathered values stay bitwise identical to the
  unsharded layout for flat and segmented (Int8/TopK/LoRA) codecs, through
  an eviction round, with per-device addressable bytes ~1/n_devices;
- ``CostModel`` collective accounting: >=3.9x int8-vs-fp32 per-hop byte
  reduction, per-tier sums, and the ``round_comm_bytes`` regression (mesh
  rounds now bill the psum traffic the old accounting silently omitted).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CohortState, CompressedPsum, FedAvg, Int8Codec, LoRACodec, NullCodec,
    RoundSpec, SegmentMap, TopKCodec, init_collective_residual,
    make_round_step,
)
from repro.core.compression import fp32_collective_bytes
from repro.core.cost_model import CostModel, DeviceProfile
from repro.kernels import ops, ref
from repro.launch.mesh import collective_tiers, make_local_mesh
from repro.models import build_model
from repro.models.sharding import (
    ShardRules, client_state_shardings, shard_client_state,
)
from repro.optim import sgd
from repro.utils.pytree import tree_size

C, STEPS, B = 4, 2, 16


# ---------------- wire kernels ----------------
def _scales(x, block=256):
    am = jnp.max(jnp.abs(x).reshape(-1, block), axis=1)
    return jnp.where(am == 0.0, 1.0, am / 127.0)


def test_collective_pack_unpack_ref_vs_interpret():
    x = jax.random.normal(jax.random.key(0), (8192,), jnp.float32)
    s = _scales(x)
    q_ref = ref.collective_pack(x, s)
    q_pal = ops.collective_pack(x, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_pal))
    assert q_ref.dtype == jnp.int32
    assert int(jnp.max(jnp.abs(q_ref))) <= 127
    d_ref = ref.collective_unpack(q_ref, s)
    d_pal = ops.collective_unpack(q_ref, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_pal))


def test_collective_quant_exactly_summable():
    """Shared scale grid => the accumulation is EXACT in the int domain
    (the int32 psum loses nothing; sum-then-dequant == dequant-then-sum up
    to ONE final fp32 rounding per element, instead of one per hop)."""
    key = jax.random.key(1)
    xs = jax.random.normal(key, (8, 4096), jnp.float32)
    s = _scales(jnp.max(jnp.abs(xs), axis=0).reshape(-1))  # pmax stand-in
    qs = [np.asarray(ref.collective_pack(x, s)) for x in xs]
    q_sum = sum(q.astype(np.int64) for q in qs)
    assert np.abs(q_sum).max() <= 8 * 127  # overflow bound: fan-in * 127
    np.testing.assert_array_equal(  # int32 accumulator == exact int sum
        np.asarray(sum(jnp.asarray(q) for q in qs)), q_sum.astype(np.int32)
    )
    summed_then_unpacked = ref.collective_unpack(jnp.asarray(q_sum), s)
    unpacked_then_summed = sum(ref.collective_unpack(jnp.asarray(q), s)
                               for q in qs)
    np.testing.assert_allclose(  # same value, one fp32 rounding apart
        np.asarray(summed_then_unpacked), np.asarray(unpacked_then_summed),
        rtol=0, atol=float(jnp.max(s)) * 1e-4,
    )


def test_collective_roundtrip_error_bounded_by_scale():
    x = jax.random.normal(jax.random.key(2), (4096,), jnp.float32)
    s = _scales(x)
    back = ref.collective_unpack(ref.collective_pack(x, s), s)
    err = jnp.abs(back - x).reshape(-1, 256)
    assert bool(jnp.all(err <= 0.5 * s[:, None] + 1e-7))


# ---------------- mesh round engine ----------------
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
    ex, ey = batch_of(512, 999)
    eval_batch = {"x": jnp.asarray(ex), "y": jnp.asarray(ey)}
    params = m.init(jax.random.key(seed))
    return m, params, train, eval_batch


def _client_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices (see conftest.py)")
    return make_local_mesh(pod=2, data=2), ("pod", "data")


def _mesh_run(m, params, train, eval_batch, spec, mesh, axes, rounds=12,
              masks=None):
    strat = FedAvg()
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat, spec, mesh=mesh, client_axes=axes,
    ))
    w = jnp.ones(C)
    bud = jnp.full((C,), STEPS, jnp.int32)
    state = strat.init_state(params)
    cstate = spec.codec.init_client_state(C, tree_size(params))
    if spec.collective == "int8":
        cstate = (cstate, init_collective_residual(params, C))
    p = params
    coll_norms = []
    for rnd in range(rounds):
        args = (p, state, cstate, train, w, bud, rnd)
        if masks is not None:
            args = args + (masks[rnd],)
        p, state, cstate, met = rs(*args)
        if "collective_residual_norm_mean" in met:
            coll_norms.append(float(met["collective_residual_norm_mean"]))
    loss, _ = m.loss_fn(p, eval_batch)
    return float(loss), p, cstate, coll_norms


def test_fp32_collective_is_the_default_and_unchanged_contract():
    """Default spec takes the pre-PR path: plain codec state (no residual
    tuple), no collective metrics, bitwise equal to an explicit "fp32"."""
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    codec = Int8Codec()
    assert RoundSpec(max_steps=1, execution_mode="parallel").collective == "fp32"
    sp_def = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec)
    sp_exp = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec,
                       collective="fp32")
    l1, p1, cs1, n1 = _mesh_run(m, params, train, eval_batch, sp_def, mesh,
                                axes, rounds=3)
    l2, p2, cs2, n2 = _mesh_run(m, params, train, eval_batch, sp_exp, mesh,
                                axes, rounds=3)
    assert l1 == l2 and n1 == [] and n2 == []
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert cs1.shape == (C, tree_size(params))  # plain block, not a tuple


def test_int8_collective_tracks_fp32_with_bounded_residual():
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    codec = Int8Codec()
    sp_fp = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec)
    sp_i8 = RoundSpec(max_steps=STEPS, execution_mode="parallel", codec=codec,
                      collective="int8")
    l_fp, _, _, _ = _mesh_run(m, params, train, eval_batch, sp_fp, mesh, axes)
    l_i8, _, cstate, norms = _mesh_run(
        m, params, train, eval_batch, sp_i8, mesh, axes
    )
    assert l_i8 == pytest.approx(l_fp, rel=5e-2)
    # collective error feedback telescopes: the residual stays bounded (on
    # the order of one block-scale quantum), never grows with rounds
    assert len(norms) == 12 and norms[-1] <= 3.0 * max(norms[0], 1e-6)
    codec_state, resid = cstate
    assert codec_state.shape == (C, tree_size(params))
    assert {l.shape[0] for l in jax.tree.leaves(resid)} == {C}


def test_int8_collective_null_codec_also_works():
    """The collective composes with an uncompressed uplink (NullCodec)."""
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    sp_fp = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                      codec=NullCodec())
    sp_i8 = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                      codec=NullCodec(), collective="int8")
    l_fp, _, _, _ = _mesh_run(m, params, train, eval_batch, sp_fp, mesh, axes,
                              rounds=6)
    l_i8, _, _, norms = _mesh_run(m, params, train, eval_batch, sp_i8, mesh,
                                  axes, rounds=6)
    assert l_i8 == pytest.approx(l_fp, rel=5e-2)
    assert norms and all(n >= 0.0 for n in norms)


def test_int8_collective_masked_residual_carries_unchanged():
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    spec = RoundSpec(max_steps=STEPS, execution_mode="parallel",
                     codec=Int8Codec(), collective="int8")
    masks = [jnp.ones((C,)), jnp.asarray([0.0, 1.0, 1.0, 1.0])]
    # round 1 (all live) seeds every residual row; round 2 masks client 0
    _, _, cs1, _ = _mesh_run(m, params, train, eval_batch, spec, mesh, axes,
                             rounds=1, masks=masks[:1])
    _, _, cs2, _ = _mesh_run(m, params, train, eval_batch, spec, mesh, axes,
                             rounds=2, masks=masks)
    r1, r2 = jax.tree.leaves(cs1[1]), jax.tree.leaves(cs2[1])
    changed = False
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(  # masked: carried bitwise
            np.asarray(a[0]), np.asarray(b[0])
        )
        changed = changed or not np.array_equal(np.asarray(a[1]),
                                                np.asarray(b[1]))
    assert changed  # live rows DID update


def test_collective_validation_errors():
    m, params, _, _ = _setup()
    with pytest.raises(ValueError, match="fp32 | int8"):
        make_round_step(
            m.loss_fn, sgd(0.1), FedAvg(),
            RoundSpec(max_steps=1, execution_mode="parallel", collective="int4"),
        )
    with pytest.raises(NotImplementedError, match="mesh"):
        make_round_step(  # int8 without a mesh: nothing to compress
            m.loss_fn, sgd(0.1), FedAvg(),
            RoundSpec(max_steps=1, execution_mode="parallel", collective="int8"),
        )


# ---------------- sharded client state ----------------
def _fsdp_mesh_rules():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices (see conftest.py)")
    mesh = make_local_mesh(data=4, model=2)
    rules = ShardRules(mode="fsdp", axis_sizes=(("data", 4), ("model", 2)))
    return mesh, rules


def _seg_tree():
    # sizes divisible by 8 shard; the odd bias replicates (spec drops axes)
    return {
        "w1": jnp.zeros((64, 16)),
        "b1": jnp.zeros((9,)),
        "w2": jnp.zeros((16, 8)),
    }


@pytest.mark.parametrize("codec_fn", [
    lambda segs: Int8Codec().with_segments(segs),
    lambda segs: TopKCodec(frac=0.25).with_segments(segs),
    lambda segs: LoRACodec(rank=2).with_segments(segs),
], ids=["int8", "topk", "lora"])
def test_shard_client_state_bitwise_segmented(codec_fn):
    mesh, rules = _fsdp_mesh_rules()
    segs = SegmentMap.from_tree(_seg_tree())
    codec = codec_fn(segs)
    state = codec.init_client_state(C, segs.n_params)
    rng = np.random.default_rng(3)
    state = tuple(
        jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
        if hasattr(x, "shape") else x
        for x in state
    )
    sharded = shard_client_state(state, mesh, rules, segments=segs)
    for a, b, seg in zip(state, sharded, segs):
        if not hasattr(a, "shape"):
            assert b == ()
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if seg.size % 8 == 0:  # param dim sharded: ~1/n_dev resident bytes
            assert b.addressable_shards[0].data.nbytes == a.nbytes // 8


def test_shard_client_state_flat_block():
    mesh, rules = _fsdp_mesh_rules()
    rng = np.random.default_rng(4)
    block = jnp.asarray(rng.normal(size=(C, 1024)).astype(np.float32))
    sharded = shard_client_state(block, mesh, rules)
    np.testing.assert_array_equal(np.asarray(block), np.asarray(sharded))
    assert sharded.addressable_shards[0].data.nbytes == block.nbytes // 8
    assert sharded.addressable_shards[0].data.shape == (C, 1024 // 8)


def test_cohort_state_sharded_gather_bitwise_with_eviction():
    mesh, rules = _fsdp_mesh_rules()
    segs = SegmentMap.from_tree(_seg_tree())
    codec = Int8Codec().with_segments(segs)
    shardings = client_state_shardings(mesh, rules, segs)
    plain = CohortState(codec, segs.n_params, capacity=2)
    sharded = CohortState(codec, segs.n_params, capacity=2,
                          shardings=shardings)
    rng = np.random.default_rng(5)
    for cid in (1, 2, 3):  # capacity 2: cid 1 evicted (residual reset to 0)
        row = tuple(rng.normal(size=(seg.size,)).astype(np.float32)
                    for seg in segs)
        plain.put_row(cid, row)
        sharded.put_row(cid, row)
    assert plain.evictions == sharded.evictions == 1
    ids = [1, 2, 3]
    g_plain, g_sharded = plain.gather(ids), sharded.gather(ids)
    for a, b, seg in zip(g_plain, g_sharded, segs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.asarray(b)[0].any()  # evicted row zeros, sharded too
        if seg.size % 8 == 0:
            assert b.addressable_shards[0].data.nbytes == a.nbytes // 8
    # scatter accepts the sharded blocks straight back
    sharded.scatter(ids, g_sharded)
    for a, b in zip(plain.gather(ids), sharded.gather(ids)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------- cost model accounting ----------------
def _cm(**kw):
    return CostModel(
        profiles=[DeviceProfile("d", step_time_s=0.1, active_power_w=5.0)],
        update_bytes=4 * 10_000, **kw,
    )


def test_collective_bytes_ratio_and_tiers():
    tiers = (("pod", 2), ("data", 16))
    n = 10_000
    fp = _cm(mesh_tiers=tiers)
    i8 = _cm(mesh_tiers=tiers, collective="int8")
    assert fp.collective_bytes(n) / i8.collective_bytes(n) >= 3.9
    for cm in (fp, i8):
        by = cm.collective_bytes_by_tier(n)
        assert set(by) == {"pod", "data"}
        assert sum(by.values()) == cm.collective_bytes(n)
        # outer tier reduces once over 2 pods; inner runs 2 groups of 16
        per_hop = cm._per_device_hop_bytes(n)
        assert by["pod"] == 2 * (2 - 1) * per_hop
        assert by["data"] == 2 * 2 * (16 - 1) * per_hop
    # the formula the model bills is the codec's own
    assert i8._per_device_hop_bytes(n) == CompressedPsum().collective_bytes(n)
    assert fp._per_device_hop_bytes(n) == fp32_collective_bytes(n)


def test_round_comm_bytes_mesh_vs_vmap_regression():
    """The mesh path's psum traffic is billed; the vmap path is unchanged."""
    n_clients, n = 8, 10_000
    vmap_cm = _cm()  # no mesh: exact pre-PR accounting
    assert vmap_cm.collective_bytes(n) == 0
    assert vmap_cm.round_comm_bytes(n_clients) == n_clients * 2 * 4 * n
    mesh_cm = _cm(mesh_tiers=(("pod", 2), ("data", 4)), collective="int8")
    got = mesh_cm.round_comm_bytes(n_clients, n_elems=n)
    assert got == n_clients * 2 * 4 * n + mesh_cm.collective_bytes(n)
    assert got > vmap_cm.round_comm_bytes(n_clients)  # was silently equal


def test_collective_tiers_from_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    mesh = make_local_mesh(pod=2, data=2, model=2)
    assert collective_tiers(mesh, ("pod", "data")) == (("pod", 2), ("data", 2))
    with pytest.raises(ValueError, match="not on mesh"):
        collective_tiers(mesh, ("rack",))


def test_compressed_psum_byte_formula():
    cp = CompressedPsum(block=256)
    n = 7050
    assert cp.collective_bytes(n) == n + 4 * ((n + 255) // 256) + 4
    assert fp32_collective_bytes(n) == 4 * n + 4
    assert fp32_collective_bytes(n) / cp.collective_bytes(n) >= 3.9
