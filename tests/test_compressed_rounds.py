"""Unified codec-carrying round engine, end-to-end (core/rounds.py).

Asserts the ISSUE-2 acceptance criteria on the synthetic head-model task:
- ONE round_step signature across parallel / mesh shard_map / sequential:
  (global, server_state, client_state, batches, weights, budgets, rnd)
  -> (global, server_state, client_state, metrics), with the client state
  owned by the codec (empty for NullCodec);
- the Int8 compressed path converges to within rtol=5e-2 of the NullCodec
  baseline on final eval loss over 20 rounds on ALL THREE paths (the mesh
  path runs on a real multi-device host-platform mesh, see conftest.py);
- TopK with error feedback also tracks the baseline (looser tol — it
  transmits a fraction of the mass per round);
- accumulated error-feedback residuals stay bounded (no blow-up);
- batch codec roundtrips agree with the 1-D codec surface.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedAvg, Int8Codec, NullCodec, RoundSpec, TopKCodec, make_round_step,
)
from repro.models import build_model
from repro.launch.mesh import make_local_mesh
from repro.optim import sgd
from repro.utils.pytree import tree_size

C, STEPS, B, ROUNDS = 4, 2, 16, 20


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
    """A 2x2 ("pod", "data") mesh: 4 clients, hierarchical cross-pod psum."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 host devices (see conftest.py)")
    return make_local_mesh(pod=2, data=2), ("pod", "data")


def _run(m, params, train, eval_batch, codec, mode="parallel", mesh=None,
         client_axes=("data",), rounds=ROUNDS):
    strat = FedAvg()
    spec = RoundSpec(max_steps=STEPS, execution_mode=mode, codec=codec)
    rs = jax.jit(make_round_step(
        m.loss_fn, sgd(0.1), strat, spec, mesh=mesh, client_axes=client_axes,
    ))
    w = jnp.ones(C)
    bud = jnp.full((C,), STEPS, jnp.int32)
    state = strat.init_state(params)
    cstate = codec.init_client_state(C, tree_size(params))
    p = params
    res_norms = []
    for rnd in range(rounds):
        p, state, cstate, met = rs(p, state, cstate, train, w, bud, rnd)
        if "residual_norm_mean" in met:
            res_norms.append(float(met["residual_norm_mean"]))
    loss, _ = m.loss_fn(p, eval_batch)
    return float(loss), res_norms


# ---------------- the uniform contract ----------------
def test_client_state_is_codec_owned():
    m, params, _, _ = _setup()
    n = tree_size(params)
    assert NullCodec().init_client_state(C, n) == ()
    res = Int8Codec().init_client_state(C, n)
    assert res.shape == (C, n) and res.dtype == jnp.float32
    assert not np.asarray(res).any()


@pytest.mark.parametrize("mode", ["parallel", "sequential"])
def test_round_step_uniform_signature(mode):
    """Same 7-arg/4-tuple contract whether or not anything is compressed."""
    m, params, train, _ = _setup()
    n = tree_size(params)
    for codec in (NullCodec(), Int8Codec()):
        spec = RoundSpec(max_steps=STEPS, execution_mode=mode, codec=codec)
        rs = jax.jit(make_round_step(m.loss_fn, sgd(0.1), FedAvg(), spec))
        cstate = codec.init_client_state(C, n)
        p, sstate, new_cstate, met = rs(
            params, (), cstate, train, jnp.ones(C),
            jnp.full((C,), STEPS, jnp.int32), 0,
        )
        assert jax.tree.structure(p) == jax.tree.structure(params)
        assert jax.tree.structure(new_cstate) == jax.tree.structure(cstate)
        if jax.tree.leaves(cstate):
            assert new_cstate.shape == (C, n)
            assert float(met["residual_norm_mean"]) >= 0.0
        assert {"client_loss_mean", "client_loss_max", "steps_total"} <= set(met)


def test_default_codec_is_null():
    assert isinstance(RoundSpec(max_steps=1, execution_mode="parallel").codec,
                      NullCodec)


# ---------------- parallel (vmap) path ----------------
def test_int8_round_path_converges_like_uncompressed():
    """ISSUE acceptance: Int8 final eval loss within rtol=5e-2 over 20 rounds."""
    m, params, train, eval_batch = _setup()
    base, base_norms = _run(m, params, train, eval_batch, NullCodec())
    assert base_norms == []  # NullCodec carries no residual state at all
    int8, res_norms = _run(m, params, train, eval_batch, Int8Codec())
    assert int8 == pytest.approx(base, rel=5e-2)
    # error feedback keeps the residual bounded (quantization error scale)
    assert res_norms[-1] < 10 * (res_norms[0] + 1e-9)
    assert max(res_norms) < 1.0


def test_topk_error_feedback_converges_and_residual_bounded():
    m, params, train, eval_batch = _setup()
    base, _ = _run(m, params, train, eval_batch, NullCodec())
    topk, res_norms = _run(m, params, train, eval_batch, TopKCodec(frac=0.25))
    # sparsified wire still reaches the neighborhood of the dense optimum
    assert topk == pytest.approx(base, rel=0.25)
    # residual does not blow up: later rounds stay within a constant factor
    # of the early-round residual scale
    assert res_norms[-1] < 5 * max(res_norms[:5])


# ---------------- mesh shard_map path ----------------
def test_mesh_path_null_codec_matches_vmap_path():
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    base, _ = _run(m, params, train, eval_batch, NullCodec(), rounds=3)
    meshed, _ = _run(m, params, train, eval_batch, NullCodec(),
                     mesh=mesh, client_axes=axes, rounds=3)
    assert meshed == pytest.approx(base, rel=1e-3)


def test_int8_mesh_path_converges_like_uncompressed():
    """ISSUE acceptance: codec on the shard_map path (encode before the
    hierarchical cross-pod psum), within 5% of NullCodec over 20 rounds."""
    m, params, train, eval_batch = _setup()
    mesh, axes = _client_mesh()
    base, _ = _run(m, params, train, eval_batch, NullCodec(),
                   mesh=mesh, client_axes=axes)
    int8, res_norms = _run(m, params, train, eval_batch, Int8Codec(),
                           mesh=mesh, client_axes=axes)
    assert int8 == pytest.approx(base, rel=5e-2)
    assert res_norms and max(res_norms) < 1.0


def test_client_loss_mean_weighted_on_every_mode():
    """Same round, same metric: client_loss_mean is the examples-weighted
    mean on the vmap, mesh shard_map, and sequential paths alike (the vmap
    and mesh paths used to report an unweighted jnp.mean)."""
    m, params, train, _ = _setup()
    mesh, axes = _client_mesh()
    w = jnp.asarray([1.0, 4.0, 0.25, 2.0])  # non-uniform: unweighted differs
    bud = jnp.full((C,), STEPS, jnp.int32)
    means = {}
    for label, kw in (
        ("parallel", {}),
        ("mesh", {"mesh": mesh, "client_axes": axes}),
        ("sequential", {}),
    ):
        mode = "sequential" if label == "sequential" else "parallel"
        spec = RoundSpec(max_steps=STEPS, execution_mode=mode, codec=NullCodec())
        rs = jax.jit(make_round_step(m.loss_fn, sgd(0.1), FedAvg(), spec, **kw))
        _, _, _, met = rs(params, (), (), train, w, bud, 0)
        means[label] = float(met["client_loss_mean"])
    assert means["mesh"] == pytest.approx(means["parallel"], rel=1e-4)
    assert means["sequential"] == pytest.approx(means["parallel"], rel=1e-4)


# ---------------- sequential scan path ----------------
def test_int8_sequential_path_converges_like_uncompressed():
    """ISSUE acceptance: codec through the sequential scan (per-client state
    rows scanned alongside), within 5% of NullCodec over 20 rounds."""
    m, params, train, eval_batch = _setup()
    base, _ = _run(m, params, train, eval_batch, NullCodec(), mode="sequential")
    int8, res_norms = _run(m, params, train, eval_batch, Int8Codec(),
                           mode="sequential")
    assert int8 == pytest.approx(base, rel=5e-2)
    assert res_norms and max(res_norms) < 1.0


def test_sequential_residual_rows_track_clients():
    """The scanned state rows land back in per-client order: round 2 of a
    sequential run equals round 2 of a parallel run (same codec state)."""
    m, params, train, eval_batch = _setup()
    outs = {}
    for mode in ("parallel", "sequential"):
        outs[mode], _ = _run(m, params, train, eval_batch, Int8Codec(),
                             mode=mode, rounds=2)
    assert outs["sequential"] == pytest.approx(outs["parallel"], rel=1e-2)


# ---------------- codec surfaces ----------------
@pytest.mark.parametrize("codec", [Int8Codec(), TopKCodec(frac=0.1), NullCodec()])
def test_batch_codec_agrees_with_vector_codec(codec):
    rng = np.random.default_rng(3)
    deltas = jnp.asarray(rng.normal(size=(3, 700)) * 0.01, jnp.float32)
    enc_b = codec.encode_batch(deltas)
    dec_b = codec.decode_batch(enc_b)
    assert dec_b.shape == deltas.shape
    for i in range(3):
        dec_1 = codec.decode(codec.encode(deltas[i]))
        np.testing.assert_allclose(
            np.asarray(dec_b[i]), np.asarray(dec_1), atol=1e-6, rtol=1e-6
        )
    # reduce == fedavg_reduce over the decoded rows
    w = jnp.asarray(rng.random(3) + 0.1, jnp.float32)
    red = codec.reduce(enc_b, w)
    exp = jnp.einsum("c,cn->n", w, dec_b) / jnp.sum(w)
    np.testing.assert_allclose(np.asarray(red), np.asarray(exp), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("codec", [Int8Codec(), TopKCodec(frac=0.1)])
def test_transmit_tree_matches_encode_decode(codec):
    rng = np.random.default_rng(7)
    delta = {"a": jnp.asarray(rng.normal(size=(40, 8)) * 0.01, jnp.float32),
             "b": jnp.asarray(rng.normal(size=(13,)) * 0.01, jnp.float32)}
    n = 40 * 8 + 13
    state = jnp.zeros((n,), jnp.float32)
    dec_tree, new_state = codec.transmit_tree(delta, state)
    from repro.utils.pytree import tree_flatten_to_vector
    vec = tree_flatten_to_vector(delta)
    dec_vec = codec.decode(codec.encode(vec))
    np.testing.assert_allclose(
        np.asarray(tree_flatten_to_vector(dec_tree)), np.asarray(dec_vec),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(new_state), np.asarray(vec - dec_vec), atol=1e-6
    )


def test_null_transmit_tree_is_identity():
    delta = {"a": jnp.ones((4, 4), jnp.bfloat16)}
    out, state = NullCodec().transmit_tree(delta, ())
    assert out is delta and state == ()
