"""Property-test harness for the codec wire format (ISSUE-3).

The wire format is load-bearing on every execution path (PR 2), so it is
proven here by properties rather than hand-picked examples, for Null / Int8
/ TopK over random shapes, dtypes and scales:

- **round-trip**: ``encode`` -> ``wire_payload`` -> serialization ->
  ``from_wire`` -> ``decode`` reproduces ``decode(encode(.))`` exactly;
- **size**: the serialized payload is EXACTLY ``codec.wire_bytes(n)`` bytes
  (Int8 encoder padding trimmed off the wire);
- **residual contraction**: repeatedly re-encoding a residual shrinks it
  monotonically, and the error-feedback loop on a fixed delta stays within
  its provable bound;
- **TopK determinism**: equal-magnitude ties break toward the lower index,
  payloads are bit-identical under jit vs eager, and indices arrive in
  canonical ascending order (regression for the lax.top_k tie order);
- **TopK selection against a stable-sort oracle**: the threshold selection
  returns, bit for bit, the payload and residual of a stable sort of
  ``-|x|`` (ties, signed zeros, inf and NaN, k=1, k=n, n=1, odd sizes, a
  segmented tree), and the lowered encoder holds no sort at all.

Hypothesis drives the randomized sweeps when installed (the CI ``test``
extra); every property is ALSO pinned by seeded deterministic cases below
so the harness keeps teeth when hypothesis is absent and the shim skips.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import Int8Codec, NullCodec, SegmentMap, TopKCodec
from repro.core.protocol import compress_to_wire, wire_to_pytree
from repro.core.compression import compress_update, decompress_update
from repro.kernels import ops

CODECS = {
    "null": NullCodec(),
    "int8": Int8Codec(),
    "topk": TopKCodec(frac=0.1),
}


def _vec(n, seed, scale=1.0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n,)) * scale, dtype)


# ------------------------------------------------------------------ round-trip
def _assert_roundtrip(codec, vec):
    n = vec.shape[0]
    enc = codec.encode(vec)
    dec = codec.decode(enc)
    # wire_payload -> (serialize) -> from_wire -> decode is the same decode
    wire = codec.wire_payload(enc)
    rebuilt = codec.from_wire(
        {k: (v if isinstance(v, (int, float)) else jnp.asarray(np.asarray(v)))
         for k, v in wire.items()}
    )
    np.testing.assert_array_equal(np.asarray(codec.decode(rebuilt)), np.asarray(dec))
    # and through the full CompressedParameters serialization
    cp = compress_to_wire(codec, enc, n)
    assert cp.num_bytes == codec.wire_bytes(n), (
        f"{type(codec).__name__}: serialized {cp.num_bytes} != "
        f"wire_bytes {codec.wire_bytes(n)}"
    )
    out = wire_to_pytree(cp, {"w": jnp.zeros_like(vec, jnp.float32)})
    np.testing.assert_allclose(
        np.asarray(out["w"]), np.asarray(dec), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("name", list(CODECS))
@pytest.mark.parametrize("n,seed,scale", [
    (64, 0, 1.0), (300, 1, 1e-3), (511, 2, 1e3), (512, 3, 0.01),
    (513, 4, 10.0), (2048, 5, 1.0), (7, 6, 1.0),
])
def test_wire_roundtrip_and_exact_size(name, n, seed, scale):
    _assert_roundtrip(CODECS[name], _vec(n, seed, scale))


@pytest.mark.parametrize("name", list(CODECS))
def test_wire_roundtrip_bf16_delta(name):
    """bf16 client deltas survive the wire (codecs upcast to fp32)."""
    vec = _vec(300, 9, dtype=jnp.bfloat16).astype(jnp.float32)
    _assert_roundtrip(CODECS[name], vec)


@settings(max_examples=30, deadline=None)
@given(
    name=st.sampled_from(sorted(CODECS)),
    n=st.integers(2, 3000),
    seed=st.integers(0, 2**16),
    log_scale=st.floats(-4.0, 4.0),
)
def test_wire_roundtrip_property(name, n, seed, log_scale):
    _assert_roundtrip(CODECS[name], _vec(n, seed, 10.0 ** log_scale))


# ------------------------------------------------------- residual contraction
def _residual_norms(codec, delta, steps=6):
    """‖r_t‖ for r_0 = delta, r_{t+1} = r_t - decode(encode(r_t))."""
    r, norms = delta, []
    for _ in range(steps):
        r = r - codec.decode(codec.encode(r))
        norms.append(float(jnp.linalg.norm(r)))
    return norms


@pytest.mark.parametrize("name", list(CODECS))
@pytest.mark.parametrize("n,seed,scale", [(256, 0, 1.0), (1000, 1, 1e-2), (333, 2, 1e2)])
def test_repeated_encode_residual_nonincreasing(name, n, seed, scale):
    codec = CODECS[name]
    delta = _vec(n, seed, scale)
    norms = [float(jnp.linalg.norm(delta))] + _residual_norms(codec, delta)
    for a, b in zip(norms, norms[1:]):
        assert b <= a + 1e-5 * max(1.0, a), norms
    if isinstance(codec, NullCodec):
        assert norms[1] == 0.0  # identity wire: nothing is ever left behind
    if isinstance(codec, TopKCodec):
        # dropping the k largest of n removes >= k/n of the energy per pass
        rho = float(np.sqrt(1.0 - codec.k_of(n) / n))
        for a, b in zip(norms, norms[1:]):
            assert b <= rho * a + 1e-5 * max(1.0, a)


@settings(max_examples=20, deadline=None)
@given(name=st.sampled_from(sorted(CODECS)), seed=st.integers(0, 2**16))
def test_repeated_encode_residual_nonincreasing_property(name, seed):
    codec = CODECS[name]
    delta = _vec(512, seed, 1.0)
    norms = [float(jnp.linalg.norm(delta))] + _residual_norms(codec, delta)
    for a, b in zip(norms, norms[1:]):
        assert b <= a + 1e-5 * max(1.0, a), norms


@pytest.mark.parametrize("name,n", [("int8", 512), ("topk", 500)])
def test_error_feedback_loop_residual_bounded(name, n):
    """The error-feedback recursion r <- (delta + r) - decode(encode(delta + r))
    on a FIXED delta stays within its provable bound (TopK: rho/(1-rho)·‖d‖
    with rho = sqrt(1 - k/n); Int8: the blockwise half-scale error)."""
    codec = CODECS[name]
    delta = _vec(n, 7, 0.5)
    r = jnp.zeros_like(delta)
    norms = []
    for _ in range(25):
        eff = delta + r
        r = eff - codec.decode(codec.encode(eff))
        norms.append(float(jnp.linalg.norm(r)))
    d = float(jnp.linalg.norm(delta))
    if name == "topk":
        rho = float(np.sqrt(1.0 - codec.k_of(n) / n))
        bound = rho / (1.0 - rho) * d
        assert max(norms) <= bound + 1e-4, (max(norms), bound)
    else:
        # int8 round-to-nearest: every entry's error <= its block half-scale,
        # and scales track |eff| <= |delta| + |r|; boundedness, not blow-up
        assert max(norms[5:]) <= 2.0 * max(norms[:5]) + 1e-6, norms


# ------------------------------------------------------- TopK determinism
def test_topk_tie_break_is_lower_index():
    """All-equal magnitudes: the k lowest indices win, in ascending order."""
    codec = TopKCodec(frac=0.1)
    n = 100
    for sign in (1.0, -1.0):
        enc = codec.encode(jnp.full((n,), 0.5 * sign, jnp.float32))
        np.testing.assert_array_equal(np.asarray(enc["idx"]), np.arange(codec.k_of(n)))


def test_topk_tie_break_mixed_magnitudes():
    """Ties below the clear winners break toward lower indices."""
    codec = TopKCodec(frac=0.03)  # k=3 of n=100
    x = np.zeros(100, np.float32)
    x[77] = 9.0          # unambiguous top-1
    x[[13, 40, 85]] = 2.0  # three-way tie for the remaining two slots
    enc = codec.encode(jnp.asarray(x))
    np.testing.assert_array_equal(np.sort(np.asarray(enc["idx"])), [13, 40, 77])


@pytest.mark.parametrize("n,seed", [(300, 0), (1024, 1), (65, 2)])
def test_topk_encode_jit_eager_bitwise_identical(n, seed):
    """Regression (ISSUE-3): the payload must be reproducible across jit and
    eager — raw lax.top_k tie order is lowering-dependent."""
    codec = TopKCodec(frac=0.1)
    # quantized values force plenty of exact magnitude ties
    vec = jnp.asarray(
        np.round(np.random.default_rng(seed).normal(size=n) * 4) / 4, jnp.float32
    )
    eager = codec.encode(vec)
    jitted = jax.jit(codec.encode)(vec)
    np.testing.assert_array_equal(np.asarray(eager["idx"]), np.asarray(jitted["idx"]))
    np.testing.assert_array_equal(np.asarray(eager["val"]), np.asarray(jitted["val"]))
    # canonical wire order: indices strictly ascending (hence distinct)
    idx = np.asarray(eager["idx"])
    assert (np.diff(idx) > 0).all(), idx
    # batch surface agrees with the vector surface
    enc_b = jax.jit(codec.encode_batch)(jnp.stack([vec, -vec]))
    np.testing.assert_array_equal(np.asarray(enc_b["idx"][0]), idx)
    np.testing.assert_array_equal(np.asarray(enc_b["idx"][1]), idx)


def test_topk_keeps_largest_magnitudes():
    """Determinism must not change WHAT is selected: the decoded vector
    carries exactly the k largest-|.| entries."""
    codec = TopKCodec(frac=0.1)
    vec = _vec(200, 11)
    enc = codec.encode(vec)
    dec = codec.decode(enc)
    top = np.argsort(-np.abs(np.asarray(vec)))[: codec.k_of(200)]
    np.testing.assert_array_equal(np.sort(np.asarray(enc["idx"])), np.sort(top))
    np.testing.assert_allclose(
        np.asarray(dec[enc["idx"]]), np.asarray(vec[enc["idx"]]), atol=0
    )


# ------------------------------------------- TopK against a stable sort
def _sort_oracle_idx(mags, k):
    """The encoder's former selection, kept here as the oracle: a stable
    sort of (-|x|, iota) and the first k re-sorted ascending."""
    iota = jax.lax.broadcasted_iota(jnp.int32, mags.shape, mags.ndim - 1)
    _, idx = jax.lax.sort(
        (-mags.astype(jnp.float32), iota), dimension=-1, num_keys=1, is_stable=True,
    )
    return jnp.sort(idx[..., :k], axis=-1)


def _oracle_encode(x, k):
    idx = _sort_oracle_idx(jnp.abs(x), k)
    return idx, jnp.take_along_axis(x, idx, axis=-1)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _assert_bitwise(got, want, what):
    np.testing.assert_array_equal(_bits(got), _bits(want), err_msg=what)


def _selection_case(name):
    """-> ((C, n) float32 rows, frac)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "random":
        return rng.normal(size=(10, 4099)).astype(np.float32) * 1e-3, 0.01
    if name == "quantised_ties":
        return (np.round(rng.normal(size=(3, 1000)) * 4) / 4).astype(np.float32), 0.1
    if name == "all_equal_positive":
        return np.full((2, 300), 0.5, np.float32), 0.1
    if name == "all_equal_negative":
        return np.full((2, 300), -0.5, np.float32), 0.1
    if name == "half_zeroed":  # k reaches into the zeros, +0 and -0 tie
        x = rng.normal(size=(4, 501)).astype(np.float32)
        x[:, 1::2] = 0.0
        x[:, 2::4] = -0.0
        x[1] = -0.0
        return x, 0.7
    if name == "inf_nan":  # NaN ranks below every number, even 0
        x = rng.normal(size=(4, 640)).astype(np.float32)
        special = np.array([np.inf, -np.inf, np.nan, -np.nan, 0.0, -0.0, 1e-45], np.float32)
        hit = rng.random(x.shape) < 0.4
        x[hit] = rng.choice(special, size=int(hit.sum()))
        return x, 0.9
    if name == "mostly_nan":
        x = rng.normal(size=(3, 200)).astype(np.float32)
        x[rng.random(x.shape) < 0.8] = np.nan
        return x, 0.5
    if name == "k_is_1":
        return rng.normal(size=(4, 777)).astype(np.float32), 0.0
    if name == "k_is_n":
        return (np.round(rng.normal(size=(3, 129)) * 2) / 2).astype(np.float32), 1.0
    if name == "n_is_1":
        return rng.normal(size=(3, 1)).astype(np.float32), 0.01
    n = int(name.removeprefix("odd_n"))
    return rng.normal(size=(5, n)).astype(np.float32) * 10.0 ** rng.uniform(-6, 6, (5, 1)), 0.05


SELECTION_CASES = [
    "random", "quantised_ties", "all_equal_positive", "all_equal_negative",
    "half_zeroed", "inf_nan", "mostly_nan", "k_is_1", "k_is_n", "n_is_1",
    "odd_n7", "odd_n65", "odd_n513", "odd_n12345",
]


@pytest.mark.parametrize("rank", [1, 2])
@pytest.mark.parametrize("case", SELECTION_CASES)
def test_topk_selection_matches_stable_sort_oracle(case, rank):
    """Threshold selection == stable sort, bit for bit: idx, val and the
    error-feedback residual, on the 1-D (per-client) and (C, n) surfaces."""
    rows, frac = _selection_case(case)
    codec = TopKCodec(frac=frac)
    x = jnp.asarray(rows[0] if rank == 1 else rows)
    n = x.shape[-1]
    k = codec.k_of(n)
    want_idx, want_val = _oracle_encode(x, k)

    enc = jax.jit(codec.encode if rank == 1 else codec.encode_batch)(x)
    _assert_bitwise(enc["idx"], want_idx, "idx")
    _assert_bitwise(enc["val"], want_val, "val")
    assert enc["n"] == n

    # the residual: a state of -0.0 leaves eff = x + state bitwise x
    state = jnp.full(x.shape, -0.0, jnp.float32)
    if rank == 1:
        seg = codec.segment_map(n)[0]
        dec, resid = jax.jit(lambda v, r: codec.transmit_segment(v, r, seg))(x, state)
        _assert_bitwise(dec, codec.decode({"idx": want_idx, "val": want_val, "n": n}), "decode")
        _assert_bitwise(resid, x.at[want_idx].set(0.0), "residual")
    else:
        w = jnp.linspace(1.0, 2.0, x.shape[0], dtype=jnp.float32)
        mean, resid = jax.jit(codec.aggregate_batch)(x, w, state)
        rows_ = jnp.arange(x.shape[0])[:, None]
        _assert_bitwise(resid, x.at[rows_, want_idx].set(0.0), "residual")
        _assert_bitwise(mean, ops.topk_scatter_reduce(want_idx, want_val, w, n), "mean")


_RESNET_LIKE = {
    "stem": {"kernel": (3, 3, 3, 16), "scale": (16,), "bias": (16,)},
    "block": {"conv1": (3, 3, 16, 32), "conv2": (3, 3, 32, 32), "scale": (32,)},
    "head": {"kernel": (32, 10), "bias": (10,)},
}


def _resnet_like_tree(rng):
    return jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s), jnp.float32), _RESNET_LIKE,
        is_leaf=lambda s: isinstance(s, tuple),
    )


def test_topk_selection_segmented_tree_matches_oracle():
    """A ResNet-like tree, leaf by leaf through ``aggregate_updates``: new
    params and every residual row equal the stable-sort oracle's."""
    rng = np.random.default_rng(7)
    glob = _resnet_like_tree(rng)
    c = 4
    clients = jax.tree.map(
        lambda g: g + jnp.asarray(np.round(rng.normal(size=(c,) + g.shape) * 8) / 256, jnp.float32),
        glob,
    )
    codec = TopKCodec(frac=0.05, segments=SegmentMap.from_tree(glob))
    n_params = sum(g.size for g in jax.tree.leaves(glob))
    state = tuple(
        jnp.asarray(rng.normal(size=r.shape) * 1e-3, jnp.float32)
        for r in codec.init_client_state(c, n_params)
    )
    w = jnp.asarray([1.0, 2.0, 0.5, 1.5], jnp.float32)
    new_glob, new_state = jax.jit(codec.aggregate_updates)(clients, glob, w, state)

    g_leaves, treedef = jax.tree.flatten(glob)
    want_leaves, want_state = [], []
    for seg, lc, lg, row in zip(codec.segments, jax.tree.leaves(clients), g_leaves, state):
        eff = (lc.reshape(c, -1) - lg.reshape(-1)) + row
        idx, val = _oracle_encode(eff, codec.k_of(seg.size))
        want_state.append(eff.at[jnp.arange(c)[:, None], idx].set(0.0))
        mean = ops.topk_scatter_reduce(idx, val, w, seg.size)
        want_leaves.append(lg + mean.reshape(lg.shape))
    for got, want in zip(jax.tree.leaves(new_glob), want_leaves):
        _assert_bitwise(got, want, "params")
    for got, want in zip(new_state, want_state):
        _assert_bitwise(got, want, "residual")
    assert jax.tree.structure(new_glob) == treedef


@pytest.mark.parametrize("surface", ["transmit_tree", "transmit_tree_flat", "encode_structured"])
def test_topk_per_client_paths_select_every_segment_in_one_call(surface, monkeypatch):
    """The per-client surfaces (mesh shard_map and sequential scan:
    ``transmit_tree``, by leaves or by a flat vector's slices; the protocol:
    ``encode_structured``) select every segment in one selection call, and
    each segment's payload, decode and residual equal the stable-sort
    oracle's bit for bit."""
    rng = np.random.default_rng(11)
    glob = _resnet_like_tree(rng)
    codec = TopKCodec(frac=0.05, segments=SegmentMap.from_tree(glob))
    delta = jax.tree.map(
        lambda g: jnp.asarray(np.round(rng.normal(size=g.shape) * 8) / 256, jnp.float32), glob
    )
    vec = jnp.concatenate([leaf.reshape(-1) for leaf in jax.tree.leaves(delta)])
    row = tuple(
        jnp.asarray(rng.normal(size=seg.size) * 1e-3, jnp.float32) for seg in codec.segments
    )
    calls = []
    select = ops.topk_select_rows
    monkeypatch.setattr(ops, "topk_select_rows", lambda xs, ks: calls.append(ks) or select(xs, ks))

    if surface == "encode_structured":
        encs = codec.encode_structured(vec).payloads
        effs = codec.segments.split(vec)
    else:
        tree = delta if surface == "transmit_tree" else vec
        dec, new_row = codec.transmit_tree(tree, row)
        decs = codec.segments.split(jnp.concatenate([d.reshape(-1) for d in jax.tree.leaves(dec)]))
        effs = [part + r for part, r in zip(codec.segments.split(vec), row)]
    assert len(calls) == 1 and len(calls[0]) == len(codec.segments)

    for i, (seg, eff) in enumerate(zip(codec.segments, effs)):
        idx, val = _oracle_encode(eff, codec.k_of(seg.size))
        if surface == "encode_structured":
            _assert_bitwise(encs[i]["idx"], idx, "idx")
            _assert_bitwise(encs[i]["val"], val, "val")
            continue
        _assert_bitwise(decs[i], codec.decode({"idx": idx, "val": val, "n": seg.size}), "decode")
        _assert_bitwise(new_row[i], eff.at[idx].set(0.0), "residual")


@pytest.mark.parametrize("surface", ["encode_batch", "aggregate_batch"])
def test_topk_encoder_lowers_without_sort(surface):
    """At ResNet-18's largest leaf, (10, 3*3*512*512) fp32, the lowered
    encoder (and the error-feedback aggregate around it) holds no sort, so
    a fallback to sorting the leaf cannot come back silently.  Lowering
    only: nothing compiles or allocates."""
    c, n = 10, 3 * 3 * 512 * 512
    codec = TopKCodec(frac=0.01)
    rows = jax.ShapeDtypeStruct((c, n), jnp.float32)
    if surface == "encode_batch":
        lowered = jax.jit(codec.encode_batch).lower(rows)
    else:
        lowered = jax.jit(codec.aggregate_batch).lower(
            rows, jax.ShapeDtypeStruct((c,), jnp.float32), rows
        )
    assert "stablehlo.sort" not in lowered.as_text(), f"{surface}: the program sorts"
    # the check can see a sort: the former selection's lowers with one
    oracle = jax.jit(lambda x: _sort_oracle_idx(jnp.abs(x), n // 100)).lower(rows)
    assert "stablehlo.sort" in oracle.as_text()


# ------------------------------------------------------- full client loop
@pytest.mark.parametrize("name", list(CODECS))
def test_compress_update_roundtrip_with_residual(name):
    """The python client loop (compress_update / decompress_update) preserves
    delta + residual telescoping: transmitted + new_residual == delta + old."""
    codec = CODECS[name]
    rng = np.random.default_rng(3)
    old = {"w": jnp.asarray(rng.normal(size=(300,)), jnp.float32)}
    new = {"w": old["w"] + 0.01 * jnp.asarray(rng.normal(size=(300,)), jnp.float32)}
    residual = 0.001 * jnp.asarray(rng.normal(size=(300,)), jnp.float32)
    enc, new_res = compress_update(codec, new, old, residual=residual)
    sent = codec.decode(enc)
    eff = (new["w"] - old["w"]) + residual
    np.testing.assert_allclose(
        np.asarray(sent + new_res), np.asarray(eff), atol=1e-5, rtol=1e-5
    )
    rebuilt = decompress_update(codec, enc, old)
    np.testing.assert_allclose(
        np.asarray(rebuilt["w"]), np.asarray(old["w"] + sent), atol=1e-6
    )
