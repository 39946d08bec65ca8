"""Per-kernel correctness: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.dequant_reduce import dequant_reduce
from repro.kernels.fedavg_reduce import fedavg_reduce
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import dequantize_int8, quantize_int8
from repro.kernels.selective_scan import selective_scan
from repro.kernels.topk_select import BLOCK_LINES, topk_select_rows

RNG = np.random.default_rng(0)


def _randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,d,window",
    [
        (1, 128, 4, 4, 64, None),      # MHA
        (2, 256, 8, 2, 64, None),      # GQA 4:1
        (1, 256, 4, 1, 128, None),     # MQA
        (2, 256, 4, 4, 64, 64),        # sliding window
        (1, 384, 6, 3, 32, 128),       # non-pow2 heads, window
    ],
)
def test_flash_attention_matches_oracle(b, s, h, kv, d, window, dtype):
    q = _randn((b, s, h, d), dtype)
    k = _randn((b, s, kv, d), dtype)
    v = _randn((b, s, kv, d), dtype)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    exp = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,d", [(2, 256, 8, 4, 64), (1, 128, 4, 1, 128)])
def test_decode_attention_matches_oracle(b, s, h, kv, d, dtype):
    q = _randn((b, h, d), dtype)
    k = _randn((b, s, kv, d), dtype)
    v = _randn((b, s, kv, d), dtype)
    valid = jnp.asarray(RNG.random((b, s)) > 0.25)
    valid = valid.at[:, 0].set(True)  # at least one valid slot
    out = decode_attention(q, k, v, kv_valid=valid, interpret=True)
    exp = ref.decode_attention(q, k, v, kv_valid=valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("b,s,di,n,bd,chunk", [
    (1, 128, 64, 16, 32, 64),
    (2, 256, 128, 8, 128, 128),
])
def test_selective_scan_matches_oracle(b, s, di, n, bd, chunk):
    x = _randn((b, s, di), scale=0.5)
    dt = jax.nn.softplus(_randn((b, s, di)))
    A = -jnp.exp(_randn((di, n), scale=0.3))
    Bm = _randn((b, s, n))
    Cm = _randn((b, s, n))
    D = _randn((di,))
    y1, h1 = selective_scan(x, dt, A, Bm, Cm, D, interpret=True, bd=bd, chunk=chunk)
    y2, h2 = ref.selective_scan(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4, rtol=2e-4)


def test_selective_scan_matches_stepwise_recurrence():
    """The parallel scan equals the literal per-token recurrence."""
    b, s, di, n = 1, 64, 32, 8
    x = _randn((b, s, di), scale=0.5)
    dt = jax.nn.softplus(_randn((b, s, di)))
    A = -jnp.exp(_randn((di, n), scale=0.3))
    Bm, Cm, D = _randn((b, s, n)), _randn((b, s, n)), _randn((di,))
    y_par, h_par = ref.selective_scan(x, dt, A, Bm, Cm, D)
    h = jnp.zeros((b, di, n))
    ys = []
    for t in range(s):
        y, h = ref.selective_scan_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y_par), np.stack(ys, 1), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h), atol=2e-5, rtol=2e-5)


def test_selective_scan_init_state_continuation():
    """scan(x[0:s]) == scan(x[0:m]) then scan(x[m:s], init_state)."""
    b, s, m_, di, n = 1, 128, 64, 32, 8
    x = _randn((b, s, di), scale=0.5)
    dt = jax.nn.softplus(_randn((b, s, di)))
    A = -jnp.exp(_randn((di, n), scale=0.3))
    Bm, Cm, D = _randn((b, s, n)), _randn((b, s, n)), _randn((di,))
    y_full, h_full = ref.selective_scan(x, dt, A, Bm, Cm, D)
    _, h1 = ref.selective_scan(x[:, :m_], dt[:, :m_], A, Bm[:, :m_], Cm[:, :m_], D)
    y2, h2 = ref.selective_scan(
        x[:, m_:], dt[:, m_:], A, Bm[:, m_:], Cm[:, m_:], D, init_state=h1
    )
    np.testing.assert_allclose(np.asarray(y_full[:, m_:]), np.asarray(y2), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("c,n,bn", [(4, 8192, 4096), (16, 16384, 8192), (3, 4096, 4096)])
def test_fedavg_reduce_matches_oracle(c, n, bn):
    u = _randn((c, n))
    w = jnp.asarray(RNG.random(c) + 0.1, jnp.float32)
    out = fedavg_reduce(u, w, interpret=True, bn=bn)
    exp = ref.fedavg_reduce(u, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("c,n,bn", [(4, 5000, 4096), (3, 8193, 8192), (2, 100, 64)])
def test_fedavg_reduce_tail_block(c, n, bn):
    """Regression: n % bn != 0 — the tail block must be reduced, not dropped."""
    u = _randn((c, n))
    w = jnp.asarray(RNG.random(c) + 0.1, jnp.float32)
    out = fedavg_reduce(u, w, interpret=True, bn=bn)
    exp = ref.fedavg_reduce(u, w)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)
    # the tail specifically (the elements past the last full tile)
    np.testing.assert_allclose(
        np.asarray(out[-(n % bn):]), np.asarray(exp[-(n % bn):]), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("c,n,bn", [(4, 5000, 4096), (8, 10, 8192), (3, 8192, 2048)])
def test_fedavg_reduce_centered_matches_oracle(c, n, bn):
    """The centered reduce is the fp32 mean of the client deltas, tail
    tile included (the Null round's leafwise aggregation)."""
    u = _randn((c, n))
    g = _randn((n,))
    w = jnp.asarray(RNG.random(c) + 0.1, jnp.float32)
    out = fedavg_reduce(u, w, g, interpret=True, bn=bn)
    exp = ref.fedavg_reduce(u, w, g)
    assert out.shape == (n,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(exp), np.asarray(ref.fedavg_reduce(u - g, w)), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("c,n,bn", [(4, 8192, 4096), (3, 5120, 2048), (2, 2048, 2048)])
def test_dequant_reduce_matches_oracle(c, n, bn):
    """Fused dequantize+weighted-reduce == dequantize rows then fedavg_reduce."""
    x = _randn((c, n))
    q, s = ref.quantize_int8(x.reshape(-1))
    q = q.reshape(c, n)
    s = s.reshape(c, n // 256)
    w = jnp.asarray(RNG.random(c) + 0.1, jnp.float32)
    fused = dequant_reduce(q, s, w, interpret=True, bn=bn)
    exp = ref.dequant_reduce(q, s, w)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(exp), atol=2e-5, rtol=2e-5)
    # and the unfused composition agrees
    dense = jnp.stack([ref.dequantize_int8(q[i], s[i]) for i in range(c)])
    unfused = ref.fedavg_reduce(dense, w)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused), atol=2e-5, rtol=2e-5)


# ---------------- TopK scatter-accumulate reduce ----------------
from repro.kernels.scatter_reduce import topk_scatter_reduce


def _sparse_payload(c, k, n, seed, dup=False):
    rng = np.random.default_rng(seed)
    if dup and k > 1:
        # force duplicate indices within each client (they must ACCUMULATE)
        pool = rng.integers(0, n, (c, max(1, k // 2)))
        idx = pool[:, rng.integers(0, pool.shape[1], k)]
    else:
        idx = np.stack([rng.choice(n, size=k, replace=False) for _ in range(c)])
    val = rng.normal(size=(c, k)).astype(np.float32)
    w = (rng.random(c) + 0.1).astype(np.float32)
    return jnp.asarray(idx, jnp.int32), jnp.asarray(val), jnp.asarray(w)


def _dense_of(idx, val, n):
    """Densify a sparse payload with np.add.at (duplicates accumulate)."""
    c = idx.shape[0]
    dense = np.zeros((c, n), np.float32)
    for i in range(c):
        np.add.at(dense[i], np.asarray(idx[i]), np.asarray(val[i]))
    return jnp.asarray(dense)


@pytest.mark.parametrize("c,k,n", [(4, 64, 8192), (8, 10, 1000), (2, 512, 4096)])
def test_topk_scatter_reduce_matches_dense_reference(c, k, n):
    idx, val, w = _sparse_payload(c, k, n, seed=c * 1000 + k)
    out = topk_scatter_reduce(idx, val, w, n, interpret=True)
    exp = ref.fedavg_reduce(_dense_of(idx, val, n), w)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.topk_scatter_reduce(idx, val, w, n)), np.asarray(exp),
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("c,k,n", [(4, 32, 2048), (3, 7, 100)])
def test_topk_scatter_reduce_duplicate_indices_accumulate(c, k, n):
    """Duplicate indices within one client sum, exactly like np.add.at."""
    idx, val, w = _sparse_payload(c, k, n, seed=42, dup=True)
    out = topk_scatter_reduce(idx, val, w, n, interpret=True)
    exp = ref.fedavg_reduce(_dense_of(idx, val, n), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5, rtol=1e-5)


def test_topk_scatter_reduce_k_zero_clients():
    """k == 0 (empty payloads) and zero-value padding rows both yield the
    contribution-free result on kernel and oracle alike."""
    n = 500
    for fn in (lambda i, v, w: topk_scatter_reduce(i, v, w, n, interpret=True),
               lambda i, v, w: ref.topk_scatter_reduce(i, v, w, n)):
        out = fn(jnp.zeros((3, 0), jnp.int32), jnp.zeros((3, 0), jnp.float32),
                 jnp.ones(3))
        assert out.shape == (n,) and not np.asarray(out).any()
    # a client padded with val=0 entries (heterogeneous k) contributes nothing
    idx, val, w = _sparse_payload(4, 16, n, seed=7)
    val = val.at[2].set(0.0)
    out = topk_scatter_reduce(idx, val, w, n, interpret=True)
    exp = ref.fedavg_reduce(_dense_of(idx, val, n), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5, rtol=1e-5)


def test_topk_scatter_reduce_out_of_range_indices_dropped():
    """A corrupt wire payload (idx < 0 or >= N) must be dropped identically
    by kernel and oracle — no negative wrapping, no out-of-block write."""
    n = 256
    idx = jnp.asarray([[0, -1, n, 5, 2**30, 255]], jnp.int32)
    val = jnp.ones((1, 6), jnp.float32)
    w = jnp.ones(1)
    exp = np.zeros(n, np.float32)
    exp[[0, 5, 255]] = 1.0  # only the in-range entries land
    for out in (topk_scatter_reduce(idx, val, w, n, interpret=True),
                ref.topk_scatter_reduce(idx, val, w, n)):
        np.testing.assert_allclose(np.asarray(out), exp, atol=1e-6)


def test_topk_scatter_reduce_zero_weight_vector():
    """safe_weight_sum semantics: all-zero weights -> zeros, never NaNs."""
    idx, val, _ = _sparse_payload(4, 32, 1024, seed=3)
    out = topk_scatter_reduce(idx, val, jnp.zeros(4), 1024, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)
    out_ref = ref.topk_scatter_reduce(idx, val, jnp.zeros(4), 1024)
    np.testing.assert_array_equal(np.asarray(out_ref), 0.0)


@pytest.mark.parametrize("n", [100, 5000, 8193, 129])
def test_topk_scatter_reduce_tail_indices(n):
    """Regression (fedavg_reduce tail-drop class): indices in the last,
    non-lane-aligned tail of the output must land, not vanish in pad."""
    c, k = 3, 8
    rng = np.random.default_rng(n)
    idx = jnp.asarray(rng.integers(0, n, (c, k)), jnp.int32)
    idx = idx.at[:, -1].set(n - 1).at[:, 0].set(0)  # pin both boundaries
    val = jnp.asarray(rng.normal(size=(c, k)), jnp.float32)
    w = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
    out = topk_scatter_reduce(idx, val, w, n, interpret=True)
    exp = ref.fedavg_reduce(_dense_of(idx, val, n), w)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5, rtol=1e-5)
    assert np.asarray(out)[-1] == pytest.approx(float(exp[-1]), abs=1e-5)


def _topk_rows(kind, c, n):
    x = RNG.normal(size=(c, n)).astype(np.float32)
    if kind == "ties":
        x = np.round(x * 4) / 4
    elif kind == "special":
        special = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-45], np.float32)
        hit = RNG.random(x.shape) < 0.4
        x[hit] = RNG.choice(special, size=int(hit.sum()))
    elif kind == "clustered":  # a run of large values: many kept in a line
        x *= 1e-3
        x[:, 300:300 + n // 20] *= 1e4
    elif kind == "equal":
        x[:] = -0.5
    return jnp.asarray(x, jnp.float32)


@pytest.mark.parametrize("kind,c,n,frac", [
    ("random", 3, 4096, 0.01),
    ("ties", 2, 1000, 0.1),            # n not a multiple of 128
    ("special", 2, 777, 0.9),          # NaN, inf, signed zeros, denormals
    ("clustered", 2, 70_000, 0.05),    # lines that keep most of their lanes
    ("equal", 2, 9000, 0.5),           # every magnitude ties
    ("random", 3, 64, 0.0),            # k = 1, a leaf shorter than a line
    ("random", 1, 130, 1.0),           # k = n
    ("random", 1, 1, 0.01),            # n = 1
    # two blocks, the last partial, the row not a multiple of 128
    ("ties", 2, (BLOCK_LINES + 88) * 128 + 5, 0.3),
])
def test_topk_select_matches_oracle(kind, c, n, frac):
    """The kernel's idx, val and residual equal the oracle's bit for bit."""
    x = _topk_rows(kind, c, n)
    k = max(1, int(n * frac))
    want = jax.jit(ref.topk_select, static_argnums=1)(x, k)
    [got] = topk_select_rows((x,), (k,), interpret=True)
    for name, g, w in zip(("idx", "val", "residual"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g).view(np.int32), np.asarray(w).view(np.int32), err_msg=name
        )


def test_topk_select_one_row_surface():
    """A 1-D row goes through the kernel as one client."""
    x = _topk_rows("ties", 1, 3000)[0]
    [got] = topk_select_rows((x,), (30,), interpret=True)
    want = ref.topk_select(x, 30)
    assert got[0].shape == (30,) and got[2].shape == (3000,)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g).view(np.int32), np.asarray(w).view(np.int32))


def test_topk_select_rows_many_leaves_in_one_call():
    """Leaves of mixed sizes and ks through one kernel call: each leaf's
    payload and residual equal its own oracle's."""
    ns = [1728, 64, 10, 36_864, 130, 5120]
    fracs = [0.01, 1.0, 0.3, 0.01, 0.9, 0.02]
    kinds = ["ties", "random", "special", "random", "equal", "clustered"]
    xs = tuple(_topk_rows(kind, 3, n) for kind, n in zip(kinds, ns))
    ks = tuple(max(1, int(n * f)) for n, f in zip(ns, fracs))
    for x, k, got in zip(xs, ks, topk_select_rows(xs, ks, interpret=True)):
        for g, w in zip(got, ref.topk_select(x, k)):
            np.testing.assert_array_equal(np.asarray(g).view(np.int32), np.asarray(w).view(np.int32))


def test_topk_codec_reduce_hits_scatter_kernel():
    """The codec's reduce on a REAL encoded payload == dense decode+reduce,
    on both the interpret-mode kernel and the dispatch path."""
    from repro.core.compression import TopKCodec
    from repro.kernels import ops

    codec = TopKCodec(frac=0.05)
    rng = np.random.default_rng(0)
    deltas = jnp.asarray(rng.normal(size=(6, 3000)) * 0.01, jnp.float32)
    w = jnp.asarray(rng.random(6) + 0.1, jnp.float32)
    enc = codec.encode_batch(deltas)
    exp = ref.fedavg_reduce(codec.decode_batch(enc), w)
    for out in (
        codec.reduce(enc, w),                       # dispatch (ref on CPU)
        codec.reduce(enc, w, interpret=True),       # Pallas interpret body
        ops.topk_scatter_reduce(enc["idx"], enc["val"], w, 3000),
    ):
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(1, 6), k=st.integers(1, 64), seed=st.integers(0, 1000))
def test_topk_scatter_reduce_property(c, k, seed):
    n = 2048
    idx, val, w = _sparse_payload(c, k, n, seed=seed, dup=(seed % 2 == 0))
    out = topk_scatter_reduce(idx, val, w, n, interpret=True)
    exp = ref.fedavg_reduce(_dense_of(idx, val, n), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    c=st.integers(2, 8),
    scale=st.floats(0.1, 10.0),
)
def test_fedavg_reduce_weight_scale_invariance(c, scale):
    """Scaling all weights by a constant must not change the mean."""
    rng = np.random.default_rng(c)
    u = jnp.asarray(rng.normal(size=(c, 2048)), jnp.float32)
    w = jnp.asarray(rng.random(c) + 0.5, jnp.float32)
    a = fedavg_reduce(u, w, interpret=True, bn=2048)
    b = fedavg_reduce(u, w * scale, interpret=True, bn=2048)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_quantize_roundtrip_matches_oracle():
    x = _randn((8192,))
    q, s = quantize_int8(x, interpret=True, bn=4096)
    qr, sr = ref.quantize_int8(x)
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    xd = dequantize_int8(q, s, interpret=True, bn=4096)
    np.testing.assert_allclose(np.asarray(xd), np.asarray(ref.dequantize_int8(qr, sr)), rtol=1e-6)


# ---------------- int8 tail tiles ----------------
# Shapes past one 32768-wide column tile (a column tail), and 40 rows (a row
# tail past the 32-row tile): the last, partial tiles must be processed,
# not dropped (a dropped tail leaves NaN scales and garbage payloads).
TAIL_SHAPES = [(41984,), (3, 41984), (40, 33024)]


@pytest.mark.parametrize("shape", TAIL_SHAPES)
def test_quantize_tail_tiles_match_oracle(shape):
    x = _randn(shape)
    q, s = quantize_int8(x, interpret=True)
    qr, sr = ref.quantize_int8(x)
    assert q.shape == qr.shape and s.shape == sr.shape
    assert bool(jnp.all(q == qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("shape", TAIL_SHAPES)
def test_dequantize_tail_tiles_match_oracle(shape):
    q, s = ref.quantize_int8(_randn(shape))
    out = dequantize_int8(q, s, interpret=True)
    assert out.shape == shape
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.dequantize_int8(q, s)))


@pytest.mark.parametrize("n", [41984, 9216 + 2 * 32768])
def test_collective_pack_unpack_tail_tiles_match_oracle(n):
    from repro.kernels.collective_quant import collective_pack, collective_unpack

    x = _randn((n,))
    s = jnp.max(jnp.abs(_randn((n,))).reshape(-1, 256), axis=1) / 127.0
    q = collective_pack(x, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(q),
                                  np.asarray(ref.collective_pack(x, s)))
    np.testing.assert_array_equal(
        np.asarray(collective_unpack(q, s, interpret=True)),
        np.asarray(ref.collective_unpack(q, s)),
    )


@pytest.mark.parametrize("c,n", [(3, 41984), (40, 33024)])
def test_dequant_reduce_tail_tiles_match_oracle(c, n):
    q, s = ref.quantize_int8(_randn((c, n)))
    w = jnp.asarray(RNG.random(c) + 0.1, jnp.float32)
    out = dequant_reduce(q, s, w, interpret=True)
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.dequant_reduce(q, s, w)),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3))
def test_quantize_error_bound(seed, scale):
    """|x - dequant(quant(x))| <= blockwise scale (= absmax/127) per entry."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1024,)) * scale, jnp.float32)
    q, s = ref.quantize_int8(x, block=256)
    xd = ref.dequantize_int8(q, s, block=256)
    err = np.abs(np.asarray(x - xd)).reshape(-1, 256)
    bound = np.asarray(s)[:, None] * 0.5 + 1e-9
    assert (err <= bound + 1e-6).all()


# ---------------- ops-level group partial sums (normalize=False) ----------------
@pytest.mark.parametrize("interpret", [False, True])
def test_ops_reduces_normalize_false_yield_weighted_sums(interpret):
    """normalize=False turns each FL reduce into the weighted SUM — the
    group-partial form the mixed-codec engine combines under one fleet
    denominator — on both the kernel and reference dispatch paths."""
    from repro.kernels import ops

    rng = np.random.default_rng(11)
    c, n = 4, 1024
    w = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
    wsum = float(jnp.sum(w))

    u = jnp.asarray(rng.normal(size=(c, n)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.fedavg_reduce(u, w, interpret=interpret, normalize=False)),
        np.asarray(ops.fedavg_reduce(u, w, interpret=interpret)) * wsum,
        atol=1e-4, rtol=1e-5,
    )

    q, s = ref.quantize_int8(u.reshape(-1))
    q = q.reshape(c, n)
    s = s.reshape(c, n // 256)
    np.testing.assert_allclose(
        np.asarray(ops.dequant_reduce(q, s, w, interpret=interpret, normalize=False)),
        np.asarray(ops.dequant_reduce(q, s, w, interpret=interpret)) * wsum,
        atol=1e-4, rtol=1e-5,
    )

    idx = jnp.asarray(rng.integers(0, n, (c, 16)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(c, 16)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.topk_scatter_reduce(idx, val, w, n, interpret=interpret,
                                           normalize=False)),
        np.asarray(ops.topk_scatter_reduce(idx, val, w, n, interpret=interpret)) * wsum,
        atol=1e-5, rtol=1e-5,
    )
    # all-zero weights: the weighted sum is exactly zero, never NaN
    z = jnp.zeros(c)
    for out in (
        ops.fedavg_reduce(u, z, interpret=interpret, normalize=False),
        ops.topk_scatter_reduce(idx, val, z, n, interpret=interpret,
                                normalize=False),
    ):
        np.testing.assert_array_equal(np.asarray(out), 0.0)
