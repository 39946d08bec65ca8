"""bench/xspace.py on the small TPU v5e trace of ``test_bench_trace.py``,
and the program's own spans and scopes that it reads, on the CPU.

The trace (``data/small_trace.xplane.pb``) holds four device programs.
Their ``XLA Modules`` events (device clock) and the host's
``DoEnqueueProgram`` and ``CompleteCallbacks`` events with the same
``run_id`` (host clock), in ns, as the events' own offsets give them:

    run_id  module start  duration     enqueue    callbacks
        11  45110775.500  34140.000   46295816     46921736
        12  48801855.500  34068.750   50014566     50541256
        13  52136280.500  34358.750   53298235     53856055
        14  55506319.328 112749.922   56652885     57279165

module start - enqueue: -1185040.5, -1212710.5, -1161954.5, -1146565.672,
so the offset's upper bound is -1212710.5 ns; module end - callbacks:
-1776820.5, -1705331.75, -1685415.75, -1660095.75, so its lower bound is
-1660095.75 ns (-1.21 and -1.66 ms).  Runs 11-13 are the three
``fedavg_reduce`` calls, each inside its ``bench.reduce`` host span
(46112227+885040, 49886897+796230, 53148477+757380); run 14 is the
matmul.  The ``fedavg_reduce`` kernel's
op metadata names it ``jit(<lambda>)/jit(fedavg_reduce)/pallas_call:``.
"""
from __future__ import annotations

import re
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import trace as tr
from bench import xspace

DATA = Path(__file__).parent / "data" / "small_trace.xplane.pb"
NS = 1e-9
MODULES = [(45_110_775.5, 34_140.0), (48_801_855.5, 34_068.75),
           (52_136_280.5, 34_358.75), (55_506_319.328, 112_749.922)]
ENQUEUES = [46_295_816, 50_014_566, 53_298_235, 56_652_885]
CALLBACKS = [46_921_736, 50_541_256, 53_856_055, 57_279_165]
REDUCE_SPANS = [(46_112_227, 885_040), (49_886_897, 796_230), (53_148_477, 757_380)]


@pytest.fixture(scope="module")
def fixture():
    return xspace.reduce(DATA)


def test_fedavg_reduce_tf_op():
    space = xspace.read_space(DATA)
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    ops = {md.name.split(" ", 1)[0]: xspace.stats(plane, md.stats)
           for md in plane.event_metadata.values()}
    kernel = ops["%fedavg_reduce.1"]
    assert kernel["tf_op"] == "jit(<lambda>)/jit(fedavg_reduce)/pallas_call:"
    assert kernel["hlo_category"] == "custom-call"
    assert ops["%convolution_reduce_fusion"]["flops"] == 17_184_063_488
    assert xspace.scope_of(kernel["tf_op"]) == xspace.UNSCOPED


def test_run_pairs_and_offset_bounds(fixture):
    upper = min(s - e for (s, _), e in zip(MODULES, ENQUEUES))
    lower = max(s + d - c for (s, d), c in zip(MODULES, CALLBACKS))
    assert (upper, lower) == pytest.approx((-1_212_710.5, -1_660_095.75), abs=1e-3)
    assert fixture.clock.pairs == 4
    assert fixture.clock.upper == pytest.approx(upper * NS, abs=1e-12)
    assert fixture.clock.lower == pytest.approx(lower * NS, abs=1e-12)
    assert fixture.enqueues == pytest.approx([e * NS for e in ENQUEUES], abs=1e-12)


def test_alignment_puts_each_reduce_in_its_own_span(fixture):
    # no device module starts before the host enqueued it
    for (start, _), enq in zip(fixture.modules, fixture.enqueues):
        assert start >= enq - 1e-12
    spans = [(s, e) for s, e, n in tr.summarize(DATA).host if n == "bench.reduce"]
    np.testing.assert_allclose(spans, [(s * NS, (s + d) * NS) for s, d in REDUCE_SPANS],
                               rtol=0, atol=1e-12)
    ops = fixture.devices["/device:TPU:0"]
    kernel = fixture.labels.index("fedavg_reduce")
    for (m0, m1), (s0, s1) in zip(fixture.modules[:3], spans):
        inside = (ops.starts >= m0 - 1e-12) & (ops.ends <= m1 + 1e-12)
        assert int(np.sum(inside & (ops.labels == kernel))) == 1
        assert inside.sum() == 4     # reduce_sum, divide, copy, the kernel
        assert np.all(ops.starts[inside] >= s0) and np.all(ops.ends[inside] <= s1)
    # unaligned, the first reduce started before the window and was cut
    assert tr.summarize(DATA).kernels() == {"fedavg_reduce": 2}
    assert int(np.sum(ops.labels == kernel)) == 3


def test_fixture_has_no_scopes_and_its_readers_read_nothing(fixture, tmp_path, monkeypatch):
    """A program without ``fl.*`` scopes or spans (the trace predates them)
    gives every new reader nothing to read, and none raises."""
    assert set(fixture.scope_seconds()) == {xspace.UNSCOPED}
    assert fixture.spans == []
    shutil.copy(DATA, tmp_path / "t.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    ctx = SimpleNamespace(trace=object(), window=SimpleNamespace(rounds=3))
    for name in ("local_fwd_ms", "local_bwd_ms", "local_update_ms", "encode_ms",
                 "fit_batch_ms", "fit_sync_ms", "aggregate_fit_ms"):
        path = harness.BENCH / "metrics" / f"{name}.py"
        spec = __import__("importlib.util").util.spec_from_file_location(name, path)
        mod = __import__("importlib.util").util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(ctx) is None
    assert xspace.of(SimpleNamespace(trace=None)) is None


def test_report_line(fixture):
    line = fixture.line(3)
    assert line.startswith("[xspace] clock offset -1.2127 ms (upper bound -1.2127, lower "
                           "bound -1.6601 ms, 4 runs) | ")
    crossed = xspace.XTrace(window=None, clock=xspace.Clock(pairs=2, upper=-1e-3, lower=-0.8e-3),
                            labels=[], scopes=[xspace.UNSCOPED])
    assert crossed.line(1).startswith(
        "[xspace] clock offset -1.0000 ms (upper bound -1.0000, lower bound -0.8000 ms, "
        "2 runs; the bounds cross by 0.2000 ms)")
    assert "unscoped share 100.000%" in line and "fedavg_reduce" in line


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(round)/vmap()/while/body/closed_call/jvp(fl.local.loss)/dot_general:",
     "jvp(fl.local.loss)"),
    ("jit(round)/while/body/transpose(jvp(fl.local.loss))/conv_general_dilated:",
     "transpose(jvp(fl.local.loss))"),
    ("jit(round)/while/body/fl.local.update/sub:", "fl.local.update"),
    ("jit(round)/fl.reduce/fl.encode/sort:", "fl.encode"),
    ("jit(round)/fl.reduce/pallas_call:", "fl.reduce"),
    ("jit(fit_steps)/fl.server_update", "fl.server_update"),
    ("jit(<lambda>)/jit(fedavg_reduce)/pallas_call:", xspace.UNSCOPED),
    ("jit(f)/self.fl/mul:", xspace.UNSCOPED),
    ("", xspace.UNSCOPED),
])
def test_scope_of(tf_op, scope):
    assert xspace.scope_of(tf_op) == scope


def test_idle_by_innermost_program_span():
    """Idle time credited, instant by instant, to the innermost fl span."""
    ops = xspace.Ops(starts=np.array([1.0, 5.0]), ends=np.array([2.0, 6.0]),
                     labels=np.zeros(2, np.int64), scopes=np.zeros(2, np.int64),
                     control=np.zeros(2, bool))
    x = xspace.XTrace(window=(0.0, 10.0), clock=xspace.Clock(), labels=["op"],
                      scopes=[xspace.UNSCOPED], devices={"d": ops},
                      spans=[(0.5, 9.0, "fl.round"), (2.0, 4.0, "fl.fit"),
                             (2.5, 3.5, "fl.fit.batch")])
    # idle: 0-0.5 no span; 0.5-1 round; 2-2.5 fit; 2.5-3.5 batch; 3.5-4
    # fit; 4-5 and 6-9 round; 9-10 no span
    assert x.idle_by_span() == pytest.approx(
        {xspace.NO_SPAN: 1.5, "fl.round": 4.5, "fl.fit": 1.0, "fl.fit.batch": 1.0})
    assert x.busy_s() == pytest.approx(2.0)
    assert x.span_seconds() == pytest.approx(
        {"fl.round": [8.5], "fl.fit": [2.0], "fl.fit.batch": [1.0]})


# ------------------------------------------------ the program's own spans
def _head():
    from repro.configs.base import get_config
    from repro.models import build_model

    return build_model(get_config("mobilenet-head-office31").reduced())


def test_server_run_spans(tmp_path):
    """A 2-round ``Server.run`` of 2 ``JaxClient``s under the profiler: the
    program's host spans, their counts and their nesting."""
    from repro.core import FedAvg, JaxClient, Server
    from repro.data.federated import ClientDataset

    model = _head()
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    dim = jax.tree.leaves(params)[0].shape[0]
    clients = [JaxClient(client_id=c, loss_fn=model.loss_fn, batch_size=8,
                         dataset=ClientDataset(
                             client_id=c, x=rng.normal(size=(24, dim)).astype(np.float32),
                             y=rng.integers(0, 4, 24).astype(np.int32)))
               for c in range(2)]
    server = Server(strategy=FedAvg(local_epochs=1, local_lr=0.1), clients=clients,
                    eval_every=1 << 62)
    server.logger.quiet = True
    server.run(params, 1)            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        g, _ = server.run(params, 2)
        jax.block_until_ready(g)
    x = xspace.reduce(tr.find_xplane(tmp_path))
    spans = x.span_seconds()
    counts = {n: len(v) for n, v in spans.items()}
    assert counts == {"fl.round": 2, "fl.configure_fit": 2, "fl.fit": 4,
                      "fl.fit.batch": 4, "fl.fit.step": 4, "fl.fit.sync": 4,
                      "fl.policy": 2, "fl.aggregate_fit": 2}

    def within(name, parent):
        outer = [(s, e) for s, e, n in x.spans if n == parent]
        for s, e, n in x.spans:
            if n == name:
                assert sum(a <= s and e <= b for a, b in outer) == 1, (name, parent)

    for child in ("fl.configure_fit", "fl.fit", "fl.policy", "fl.aggregate_fit"):
        within(child, "fl.round")
    for child in ("fl.fit.batch", "fl.fit.step", "fl.fit.sync"):
        within(child, "fl.fit")


# ------------------------------------------------ the program's own scopes
@pytest.mark.parametrize("mode", ["parallel", "sequential", "mesh"])
def test_round_step_scopes(mode):
    """A lowered ``make_round_step`` round carries every device scope in its
    HLO op metadata, on each execution mode."""
    from repro.core import FedAdam, RoundSpec, SegmentMap, TopKCodec, make_round_step
    from repro.launch.mesh import make_local_mesh
    from repro.optim import sgd
    from repro.utils.pytree import tree_size

    model = _head()
    params = model.init(jax.random.key(0))
    n, steps, batch = 2, 2, 4
    dim = jax.tree.leaves(params)[0].shape[0]
    codec = TopKCodec(frac=0.05, segments=SegmentMap.from_tree(params))
    spec = RoundSpec(max_steps=steps, codec=codec,
                     execution_mode="sequential" if mode == "sequential" else "parallel")
    strategy = FedAdam()     # FedAvg's server update is the identity: no ops
    mesh = make_local_mesh(data=n) if mode == "mesh" else None
    step = make_round_step(model.loss_fn, sgd(0.1), strategy, spec, mesh=mesh)
    batches = {"x": jnp.zeros((n, steps, batch, dim)),
               "y": jnp.zeros((n, steps, batch), jnp.int32)}
    args = (params, strategy.init_state(params),
            codec.init_client_state(n, tree_size(params)), batches,
            jnp.ones(n), jnp.full((n,), steps, jnp.int32), jnp.int32(1))
    hlo = jax.jit(step).lower(*args).compile().as_text()
    scopes = {xspace.scope_of(name) for name in re.findall(r'op_name="([^"]*)"', hlo)}
    assert {"jvp(fl.local.loss)", "transpose(jvp(fl.local.loss))", "fl.local.update",
            "fl.encode", "fl.reduce", "fl.server_update"} <= scopes


def test_compile_cache_keys_on_scopes_not_on_the_checkout(monkeypatch, tmp_path):
    """The compile cache's key covers op metadata, so an executable compiled
    before a scope changed is not loaded with stale names; source files are
    named relative to the checkout, so another checkout still hits."""
    from repro.utils import compile_cache

    names = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        source = compile_cache.CHECKOUT / "src" / "repro" / "core" / "rounds.py"
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(pattern, "", str(source)) == "src/repro/core/rounds.py"
        assert re.sub(pattern, "", "/elsewhere/src/repro/core/rounds.py").startswith("/elsewhere")
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
