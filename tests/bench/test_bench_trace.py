"""bench/trace.py on a small trace recorded on a TPU v5e, with the numbers
read by hand from its events.

The trace (``data/small_trace.xplane.pb``): inside a ``bench.window`` host
span, three ``bench.reduce`` spans each run ``fedavg_reduce`` on a
(10, 300000) operand and block, each followed by a ``bench.sleep`` of 2 ms,
then a ``bench.matmul`` span runs a 2048x2048 matmul and a sum.  Its TPU
ops (start ns, duration ns), as the profiler wrote them:

    45110781  643  reduce_sum          48801861  640  reduce_sum
    45112124  323  divide_bitcast_f.   48803201  322  divide_bitcast_f.
    45112449  150  copy                48803525  150  copy
    45112599 32314 fedavg_reduce       48803675 32247 fedavg_reduce
    52136286  641  reduce_sum          55506321   13  copy-start
    52137628  322  divide_bitcast_f.   55506336 22778 copy-done
    52137950  149  copy                55529116 89951 convolution_reduce_fusion
    52138101 32536 fedavg_reduce

and the window span starts at 46108557 ns and lasts 11269159 ns.  The
device clock lags the host's by about a millisecond here (each reduce's
first op starts ~1.07 ms before the host's execute call that launched it),
so the first reduce falls before the window and is left out.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "small_trace.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(DATA)


def test_window_and_busy(summary):
    assert list(summary.devices) == ["/device:TPU:0"]
    assert summary.window_s() == pytest.approx(11_269_159 * NS, abs=1e-12)
    # group 2: 640 + 322 + (150 + 32247, touching) ; group 3: 641 + (322 +
    # 149, touching) + 32536 ; matmul: 13 + 22778 + 89951
    busy = (640 + 322 + 150 + 32_247) + (641 + 322 + 149 + 32_536) + (13 + 22_778 + 89_951)
    assert summary.mean_busy_s() == pytest.approx(busy * NS, abs=1e-12)


def test_kernels_and_ops(summary):
    assert summary.kernels() == {"fedavg_reduce": 2}
    assert summary.kernel_seconds("fedavg_reduce") == pytest.approx(
        (32_247 + 32_536) * NS, abs=1e-12)
    ops = summary.op_seconds()
    assert ops["convolution_reduce_fusion"] == pytest.approx(89_951 * NS, abs=1e-12)
    assert ops["copy-done"] == pytest.approx(22_778 * NS, abs=1e-12)
    assert ops["reduce_sum"] == pytest.approx((640 + 641) * NS, abs=1e-12)
    assert summary.collective_seconds() == 0.0


def test_idle_gaps_by_host_span(summary):
    idle = summary.idle_by_host()
    assert sum(idle.values()) == pytest.approx(
        summary.window_s() - summary.mean_busy_s(), abs=1e-12)
    # the window's first gap, 46108557..48801861, has its midpoint inside
    # the first 2 ms sleep (47003657..49879527)
    assert idle["$time sleep"] >= (48_801_861 - 46_108_557) * NS
    breakdown = summary.breakdown()
    assert breakdown["device_ops"][0][0] == "convolution_reduce_fusion"
    assert len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 10


@pytest.mark.parametrize("name,label", [
    ("%fedavg_reduce.185 = f32[1,4096]{1,0} custom-call(f32[10,4096] %x)", "fedavg_reduce"),
    ("%fusion.865 = (f32[10,50,64]{2,1,0}, f32[2]) fusion(f32[3] %a), kind=kOutput", "fusion"),
    ("%all-reduce.3 = f32[100]{0} all-reduce(f32[100]{0} %p), to_apply=%add", "all-reduce"),
    ("%copy-start.161 = (f32[2], u32[]) copy-start(f32[2] %g)", "copy-start"),
    ("%broadcast_divide_fusion = f32[8]{0} fusion(f32[8] %x)", "broadcast_divide_fusion"),
])
def test_op_label(name, label):
    assert tr.op_label(name) == label


def test_exposed_collective_on_synthetic_ops():
    """An all-reduce of 10 units, 4 of them under another op: 6 exposed."""
    names = ["all-reduce", "fusion", "while"]
    dev = tr.Device(starts=np.array([0.0, 6.0, -1.0, 20.0]),
                    ends=np.array([10.0, 14.0, 30.0, 25.0]),
                    labels=np.array([0, 1, 2, 1]),
                    kernel=np.zeros(4, bool))
    s = tr.Summary(window=(-1.0, 30.0), label_names=names, devices={"d": dev})
    assert s.collective_seconds() == pytest.approx(10.0)
    assert s.exposed_collective_seconds() == pytest.approx(6.0)
    # the while op covers the window, so the device is never idle
    assert s.busy_s("d") == pytest.approx(31.0)
    assert s.op_seconds() == {"all-reduce": 10.0, "fusion": 13.0}
