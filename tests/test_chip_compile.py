"""Every main-path Pallas kernel compiles for a TPU v5e chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached: these tests lower each kernel module (not the
``ops`` dispatchers, which see the CPU backend and take the oracles) at the
widths of a ResNet-18 round with C=8 clients, plus a tail that is not a tile
multiple, and check that the compiled program holds the Pallas kernel
(``tpu_custom_call``).  Nothing runs, so results are checked elsewhere
(``test_kernels.py`` in interpret mode, ``chip_smoke.py`` on the chip).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    collective_quant, dequant_reduce, fedavg_reduce, flash_attention,
    quantize, scatter_reduce, topk_select,
)

C = 8
N = 11_173_962                  # resnet18-cifar10 parameters
NP = -(-N // 256) * 256         # one client row of the Int8 wire
LEAF = 3 * 3 * 512 * 512        # its largest leaf
TAIL = 3 * 32768 + 9216         # a multiple of 256, not of any tile


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this installation")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I8, I32 = jnp.float32, jnp.int8, jnp.int32

CASES = {
    "fedavg_reduce": (
        fedavg_reduce.fedavg_reduce, [((C, N), F32), ((C,), F32)]),
    "fedavg_reduce_tail": (
        fedavg_reduce.fedavg_reduce, [((C, TAIL + 100), F32), ((C,), F32)]),
    # the Null round: client params of one leaf centered on the global leaf
    "fedavg_reduce_centered_leaf": (
        fedavg_reduce.fedavg_reduce,
        [((C, LEAF), F32), ((C,), F32), ((LEAF,), F32)]),
    "fedavg_reduce_centered_small_leaf": (
        fedavg_reduce.fedavg_reduce, [((C, 10), F32), ((C,), F32), ((10,), F32)]),
    "quantize_int8": (
        quantize.quantize_int8, [((C, NP), F32)]),
    "quantize_int8_leaf_tail": (
        quantize.quantize_int8, [((TAIL,), F32)]),
    "dequantize_int8": (
        quantize.dequantize_int8, [((C, NP), I8), ((C, NP // 256), F32)]),
    "dequant_reduce": (
        dequant_reduce.dequant_reduce,
        [((C, NP), I8), ((C, NP // 256), F32), ((C,), F32)]),
    "dequant_reduce_cohort_tail": (
        dequant_reduce.dequant_reduce,
        [((40, TAIL), I8), ((40, TAIL // 256), F32), ((40,), F32)]),
    "collective_pack": (
        collective_quant.collective_pack, [((LEAF,), F32), ((LEAF // 256,), F32)]),
    "collective_unpack_tail": (
        collective_quant.collective_unpack, [((TAIL,), I32), ((TAIL // 256,), F32)]),
    "topk_scatter_reduce": (
        lambda i, v, w: scatter_reduce.topk_scatter_reduce(i, v, w, LEAF),
        [((C, LEAF // 100), I32), ((C, LEAF // 100), F32), ((C,), F32)]),
    "topk_scatter_reduce_max": (
        lambda i, v, w: scatter_reduce.topk_scatter_reduce(
            i, v, w, scatter_reduce.MAX_N_PARAMS - 100),
        [((C, 1000), I32), ((C, 1000), F32), ((C,), F32)]),
    # the TopK encoder's selection: the largest leaf at 1%, alone and with
    # a leaf shorter than a line in one call
    "topk_select": (
        lambda x: topk_select.topk_select_rows((x,), (LEAF // 100,)), [((C, LEAF), F32)]),
    "topk_select_rows": (
        lambda x, y: topk_select.topk_select_rows((x, y), (LEAF // 100, 1)),
        [((C, LEAF), F32), ((C, 64), F32)]),
    # qwen3-0.6b attention: 16 query heads over 8 kv heads of width 128
    "flash_attention": (
        lambda q, k, v: flash_attention.flash_attention(q, k, v, causal=True),
        [((1, 2048, 16, 128), jnp.bfloat16), ((1, 2048, 8, 128), jnp.bfloat16),
         ((1, 2048, 8, 128), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    text = _compile_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, f"{name}: no Pallas kernel in the program"
