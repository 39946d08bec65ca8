"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel has: <name>.py (pl.pallas_call + BlockSpec), an entry in ops.py
(backend-dispatching jit wrapper) and an oracle in ref.py (pure jnp).  On a
TPU the dispatchers run the kernels; on the CPU the tests check the kernel
bodies against the oracles with interpret=True, and
tests/test_chip_compile.py compiles them for a described TPU v5e.

Submodules load lazily (PEP 562): importing ``repro.kernels`` must not pull
in jax — fedlint's import-scan gate (and pytest collection on machines
without any accelerator backend) depends on module import staying inert.
"""
from __future__ import annotations

import importlib

_SUBMODULES = (
    "collective_quant", "decode_attention", "dequant_reduce",
    "fedavg_reduce", "flash_attention", "ops", "quantize", "ref",
    "scatter_reduce", "selective_scan", "topk_select",
)


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
