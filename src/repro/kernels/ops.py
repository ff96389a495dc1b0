"""jit'd public wrappers for the Pallas kernels.

Every kernel's ``interpret`` follows the backend: compiled (Mosaic) on
a TPU, the Pallas interpreter elsewhere, where it validates the kernel
bodies while the pure-JAX fallbacks serve the compiled path.
"""
from __future__ import annotations

import jax

from .flash_attention import flash_attention
from .rglru import rglru_scan
from .segsum import segsum
from .spmm import spgemm_sel, spmm_ell
from .spmv import EllOverflowError, csr_to_ell, spmv_ell
from .wkv6 import wkv6


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    return not on_tpu()


__all__ = [
    "segsum", "spmv_ell", "spmm_ell", "spgemm_sel", "csr_to_ell",
    "EllOverflowError", "flash_attention", "rglru_scan", "wkv6", "on_tpu",
    "default_interpret",
]
