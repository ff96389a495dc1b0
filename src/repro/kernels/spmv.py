"""ELL-format semiring SpMV (PageRank / background model) and the
host-side CSR→ELL pack shared by the SpMV and SpMM kernels.

CSR's per-row ragged nnz is hostile to the MXU; the TPU adaptation packs
rows to ELL (fixed ``k_max`` nnz per row, zero-padded — D4M incidence
matrices are near-regular: one nnz per header field).  The gather
``x[cols]`` is realized as a one-hot matmul per nnz-slot, so the whole
kernel is dense systolic work:

    y[r] ⊕= Σ_k vals[r,k] ⊗ (onehot(cols[r,k]) @ x_tile)

:func:`spmv_ell` is the ``b = 1`` column of
:func:`~repro.kernels.spmm.spmm_ell`: ``x`` rides as an ``(n_cols, 1)``
block, so every operand keeps the 2-D layout Mosaic tiles.  (Mosaic
tiles a 1-D f32 array in 1024-element units, so a 1-D block of
``block_rows`` = 256 does not compile.)

``interpret`` auto-selects by backend: compiled on TPU, interpreter
everywhere else (the kernel targets Mosaic; CPU/GPU runs validate
semantics, TPU runs take the MXU path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .spmm import spmm_ell


class EllOverflowError(ValueError):
    """A CSR row holds more entries than the ELL pack's ``k_max``.

    Truncating would silently drop nnz (wrong query answers), so the
    pack refuses by default.  Raise ``k_max`` (the device lowering uses
    ``max(nnz per row)``), route the payload through the CSR/COO path
    instead, or pass ``on_overflow='truncate'`` to accept the loss
    explicitly (top-k style sketches only).
    """

    def __init__(self, n_over: int, worst: int, k_max: int):
        self.n_over = n_over
        self.worst = worst
        self.k_max = k_max
        super().__init__(
            f"{n_over} row(s) exceed k_max={k_max} (worst row has "
            f"{worst} nnz): truncation would silently drop entries — "
            f"raise k_max, use the CSR/COO path, or pass "
            f"on_overflow='truncate' to accept the loss")


def csr_to_ell(row_ptr, cols, vals, n_rows: int, k_max: int,
               on_overflow: str = "raise"):
    """Host-side CSR→ELL pack (pad to k_max nnz per row) — fully
    vectorized scatter, no Python row loop.

    Rows with more than ``k_max`` entries cannot be represented: the
    default ``on_overflow='raise'`` surfaces :class:`EllOverflowError`
    instead of silently truncating; ``'truncate'`` keeps the first
    ``k_max`` entries per row (explicit lossy opt-in).
    """
    if on_overflow not in ("raise", "truncate"):
        raise ValueError(f"on_overflow must be 'raise' or 'truncate', "
                         f"got {on_overflow!r}")
    row_ptr = np.asarray(row_ptr, dtype=np.int64)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    counts = np.diff(row_ptr)
    if on_overflow == "raise" and counts.size and counts.max() > k_max:
        over = counts > k_max
        raise EllOverflowError(int(over.sum()), int(counts.max()), k_max)
    ecols = np.full((n_rows, k_max), -1, np.int32)
    evals = np.zeros((n_rows, k_max), np.float32)
    keep = np.minimum(counts, k_max)
    total = int(keep.sum())
    if total:
        rows = np.repeat(np.arange(n_rows), keep)
        offs = np.arange(total) - np.repeat(np.cumsum(keep) - keep, keep)
        src = np.repeat(row_ptr[:-1], keep) + offs
        ecols[rows, offs] = cols[src]
        evals[rows, offs] = vals[src]
    return jnp.asarray(ecols), jnp.asarray(evals)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "ring", "interpret"))
def spmv_ell(ecols: jax.Array, evals: jax.Array, x: jax.Array,
             block_rows: int = 256, block_cols: int = 1024,
             ring: str = "plus_times",
             interpret: Optional[bool] = None) -> jax.Array:
    """y = A ⊕.⊗ x with A in ELL (n_rows, k_max).

    ``interpret=None`` (default) compiles on TPU and interprets on other
    backends; pass an explicit bool to force either mode.
    """
    return spmm_ell(ecols, evals, x[:, None], block_rows=block_rows,
                    block_cols=block_cols, ring=ring,
                    interpret=interpret)[:, 0]
