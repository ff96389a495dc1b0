"""Pallas TPU kernels: ELL-format semiring SpMM and masked column-select
SpGEMM (the batched hypersparse analytics layer).

``spmv_ell`` (repro.kernels.spmv) answers ONE query per launch; a gateway
with eight concurrent top-K readers pays eight Python dispatches and
re-streams the sparse block from HBM each time.  Following the real-time
GraphBLAS deployment work (arXiv:2309.02464), the batched layer instead
multiplies one sparse Tedge block against a dense *multi-vector* in a
single launch:

* :func:`spmm_ell` — ``Y (n, b) = A ⊕.⊗ X (n_cols, b)``: the ELL block
  streams from HBM **once** and every one-hot gather matmul amortizes
  over all ``b`` query vectors — per-query cost approaches pure HBM
  bandwidth instead of per-launch dispatch;
* :func:`spgemm_sel` — ``Y (n, b) = A ⊕.⊗ onehot(sel)``: a *masked
  SpGEMM* that selects a batch of columns directly from the column-id
  vector ``sel`` — the one-hot mask matrix is never materialized
  host-side (the kernel compares ``cols[r, k] == sel[j]`` in VMEM).

Both support the ``plus_times`` and ``max_times`` semirings with the
same conventions as ``spmv_ell``: the max_times accumulator starts at
-inf (a 0 floor would clamp negative products), padding slots
(``col == -1``) are masked, and rows with no entries resolve to 0 — the
sparse no-entry value.  ``interpret=None`` auto-selects by backend:
compiled on TPU, interpreter elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmm_ell_kernel(cols_ref, vals_ref, x_ref, out_ref, *,
                     block_cols: int, ring: str):
    ct = pl.program_id(1)

    @pl.when(ct == 0)
    def _init():
        if ring == "plus_times":
            out_ref[...] = jnp.zeros_like(out_ref)
        else:                    # max_times identity is -inf, not 0
            out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    cols = cols_ref[...]                         # (BR, Kmax) int32
    vals = vals_ref[...].astype(jnp.float32)     # (BR, Kmax)
    x = x_ref[...].astype(jnp.float32)           # (block_cols, B)
    base = ct * block_cols
    local = cols - base
    br, kmax = cols.shape
    acc = out_ref[...]                           # (BR, B)
    iota = jax.lax.broadcasted_iota(jnp.int32, (br, block_cols), 1)
    for k in range(kmax):            # Kmax is small and static — unrolled
        onehot = (iota == local[:, k][:, None]).astype(jnp.float32)
        # the gather matmul is shared by all B columns of X — this is
        # where batching beats the SpMV loop: one (BR, bc) @ (bc, B)
        # instead of B separate (bc, 1) products.  HIGHEST keeps x in
        # f32 on the MXU (the default contracts in one bf16 pass, which
        # rounds x to 8 mantissa bits; the one-hot side is exact).
        gathered = jnp.dot(onehot, x, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        if ring == "plus_times":
            acc = acc + vals[:, k][:, None] * gathered
        else:                        # max_times
            # padding cols are -1, so local < 0 on every tile — the
            # mask excludes both padding and out-of-tile slots
            hit = (local[:, k] >= 0) & (local[:, k] < block_cols)
            acc = jnp.where(hit[:, None],
                            jnp.maximum(acc, vals[:, k][:, None] * gathered),
                            acc)
    if ring != "plus_times":
        # last col tile: rows with no entries anywhere stay at the
        # -inf identity — resolve them to 0 (sparse no-entry value)
        is_last = ct == pl.num_programs(1) - 1
        acc = jnp.where(is_last & jnp.isneginf(acc), 0.0, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "ring", "interpret"))
def spmm_ell(ecols: jax.Array, evals: jax.Array, x: jax.Array,
             block_rows: int = 256, block_cols: int = 1024,
             ring: str = "plus_times",
             interpret: Optional[bool] = None) -> jax.Array:
    """``Y = A ⊕.⊗ X`` with A in ELL (n_rows, k_max), X dense (n_cols, b).

    One launch answers ``b`` queries: grid over (row blocks, col tiles),
    col-tile dimension sequential so the (block_rows, b) VMEM accumulator
    is race-free.  ``b == 1`` degenerates to :func:`~repro.kernels.spmv.
    spmv_ell` (the SpMV loop's unit).  ``interpret=None`` compiles on TPU
    and interprets elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if x.ndim != 2:
        raise ValueError(f"X must be (n_cols, b), got shape {x.shape}")
    n_rows, _ = ecols.shape
    n_cols, b = x.shape
    rpad = (-n_rows) % block_rows
    cpad = (-n_cols) % block_cols
    if rpad:
        ecols = jnp.pad(ecols, ((0, rpad), (0, 0)), constant_values=-1)
        evals = jnp.pad(evals, ((0, rpad), (0, 0)))
    if cpad:
        x = jnp.pad(x, ((0, cpad), (0, 0)))
    grid = ((n_rows + rpad) // block_rows, (n_cols + cpad) // block_cols)
    out = pl.pallas_call(
        functools.partial(_spmm_ell_kernel, block_cols=block_cols,
                          ring=ring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, ecols.shape[1]), lambda r, c: (r, 0)),
            pl.BlockSpec((block_rows, evals.shape[1]), lambda r, c: (r, 0)),
            pl.BlockSpec((block_cols, b), lambda r, c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, b), lambda r, c: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows + rpad, b), jnp.float32),
        interpret=interpret,
    )(ecols, evals, x)
    return out[:n_rows]


def _spgemm_sel_kernel(cols_ref, vals_ref, sel_ref, out_ref, *, ring: str):
    cols = cols_ref[...]                         # (BR, Kmax) int32
    vals = vals_ref[...].astype(jnp.float32)     # (BR, Kmax)
    sel = sel_ref[...]                           # (B,) int32
    br, kmax = cols.shape
    if ring == "plus_times":
        acc = jnp.zeros((br, sel.shape[0]), jnp.float32)
    else:
        acc = jnp.full((br, sel.shape[0]), -jnp.inf, jnp.float32)
    for k in range(kmax):
        # the mask IS the one-hot column of the selection matrix —
        # built by comparison in VMEM, never materialized host-side
        hit = (cols[:, k][:, None] == sel[None, :]) & \
              (cols[:, k][:, None] >= 0)         # (BR, B)
        if ring == "plus_times":
            acc = acc + jnp.where(hit, vals[:, k][:, None], 0.0)
        else:
            acc = jnp.where(hit, jnp.maximum(acc, vals[:, k][:, None]),
                            acc)
    if ring != "plus_times":
        acc = jnp.where(jnp.isneginf(acc), 0.0, acc)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "ring",
                                             "interpret"))
def spgemm_sel(ecols: jax.Array, evals: jax.Array, sel: jax.Array,
               block_rows: int = 256, ring: str = "plus_times",
               interpret: Optional[bool] = None) -> jax.Array:
    """``Y[r, j] = A[r, sel[j]]`` under the semiring — the masked SpGEMM
    answering a batch of column queries in one launch.

    ``sel`` is the (b,) vector of selected column indices; entries of A
    in unselected columns are skipped by the mask, so the launch cost is
    O(nnz · b) comparisons over one HBM stream of the block, not b
    scans.  Matches :func:`spmm_ell` against the dense one-hot X under
    plus_times exactly; under max_times the mask keeps GraphBLAS sparse
    semantics — only *stored* hits reduce, so a dense zero never clamps
    a negative maximum the way the one-hot product's zeros would.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_rows, _ = ecols.shape
    b = sel.shape[0]
    rpad = (-n_rows) % block_rows
    if rpad:
        ecols = jnp.pad(ecols, ((0, rpad), (0, 0)), constant_values=-1)
        evals = jnp.pad(evals, ((0, rpad), (0, 0)))
    grid = ((n_rows + rpad) // block_rows,)
    out = pl.pallas_call(
        functools.partial(_spgemm_sel_kernel, ring=ring),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, ecols.shape[1]), lambda r: (r, 0)),
            pl.BlockSpec((block_rows, evals.shape[1]), lambda r: (r, 0)),
            pl.BlockSpec((b,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, b), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows + rpad, b), jnp.float32),
        interpret=interpret,
    )(ecols, evals, sel.astype(jnp.int32))
    return out[:n_rows]
