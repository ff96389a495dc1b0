"""Device-side sparse payloads for associative arrays.

Two representations:

* :class:`COO` — sorted coordinate triples. The construction format; all
  Assoc payloads normalize to row-major sorted, coalesced COO.
* :class:`CSR` — compressed rows, the layout consumed by the Pallas
  segmented-reduction kernels (see ``repro.kernels``).

Both are registered pytrees so they pass through ``jax.jit`` /
``shard_map`` untouched.  nnz is static (a Python int) — JAX requires
static shapes — so in-jit ops that could shrink nnz (coalesce) keep the
buffer size and park dead entries at ``row == nrows`` (sorted past the
end, value = semiring zero).  Host-side construction (numpy) produces
exact-size buffers.

The degree computation / SpMV here are the numeric heart of the paper:
stage 6 builds ``TedgeDeg`` with exactly :func:`row_degree` /
:func:`col_degree`, and every analytic (power-law background, PageRank)
is a semiring SpMV over the incidence/adjacency payload.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..device import count_h2d
from . import semiring as sr

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class COO:
    """Sorted, coalesced coordinate-format sparse matrix."""

    rows: Array            # int32[nnz]   (row-major sorted)
    cols: Array            # int32[nnz]
    vals: Array            # dtype[nnz]
    shape: Tuple[int, int]  # static

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.rows, self.cols, self.vals), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        rows, cols, vals = children
        return cls(rows, cols, vals, shape)

    # -- basics ------------------------------------------------------------
    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def dtype(self):
        return self.vals.dtype

    def astype(self, dtype) -> "COO":
        return COO(self.rows, self.cols, self.vals.astype(dtype), self.shape)

    @classmethod
    def from_numpy(cls, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                   shape: Tuple[int, int]) -> "COO":
        """Build from host triples: sort + coalesce (exact nnz) on host."""
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        if rows.size:
            # coalesce duplicates by summation (plus_times construction).
            key = rows * shape[1] + cols
            uniq, inv = np.unique(key, return_inverse=True)
            out = np.zeros(uniq.shape[0], dtype=vals.dtype)
            np.add.at(out, inv, vals)
            rows = (uniq // shape[1]).astype(np.int32)
            cols = (uniq % shape[1]).astype(np.int32)
            vals = out
        return cls(jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
                   jnp.asarray(vals), shape)

    def to_dense(self) -> Array:
        out = jnp.zeros(self.shape, self.vals.dtype)
        return out.at[self.rows, self.cols].add(self.vals)

    def to_scipy(self):
        import scipy.sparse as sp
        return sp.coo_matrix(
            (np.asarray(self.vals), (np.asarray(self.rows), np.asarray(self.cols))),
            shape=self.shape).tocsr()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSR:
    """Compressed-sparse-row payload (kernel-facing layout)."""

    row_ptr: Array          # int32[nrows+1]
    cols: Array             # int32[nnz]
    vals: Array             # dtype[nnz]
    shape: Tuple[int, int]

    def tree_flatten(self):
        return (self.row_ptr, self.cols, self.vals), self.shape

    @classmethod
    def tree_unflatten(cls, shape, children):
        row_ptr, cols, vals = children
        return cls(row_ptr, cols, vals, shape)

    @property
    def nnz(self) -> int:
        return int(self.cols.shape[0])


def coo_to_csr(m: COO) -> CSR:
    counts = jax.ops.segment_sum(
        jnp.ones_like(m.rows), m.rows, num_segments=m.shape[0])
    row_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    return CSR(row_ptr, m.cols, m.vals, m.shape)


def csr_to_coo(m: CSR) -> COO:
    nrows = m.shape[0]
    rows = jnp.searchsorted(
        m.row_ptr, jnp.arange(m.nnz, dtype=jnp.int32), side="right"
    ).astype(jnp.int32) - 1
    del nrows
    return COO(rows, m.cols, m.vals, m.shape)


# ---------------------------------------------------------------------------
# Core semiring contractions (jit-safe; used by the sharded analytics).
# ---------------------------------------------------------------------------

def spmv(m: COO, x: Array, ring: "sr.Semiring | str" = sr.PLUS_TIMES) -> Array:
    """y[i] = ⊕_j m[i,j] ⊗ x[j]  — generic semiring mat-vec."""
    ring = sr.get(ring)
    prods = ring.mul(m.vals, x[m.cols])
    return ring.reduce(prods, m.rows, m.shape[0])


def spmv_t(m: COO, x: Array, ring: "sr.Semiring | str" = sr.PLUS_TIMES) -> Array:
    """y[j] = ⊕_i m[i,j] ⊗ x[i]  — transpose mat-vec without re-sorting."""
    ring = sr.get(ring)
    prods = ring.mul(m.vals, x[m.rows])
    return ring.reduce(prods, m.cols, m.shape[1])


def spmm(m: COO, x: Array, ring: "sr.Semiring | str" = sr.PLUS_TIMES) -> Array:
    """(nr, nc) sparse @ (nc, k) dense → (nr, k) dense, generic semiring."""
    ring = sr.get(ring)
    prods = ring.mul(m.vals[:, None], x[m.cols])        # (nnz, k)
    return ring.reduce(prods, m.rows, m.shape[0])


def row_degree(m: COO, weighted: bool = False) -> Array:
    """Out-degree per row — the ``sum(E, 2)`` of the paper's stage 6."""
    w = m.vals if weighted else jnp.ones_like(m.vals)
    return jax.ops.segment_sum(w, m.rows, num_segments=m.shape[0])


def col_degree(m: COO, weighted: bool = False) -> Array:
    """In-degree per column — the ``sum(E, 1)`` building ``TedgeDeg``."""
    w = m.vals if weighted else jnp.ones_like(m.vals)
    return jax.ops.segment_sum(w, m.cols, num_segments=m.shape[1])


def transpose(m: COO) -> COO:
    order = jnp.lexsort((m.rows, m.cols))
    return COO(m.cols[order], m.rows[order], m.vals[order],
               (m.shape[1], m.shape[0]))


@partial(jax.jit, static_argnames=("num_rows",))
def _coalesce_fixed(rows: Array, cols: Array, vals: Array, num_rows: int):
    """In-jit coalesce: keeps nnz, sums duplicates, parks dead slots at end.

    Dead slots get ``row == num_rows`` so a subsequent segment reduce with
    ``num_segments == num_rows`` drops them.
    """
    ncols_key = jnp.max(cols) + 1
    key = rows.astype(jnp.int64) * ncols_key + cols
    order = jnp.argsort(key)
    key, vals = key[order], vals[order]
    head = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    # Position of each run head; duplicates accumulate into the head slot.
    seg = jnp.cumsum(head) - 1
    summed = jax.ops.segment_sum(vals, seg, num_segments=key.shape[0])
    n_unique = jnp.sum(head)
    idx = jnp.arange(key.shape[0])
    live = idx < n_unique
    head_pos = jnp.nonzero(head, size=key.shape[0], fill_value=key.shape[0] - 1)[0]
    out_key = jnp.where(live, key[head_pos], -1)
    out_val = jnp.where(live, summed[idx], 0)
    out_rows = jnp.where(live, (out_key // ncols_key).astype(jnp.int32), num_rows)
    out_cols = jnp.where(live, (out_key % ncols_key).astype(jnp.int32), 0)
    return out_rows, out_cols, out_val


def coalesce(m: COO) -> COO:
    """jit-safe coalesce (fixed nnz, dead entries parked at row == nrows)."""
    r, c, v = _coalesce_fixed(m.rows, m.cols, m.vals, m.shape[0])
    return COO(r, c, v, m.shape)


# ---------------------------------------------------------------------------
# Host-side exact algebra (scipy bridge) — used by Assoc, mirrors how D4M
# delegates to MATLAB's sparse engine.  Device analytics never touch this.
# ---------------------------------------------------------------------------

def scipy_from_triples(rows, cols, vals, shape):
    import scipy.sparse as sp
    return sp.csr_matrix(
        (np.asarray(vals, dtype=np.float64),
         (np.asarray(rows, dtype=np.int64), np.asarray(cols, dtype=np.int64))),
        shape=shape)


def coo_from_scipy(m) -> COO:
    m = m.tocoo()
    order = np.lexsort((m.col, m.row))
    coo = COO(jnp.asarray(m.row[order], jnp.int32),
              jnp.asarray(m.col[order], jnp.int32),
              jnp.asarray(m.data[order]), m.shape)
    count_h2d("coo", coo.rows, coo.cols, coo.vals)
    return coo
