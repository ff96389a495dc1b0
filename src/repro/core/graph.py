"""Graph construction and device-side graph algebra.

The incidence matrix ``E`` (packets × field|value columns) produced by
the D4M schema directly encodes the network graph: selecting the
``ip.src|*`` block and the ``ip.dst|*`` block and correlating them
(``E_src' * E_dst``) yields the directed source→destination adjacency
matrix (paper §IV-E/F, and Fig. 2's "find 1.1.1.1's connections").

Host-side functions operate on :class:`Assoc` (exact, string-keyed);
device-side functions operate on :class:`repro.core.sparse.COO` under
``jit``/``shard_map`` — these are the hot loops the Pallas kernels
accelerate on TPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from .assoc import Assoc, StartsWith
from . import sparse as S
from ..obs.metrics import REGISTRY

_BUILDS = REGISTRY.counter(
    "repro_adjacency_builds_total",
    "Square adjacencies built from a src band and a dst band "
    "(adjacency_bands), by how the bands' rows were aligned",
    labels=("rows",))
# the family holds children weakly, so pin them
_BUILT = {rows: _BUILDS.labels(rows=rows)
          for rows in ("shared", "intersected")}


# ---------------------------------------------------------------------------
# Host-side (Assoc) graph construction — mirrors the paper's D4M listings.
# ---------------------------------------------------------------------------

def adjacency(E: Assoc, src_field: str = "ip.src", dst_field: str = "ip.dst",
              sep: str = "|") -> Assoc:
    """Directed adjacency  A[src, dst] = #packets  from the incidence matrix."""
    # columns are field|value ⇒ select column blocks:
    Esrc = E[:, StartsWith(f"{src_field}{sep}")]
    Edst = E[:, StartsWith(f"{dst_field}{sep}")]
    A = Esrc.T * Edst  # (src values) × (dst values), packet-count weighted
    # strip the 'field|' prefixes so keys are bare IPs
    r, c, v = A.triples()
    strip = len(src_field) + len(sep)
    stripd = len(dst_field) + len(sep)
    return Assoc(np.asarray([k[strip:] for k in r], dtype=str),
                 np.asarray([k[stripd:] for k in c], dtype=str), v)


def square(A: Assoc) -> Assoc:
    """Promote to a square array over the union of row/col keys (needed
    before spectral/PageRank work on a directed adjacency)."""
    nodes = np.union1d(A.row, A.col)
    sm = A._numeric_sm_promoted(nodes, nodes)
    return Assoc._from_parts(nodes, nodes, None, sm)


def _field_block(E: Assoc, prefix: str):
    """(column keys, payload) of the columns of ``E`` under ``prefix``."""
    ci = StartsWith(prefix).mask(E.col)
    if ci.all():
        return E.col, E.sm
    return E.col[ci], E.sm[:, ci]


def adjacency_bands(Esrc: Assoc, Edst: Assoc, src_field: str = "ip.src",
                    dst_field: str = "ip.dst", sep: str = "|") -> Assoc:
    """``square(adjacency(Esrc + Edst))`` built on integer indices.

    ``Esrc`` holds the ``src_field|*`` columns of an incidence array and
    ``Edst`` its ``dst_field|*`` columns (any other column is ignored).
    The product runs on the payloads' indices; keys are touched once per
    distinct vertex, never per entry.  Same node keys, values and shape
    as the D4M listing, which stays the reference.
    """
    skeys, a = _field_block(Esrc, f"{src_field}{sep}")
    dkeys, b = _field_block(Edst, f"{dst_field}{sep}")
    if Esrc.val is not None or Edst.val is not None:
        # the sum of the bands is categorical, which ``*`` views as logical
        a, b = a.copy(), b.copy()
        a.data = np.ones_like(a.data)
        b.data = np.ones_like(b.data)
    if np.array_equal(Esrc.row, Edst.row):
        _BUILT["shared"].inc()
    else:
        _, ia, ib = np.intersect1d(Esrc.row, Edst.row, assume_unique=True,
                                   return_indices=True)
        a, b = a[ia], b[ib]
        _BUILT["intersected"].inc()
    P = (a.T.tocsr() @ b).tocsr()   # src columns x dst columns
    P.eliminate_zeros()
    # only vertices holding an entry are nodes
    used_r = np.flatnonzero(np.diff(P.indptr))
    used_c = np.flatnonzero(np.bincount(P.indices, minlength=P.shape[1]))
    strip = len(src_field) + len(sep)
    stripd = len(dst_field) + len(sep)
    src = np.asarray([k[strip:] for k in skeys[used_r]], dtype=str)
    dst = np.asarray([k[stripd:] for k in dkeys[used_c]], dtype=str)
    nodes = np.union1d(src, dst)
    rmap = np.zeros(P.shape[0], np.int64)
    rmap[used_r] = np.searchsorted(nodes, src)
    cmap = np.zeros(P.shape[1], np.int64)
    cmap[used_c] = np.searchsorted(nodes, dst)
    coo = P.tocoo()
    sm = sp.csr_matrix((coo.data, (rmap[coo.row], cmap[coo.col])),
                       shape=(nodes.shape[0], nodes.shape[0]))
    return Assoc._from_parts(nodes, nodes, None, sm)


def undirected_bands(Esrc: Assoc, Edst: Assoc) -> Assoc:
    """The undirected simple graph of the ``ip.src|`` / ``ip.dst|``
    bands' edges, on the nodes of :func:`adjacency_bands`: each unordered
    pair {u, v} with u != v that appears in either direction is one
    entry each way (value 1); duplicates merge and self-loops drop, so a
    vertex whose only edges are self-loops stays a node with no
    neighbours (LDBC Graphalytics' undirected graph)."""
    A = adjacency_bands(Esrc, Edst)
    sm = (A.sm + A.sm.T).tocsr()
    sm = sm - sp.diags(sm.diagonal(), format="csr")
    sm.eliminate_zeros()
    sm.data[:] = 1.0
    sm.sort_indices()
    return Assoc._from_parts(A.row, A.col, None, sm)


def connections(E: Assoc, ip: str, src_field: str = "ip.src",
                dst_field: str = "ip.dst", sep: str = "|") -> Assoc:
    """Fig. 2's operation: every host that ``ip`` talked to (either
    direction), as a packet-count-valued associative array."""
    out_pkts = E[:, [f"{src_field}{sep}{ip}"]]
    in_pkts = E[:, [f"{dst_field}{sep}{ip}"]]
    # packets involving ip → all their other endpoint columns
    touched = (out_pkts.sum(1) + in_pkts.sum(1)).logical()  # packets × ['']
    sel = touched.T * E  # 1 × columns, counts per field|value
    return sel[:, StartsWith(f"{dst_field}{sep}")] + \
        sel[:, StartsWith(f"{src_field}{sep}")]


def degree_table(E: Assoc) -> Assoc:
    """``TedgeDeg``: per-column-key degree (stage 6's
    ``Edeg = putCol(sum(E.',2),'degree,')``)."""
    return E.T.sum(1).putcol("degree,")


# ---------------------------------------------------------------------------
# Device-side (COO) graph algebra — jit'd, semiring-generic, shardable.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_iters",))
def pagerank(adj: S.COO, num_iters: int = 20, damping: float = 0.85) -> jax.Array:
    """PageRank on a directed adjacency COO (Bottrack-style botnet
    centrality, paper ref [23]).  Dangling mass redistributed uniformly."""
    n = adj.shape[0]
    out_deg = S.row_degree(adj, weighted=True)
    inv_deg = jnp.where(out_deg > 0, 1.0 / jnp.maximum(out_deg, 1e-30), 0.0)
    rank = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(rank, _):
        contrib = rank * inv_deg
        spread = S.spmv_t(adj, contrib)  # mass flows src→dst
        dangling = jnp.sum(jnp.where(out_deg > 0, 0.0, rank))
        rank_new = (1 - damping) / n + damping * (spread + dangling / n)
        return rank_new, None

    rank, _ = jax.lax.scan(body, rank, None, length=num_iters)
    return rank


@jax.jit
def triangle_count(adj: S.COO, probe: jax.Array) -> jax.Array:
    """Randomized triangle-mass estimate  ≈ tr(A³)/6 via Hutchinson probes
    (z' A³ z).  ``probe``: (n, k) ±1.  Used as a density anomaly score."""
    az = S.spmm(adj, probe)
    aaz = S.spmm(adj, az)
    aaaz = S.spmm(adj, aaz)
    return jnp.mean(jnp.sum(probe * aaaz, axis=0)) / 6.0


@jax.jit
def degree_counts(m: S.COO) -> tuple[jax.Array, jax.Array]:
    """(row_degrees, col_degrees) of an incidence/adjacency payload."""
    return S.row_degree(m), S.col_degree(m)


def bfs_reachable(adj: S.COO, seed: jax.Array, hops: int = 3) -> jax.Array:
    """Boolean k-hop reachability via the or_and semiring (command-and-
    control spread estimation)."""
    frontier = seed.astype(jnp.float32)

    def body(f, _):
        nxt = S.spmv_t(adj, f, ring="or_and")
        return jnp.maximum(f, nxt), None

    out, _ = jax.lax.scan(body, frontier, None, length=hops)
    return out > 0
