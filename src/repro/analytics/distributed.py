"""Device-parallel sparse analytics over the mesh (shard_map).

The paper scales its analytics with data-parallel map over files; on the
TPU mesh the same work is *device*-parallel: the incidence/adjacency
payload is row-sharded (packet/source blocks) across the ``data`` axis
and each device reduces its shard, combining with ``psum`` — degree
tables, SpMV, and PageRank become collective segment reductions.

Shards are padded to equal nnz (COO dead-entry convention: row == nrows
contributes nothing), so ``shard_map`` sees uniform blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.expr import count_launch
from ..core.sparse import COO
from ..device import count_h2d
from ..obs.trace import stage


def shard_coo(m: COO, n_shards: int) -> COO:
    """Split nnz into equal row-contiguous shards (pad with dead entries
    at row == nrows). Returns a COO whose leading dim stacks shards."""
    nnz = m.nnz
    per = -(-nnz // n_shards)
    pad = per * n_shards - nnz
    rows = jnp.pad(m.rows, (0, pad), constant_values=m.shape[0])
    cols = jnp.pad(m.cols, (0, pad))
    vals = jnp.pad(m.vals, (0, pad))
    return COO(rows.reshape(n_shards, per), cols.reshape(n_shards, per),
               vals.reshape(n_shards, per), m.shape)


def degree_sharded(m: COO, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Column degrees of a COO, nnz-sharded over ``axis`` with psum."""
    n_shards = mesh.shape[axis]
    sh = shard_coo(m, n_shards)
    n_cols = m.shape[1]
    n_rows = m.shape[0]

    spec = P(axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=P(),
        check_vma=False)
    def _deg(rows, cols, vals):
        rows, cols, vals = rows[0], cols[0], vals[0]
        live = (rows < n_rows).astype(vals.dtype)
        local = jax.ops.segment_sum(live, cols, num_segments=n_cols)
        return jax.lax.psum(local, axis)

    return _deg(sh.rows, sh.cols, sh.vals)


def spmv_t_sharded(m: COO, x: jax.Array, mesh: Mesh,
                   axis: str = "data") -> jax.Array:
    """y[j] = Σ_i m[i,j]·x[i], nnz-sharded with psum (PageRank inner op)."""
    return _spmv_t_shards(shard_coo(m, mesh.shape[axis]), x, mesh, axis)


def _spmv_t_shards(sh: COO, x: jax.Array, mesh: Mesh, axis: str
                   ) -> jax.Array:
    """:func:`spmv_t_sharded` over a COO already split by
    :func:`shard_coo`."""
    n_rows, n_cols = sh.shape
    spec = P(axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec, P()),
        out_specs=P(), check_vma=False)
    def _spmv(rows, cols, vals, xv):
        rows, cols, vals = rows[0], cols[0], vals[0]
        safe = jnp.minimum(rows, n_rows - 1)
        live = (rows < n_rows).astype(vals.dtype)
        prods = vals * live * xv[safe]
        local = jax.ops.segment_sum(prods, cols, num_segments=n_cols)
        return jax.lax.psum(local, axis)

    return _spmv(sh.rows, sh.cols, sh.vals, x)


def pagerank_sharded(adj: COO, mesh: Mesh, num_iters: int = 20,
                     damping: float = 0.85, axis: str = "data",
                     personalize: jax.Array | None = None) -> jax.Array:
    """PageRank with the SpMV inner loop distributed over the mesh.

    ``personalize`` (n,) replaces the uniform restart distribution: the
    random surfer teleports to those nodes instead of anywhere, and
    dangling mass is redistributed the same way — personalized PageRank
    (the MicroRCA root-cause localization primitive)."""
    n = adj.shape[0]
    if personalize is None:
        p = jnp.full((n,), 1.0 / n, jnp.float32)
    else:
        p = jnp.maximum(personalize.astype(jnp.float32), 0.0)
        p = p / jnp.maximum(jnp.sum(p), 1e-30)
    return _pagerank(adj, p, mesh=mesh, num_iters=num_iters,
                     damping=damping, axis=axis)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "num_iters", "damping", "axis"))
def _pagerank(adj: COO, p: jax.Array, *, mesh: Mesh, num_iters: int,
              damping: float, axis: str) -> jax.Array:
    """The whole power iteration as one program: the shard_map bodies
    are traced once, not rebuilt (and recompiled) every iteration."""
    sh = shard_coo(adj, mesh.shape[axis])
    out_deg_w = spmv_weighted_rowsum(adj, mesh, axis)
    inv_deg = jnp.where(out_deg_w > 0, 1.0 / jnp.maximum(out_deg_w, 1e-30),
                        0.0)

    def step(_, rank):
        spread = _spmv_t_shards(sh, rank * inv_deg, mesh, axis)
        dangling = jnp.sum(jnp.where(out_deg_w > 0, 0.0, rank))
        return (1 - damping) * p + damping * (spread + dangling * p)

    return jax.lax.fori_loop(0, num_iters, step, p)


def pagerank_table(T, mesh: Mesh | None = None, num_iters: int = 20,
                   src_field: str = "ip.src", dst_field: str = "ip.dst",
                   sep: str = "|", axis: str = "data",
                   personalize: dict | None = None, reverse: bool = False,
                   damping: float = 0.85) -> tuple[np.ndarray, np.ndarray]:
    """PageRank served straight from the database binding.

    Queries the src/dst column blocks through the :class:`DBTable`
    selection grammar (pushed-down transpose-table scans), builds the
    square adjacency on the host (:func:`graph.adjacency_bands`), then
    runs the mesh-sharded PageRank on the device payload.  Returns
    ``(node_keys, ranks)`` aligned by index, the ranks a float32 host
    array.  The three phases are the stages
    ``analytics.pagerank.scan``, ``.adjacency`` and ``.device``.

    ``T`` may equally be an in-memory incidence :class:`Assoc` (a
    streaming window slice) — anything speaking the selection grammar.
    ``personalize`` maps host keys to restart weights (personalized
    PageRank); ``reverse`` transposes the adjacency first, so mass flows
    from a seed *victim* back to the hosts feeding it traffic — the
    MicroRCA root-cause direction.
    """
    from ..core import graph
    from ..core.expr import eval_batch

    with stage("analytics.pagerank.scan"):
        src, dst = eval_batch([T[:, f"{src_field}{sep}*,"],
                               T[:, f"{dst_field}{sep}*,"]])
    with stage("analytics.pagerank.adjacency"):
        adj = graph.adjacency_bands(src, dst, src_field=src_field,
                                    dst_field=dst_field, sep=sep)
    if adj.nnz == 0:
        return np.empty((0,), dtype=str), np.zeros((0,), np.float32)
    if reverse:
        adj = adj.T
    p = None
    if personalize is not None:
        w = np.zeros(adj.row.shape[0], np.float32)
        pos = np.searchsorted(adj.row, list(personalize))
        for k, i in zip(personalize, pos):
            if i < adj.row.shape[0] and adj.row[i] == k:
                w[i] = float(personalize[k])
        if w.sum() <= 0:            # no seed present — uniform restart
            p = None
        else:
            p = w
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), (axis,))
    # upload, the whole power iteration, and the ranks back on the host
    with stage("analytics.pagerank.device", nnz=adj.nnz):
        coo = adj.device_coo(jnp.float32)
        count_h2d("pagerank", coo.rows, coo.cols, coo.vals)
        if p is not None:
            p = jnp.asarray(p)
            count_h2d("pagerank", p)
        ranks = np.asarray(pagerank_sharded(
            coo, mesh, num_iters=num_iters, axis=axis, personalize=p,
            damping=damping))
    return adj.row, ranks


def spmv_weighted_rowsum(m: COO, mesh: Mesh, axis: str = "data"
                         ) -> jax.Array:
    """Row sums (weighted out-degree), sharded."""
    n_shards = mesh.shape[axis]
    sh = shard_coo(m, n_shards)
    n_rows = m.shape[0]
    spec = P(axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=P(), check_vma=False)
    def _rs(rows, cols, vals):
        rows, vals = rows[0], vals[0]
        safe = jnp.minimum(rows, n_rows - 1)
        live = (rows < n_rows).astype(vals.dtype)
        local = jax.ops.segment_sum(vals * live, safe,
                                    num_segments=n_rows)
        return jax.lax.psum(local, axis)

    return _rs(sh.rows, sh.cols, sh.vals)


# ---------------------------------------------------------------------------
# Community detection by label propagation (LDBC Graphalytics CDLP).
# ---------------------------------------------------------------------------

def shard_by_vertex(indptr: np.ndarray, indices: np.ndarray,
                    n_shards: int) -> tuple:
    """Split a CSR's entries into ``n_shards`` row-contiguous blocks of
    about equal nnz that never split a row (a vertex's pairs stay on one
    shard), padded with dead entries at row == nrows as :func:`shard_coo`
    pads.  Returns host arrays ``(rows, cols)``, each
    ``(n_shards, per)``."""
    n = indptr.shape[0] - 1
    nnz = int(indptr[-1])
    cut = np.searchsorted(indptr, np.arange(n_shards + 1) * nnz / n_shards)
    cut[-1] = n                     # trailing rows without pairs
    lo, hi = indptr[cut[:-1]], indptr[cut[1:]]
    per = max(int((hi - lo).max()), 1)
    rows = np.full((n_shards, per), n, np.int32)
    cols = np.zeros((n_shards, per), np.int32)
    deg = np.diff(indptr)
    for k in range(n_shards):
        r0, r1 = cut[k], cut[k + 1]
        m = hi[k] - lo[k]
        rows[k, :m] = np.repeat(np.arange(r0, r1), deg[r0:r1])
        cols[k, :m] = indices[lo[k]:hi[k]]
    return rows, cols


def cdlp_sharded(adj, mesh: Mesh, num_iters: int = 10) -> jax.Array:
    """Community detection by label propagation over an undirected graph
    (``adj``: a symmetric scipy CSR pattern without diagonal, as
    :func:`graph.undirected_bands` builds).  Each vertex starts with its
    own index as label; in each of ``num_iters`` synchronous iterations
    every vertex takes the label most frequent among its neighbours,
    ties to the smallest, and a vertex with no neighbours keeps its own.
    The pairs are sharded by owning vertex over the mesh axis ``data``
    (:func:`shard_by_vertex`).  Returns the int32 labels."""
    sharding = NamedSharding(mesh, P("data", None))
    rows, cols = (jax.device_put(b, sharding) for b in
                  shard_by_vertex(adj.indptr, adj.indices,
                                  mesh.shape["data"]))
    count_h2d("cdlp", rows, cols)
    count_launch("cdlp")
    return _cdlp(rows, cols, n=adj.shape[0], mesh=mesh,
                 num_iters=num_iters)


@functools.partial(jax.jit, static_argnames=("n", "mesh", "num_iters"))
def _cdlp(rows: jax.Array, cols: jax.Array, *, n: int, mesh: Mesh,
          num_iters: int) -> jax.Array:
    """Every iteration as one program: gather the neighbours' labels,
    sort each shard's pairs by (vertex, label), count each run, read per
    vertex the first label of its longest run at the vertex's last pair,
    and combine the shards' disjoint vertex blocks with ``psum``.  The
    loop reads the sorted order by position and never scatters."""
    spec = P("data", None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec),
        out_specs=P(), check_vma=False)
    def _run(rows, cols):
        rows, cols = rows[0], cols[0]
        pos = jnp.arange(rows.shape[0], dtype=jnp.int32)
        first = jnp.ones((1,), bool)
        row_start = jnp.concatenate([first, rows[1:] != rows[:-1]])
        # the sorts keep rows, which is sorted already (the dead entries at
        # row n last), so each vertex's pairs keep their positions: seg is
        # each pair's vertex's first, end each vertex's last
        vertex = jnp.arange(n, dtype=rows.dtype)
        end = jnp.maximum(jnp.searchsorted(rows, vertex, side="right") - 1, 0)
        mine = rows[end] == vertex
        seg = jax.lax.cummax(jnp.where(row_start, pos, 0))
        has_pairs = jax.lax.psum(mine.astype(jnp.int32), "data") > 0

        def step(_, labels):
            _, lab = jax.lax.sort((rows, labels[cols]), num_keys=2)
            run_start = row_start | jnp.concatenate([first,
                                                     lab[1:] != lab[:-1]])
            # pairs so far in each (vertex, label) run
            count = pos - jax.lax.cummax(jnp.where(run_start, pos, 0)) + 1
            # seg + count exceeds every earlier vertex's, so its running
            # maximum is each vertex's own longest run so far; the last
            # pair that raised it is the first, in label order, to reach
            # the vertex's longest run: ties go to the smallest label
            most = jax.lax.cummax(seg + count)
            raised = most > jnp.concatenate([jnp.full((1,), -1, most.dtype),
                                             most[:-1]])
            mode = lab[jax.lax.cummax(jnp.where(raised, pos, 0))[end]]
            return jnp.where(has_pairs, jax.lax.psum(
                jnp.where(mine, mode, 0), "data"), labels)

        return jax.lax.fori_loop(0, num_iters, step,
                                 jnp.arange(n, dtype=jnp.int32))

    return _run(rows, cols)


def cdlp_table(T, mesh: Mesh | None = None, num_iters: int = 10
               ) -> tuple[np.ndarray, np.ndarray]:
    """CDLP served straight from the database binding, as
    :func:`pagerank_table`: the two band scans (``ip.src|*`` and
    ``ip.dst|*``), the undirected simple
    graph built on the host (:func:`graph.undirected_bands`), then
    :func:`cdlp_sharded` on the mesh.  Returns ``(node_keys, labels)``
    aligned by index; a label is the index of a node in ``node_keys``.
    The three phases are the stages ``analytics.cdlp.scan``,
    ``.adjacency`` and ``.device`` (upload, program, labels home)."""
    from ..core import graph
    from ..core.expr import eval_batch

    with stage("analytics.cdlp.scan"):
        src, dst = eval_batch([T[:, "ip.src|*,"], T[:, "ip.dst|*,"]])
    with stage("analytics.cdlp.adjacency"):
        und = graph.undirected_bands(src, dst)
    if und.shape[0] == 0:
        return np.empty((0,), dtype=str), np.zeros((0,), np.int32)
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
    with stage("analytics.cdlp.device", nnz=und.nnz):
        labels = np.asarray(cdlp_sharded(und.sm, mesh, num_iters=num_iters))
    return und.row, labels
