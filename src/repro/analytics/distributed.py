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
