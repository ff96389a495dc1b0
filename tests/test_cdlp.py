"""CDLP (LDBC Graphalytics community detection by label propagation):
the undirected simple graph built from two bands, and the device
program's labels, against the plain dict reference on seeded graphs."""
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from _graphalytics_ref import cdlp, neighbours
from bench.data.kronecker import edges, incidence
from repro.analytics import distributed as D
from repro.core import Assoc, StartsWith, graph
from repro.obs.metrics import REGISTRY

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _edges_random(seed):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, 40, 160)
    v = rng.zipf(1.6, 160) % 40
    return list(zip(u.tolist(), v.tolist()))


def _edges_ties():
    # 0 sees labels {1, 2, 3} once each; 4 sees 5 and 6 once each
    return [(0, 1), (0, 2), (0, 3), (4, 5), (6, 4), (7, 8)]


def _edges_reciprocal_and_duplicates():
    # both directions, repeated: each pair is still one edge
    return [(1, 2), (2, 1), (1, 2), (2, 3), (3, 2), (3, 2), (3, 4), (1, 4)]


def _edges_self_loops():
    # 9's only edge is a self-loop: it is a vertex with no neighbours
    return [(9, 9), (1, 1), (1, 2), (2, 3), (3, 3), (3, 1), (5, 6)]


def _edges_tied_runs():
    # in the second iteration hub 50 sees the labels 40, 40, 35, 35, 30, 30
    # in neighbour order (a three-way tie, the largest label first), and
    # 60 sees 1 once and 20 twice (the smallest label is not the mode)
    leaves = [(10, 40), (11, 40), (12, 35), (13, 35), (14, 30), (15, 30)]
    return ([(50, a) for a, _ in leaves] + leaves
            + [(60, 2), (60, 21), (60, 22), (2, 1), (21, 20), (22, 20)])


def _edges_kronecker(seed=5300000016):
    u, v = edges(seed, 7)
    return list(zip(u.tolist(), v.tolist()))


GRAPHS = {"random_0": lambda: _edges_random(0),
          "random_1": lambda: _edges_random(1),
          "ties": _edges_ties,
          "reciprocal_duplicates": _edges_reciprocal_and_duplicates,
          "self_loops": _edges_self_loops,
          "tied_runs": _edges_tied_runs,
          "kronecker": _edges_kronecker}


def _bands(pairs):
    u, v = (np.asarray(x) for x in zip(*pairs))
    E = Assoc(*incidence(u, v))
    return E, E[:, StartsWith("ip.src|")], E[:, StartsWith("ip.dst|")]


def _want(pairs, num_iters):
    """Reference labels as ranks in key order (zero-padded keys sort as
    the ids do)."""
    vertices = sorted({x for e in pairs for x in e})
    lab = cdlp(neighbours(vertices, pairs), num_iters)
    rank = {v: i for i, v in enumerate(vertices)}
    return [rank[lab[v]] for v in vertices], vertices


def _mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("num_iters", [0, 1, 10])
@pytest.mark.parametrize("case", list(GRAPHS))
def test_labels_equal_the_dict_reference(case, num_iters):
    pairs = GRAPHS[case]()
    E, _, _ = _bands(pairs)
    keys, labels = D.cdlp_table(E, mesh=_mesh(), num_iters=num_iters)
    want, vertices = _want(pairs, num_iters)
    assert keys.tolist() == [f"{v:06d}" for v in vertices]
    assert labels.dtype == np.int32
    assert labels.tolist() == want


def test_cases_read_as_meant():
    """The tie rule decides the ties case, in the second iteration the
    tied_runs case's hub takes the smallest of three tied labels and 60
    the label of its longest run, and a self-loop alone leaves a vertex
    with no neighbours."""
    want, vertices = _want(_edges_ties(), 1)
    assert want[vertices.index(0)] == vertices.index(1)
    assert want[vertices.index(4)] == vertices.index(5)
    want, vertices = _want(_edges_tied_runs(), 2)
    got = D.cdlp_table(_bands(_edges_tied_runs())[0], mesh=_mesh(),
                       num_iters=2)[1]
    for v, mode in ((50, 30), (60, 20)):
        assert want[vertices.index(v)] == got[vertices.index(v)] \
            == vertices.index(mode)
    E, src, dst = _bands(_edges_self_loops())
    U = graph.undirected_bands(src, dst)
    nine = list(U.row).index("000009")
    assert U.sm[nine].nnz == 0
    assert D.cdlp_table(E, mesh=_mesh())[1][nine] == nine


@pytest.mark.parametrize("case", list(GRAPHS))
def test_undirected_graph_is_a_plus_a_transpose(case):
    """The pattern of A + A.T with the diagonal dropped, on the square
    adjacency's nodes, every value 1; one adjacency build per graph."""
    _, src, dst = _bands(GRAPHS[case]())
    A = graph.adjacency_bands(src, dst)
    key = ("repro_adjacency_builds_total", (("rows", "shared"),))
    n0 = REGISTRY.as_dict()[key]
    U = graph.undirected_bands(src, dst)
    assert REGISTRY.as_dict()[key] == n0 + 1
    want = (A.sm + A.sm.T).astype(bool).tolil()
    want.setdiag(False)
    want = sp.csr_matrix(want)
    want.eliminate_zeros()
    assert np.array_equal(U.row, A.row) and np.array_equal(U.col, A.col)
    assert (U.sm.astype(bool) != want).nnz == 0
    assert set(U.sm.data) <= {1.0} and U.sm.has_sorted_indices


def test_empty_bands_give_no_nodes():
    E = Assoc("p1,p2,", "ip.src|000001,ip.dst|000002,", 1.0)
    src = E[:, StartsWith("ip.src|")]
    dst = Assoc("p3,", "ip.dst|000002,", 1.0)
    keys, labels = D.cdlp_table(Assoc("p1,", "ip.src|000001,", 1.0)
                                + dst, mesh=_mesh())
    assert keys.shape == labels.shape == (0,)
    assert graph.undirected_bands(src, dst).shape == (0, 0)


def test_one_job_one_launch_and_its_bytes():
    """One launch per job; the upload is the (vertex, neighbour) pairs as
    two int32 arrays."""
    from repro.core.expr import launch_counts
    E, src, dst = _bands(_edges_kronecker())
    pairs = graph.undirected_bands(src, dst).nnz
    h2d = ("repro_h2d_bytes_total", (("site", "cdlp"),))
    b0, n0 = REGISTRY.as_dict()[h2d], launch_counts()["cdlp"]
    D.cdlp_table(E, mesh=_mesh(), num_iters=3)
    assert launch_counts()["cdlp"] == n0 + 1
    assert REGISTRY.as_dict()[h2d] == b0 + 8 * pairs


def test_label_program_compiles_once():
    """The whole iteration is one jitted program: a second job over the
    same graph reuses it."""
    _, src, dst = _bands(_edges_random(7))
    U = graph.undirected_bands(src, dst).sm
    before = D._cdlp._cache_size()
    a = D.cdlp_sharded(U, _mesh(), num_iters=4)
    b = D.cdlp_sharded(U.copy(), _mesh(), num_iters=4)
    assert D._cdlp._cache_size() == before + 1
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _loop_text(hlo: str) -> str:
    """The StableHLO the ``while`` loops run: each loop's body region and
    every function it calls, transitively."""
    funcs = {m.group(1): m.group(0) for m in re.finditer(
        r"^  func\.func \w+ @([\w.$-]+)\(.*?^  }$", hlo, re.S | re.M)}
    parts = []
    for m in re.finditer(r"\} do \{", hlo):
        depth, i = 1, m.end()
        while depth:
            depth += {"{": 1, "}": -1}.get(hlo[i], 0)
            i += 1
        parts.append(hlo[m.end():i])
    seen = set()
    for text in parts:
        for name in re.findall(r"call @([\w.$-]+)", text):
            if name not in seen:
                seen.add(name)
                parts.append(funcs[name])
    return "\n".join(parts)


def test_label_loop_has_no_scatter():
    """Each iteration finds every vertex's mode from sorted order: the
    loop holds sorts and gathers but no scatter."""
    _, src, dst = _bands(_edges_random(0))
    U = graph.undirected_bands(src, dst).sm
    rows, cols = D.shard_by_vertex(U.indptr, U.indices, 1)
    hlo = D._cdlp.lower(rows, cols, n=U.shape[0], mesh=_mesh(),
                        num_iters=3).as_text()
    loop = _loop_text(hlo)
    assert "stablehlo.sort" in loop and "stablehlo.gather" in loop
    assert "scatter" not in loop


def test_vertex_blocks_never_split_a_row():
    rng = np.random.default_rng(3)
    A = sp.random(50, 50, density=0.2, random_state=rng, format="lil")
    A[7, :] = 1.0                   # a hub longer than an equal split
    A = A.tocsr()
    rows, cols = D.shard_by_vertex(A.indptr, A.indices, 8)
    live = rows < 50
    assert live.sum() == A.nnz
    for k in range(8):
        owned = set(rows[k][live[k]].tolist())
        for r in owned:
            assert (rows[k] == r).sum() == A.indptr[r + 1] - A.indptr[r]
    got = sorted(zip(rows[live].tolist(), cols[live].tolist()))
    coo = A.tocoo()
    assert got == sorted(zip(coo.row.tolist(), coo.col.tolist()))


@pytest.mark.slow
def test_sharded_labels_match_one_device_and_the_reference():
    """Over 8 CPU devices the pairs are split by owning vertex: a hub
    whose pairs would straddle an equal-nnz split stays whole, and the
    labels equal one device's and the dict reference's."""
    code = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sys.path.insert(0, %r)
        import jax, numpy as np, scipy.sparse as sp
        from _graphalytics_ref import cdlp, neighbours
        from repro.analytics import distributed as D
        rng = np.random.default_rng(11)
        n = 300
        u = rng.integers(0, n, 1200)
        v = rng.integers(0, n, 1200)
        u[:500] = 17                      # the hub
        pairs = list(zip(u.tolist(), v.tolist()))
        nb = neighbours(range(n), pairs)
        A = sp.csr_matrix((np.ones(1200), (u, v)), shape=(n, n))
        A = (A + A.T).tolil()
        A.setdiag(0)
        A = sp.csr_matrix(A)
        A.eliminate_zeros()
        A.data[:] = 1.0
        per = -(-A.nnz // 8)
        lo, hi = A.indptr[17], A.indptr[18]
        assert lo // per != (hi - 1) // per, "hub does not straddle"
        mesh8 = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
        mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
        for it in (0, 1, 10):
            g8 = np.asarray(D.cdlp_sharded(A, mesh8, num_iters=it))
            g1 = np.asarray(D.cdlp_sharded(A, mesh1, num_iters=it))
            want = cdlp(nb, it)
            assert g8.tolist() == g1.tolist() == [want[i] for i in range(n)]
        print("SHARDED_CDLP_OK")
    """ % os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_CDLP_OK" in out.stdout
