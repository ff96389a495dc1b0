"""Host graph construction: the integer-indexed square adjacency built
from two scanned bands against the D4M listing it stands in for."""
import numpy as np
import pytest

from bench.data.kronecker import edges, incidence
from repro.core import Assoc, StartsWith, graph
from repro.obs.metrics import REGISTRY


def _bands(E: Assoc):
    return E[:, StartsWith("ip.src|")], E[:, StartsWith("ip.dst|")]


def _kronecker():
    return _bands(Assoc(*incidence(*edges(5300000002, 9))))


def _rows_differ():
    # p5 holds only a src entry and p6 only a dst entry: x and y are in
    # no edge, so neither is a node
    return _bands(Assoc("p1,p1,p2,p2,p3,p3,p4,p4,p5,p6,",
                        "ip.src|a,ip.dst|b,ip.src|b,ip.dst|c,ip.src|a,"
                        "ip.dst|c,ip.src|c,ip.dst|a,ip.src|x,ip.dst|y,",
                        [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]))


def _categorical():
    return _bands(Assoc("p1,p1,p2,p2,p3,p3,",
                        "ip.src|a,ip.dst|b,ip.src|b,ip.dst|a,ip.src|a,"
                        "ip.dst|c,", "tcp,udp,tcp,icmp,udp,tcp,"))


def _mixed():
    # one categorical band makes the bands' sum categorical: both count
    # as logical
    src, _ = _bands(Assoc("p1,p2,p3,", "ip.src|a,ip.src|b,ip.src|a,",
                          [2.0, 3.0, 5.0]))
    _, dst = _categorical()
    return src, dst


def _duplicates():
    # a->b on three rows and b->a on one: weights sum per edge
    return _bands(Assoc("p1,p1,p2,p2,p3,p3,p4,p4,",
                        "ip.src|a,ip.dst|b,ip.src|a,ip.dst|b,ip.src|a,"
                        "ip.dst|b,ip.src|b,ip.dst|a,",
                        [1.0, 0.5, 1.0, 2.0, 3.0, 0.25, 1.0, 1.0]))


def _disjoint():
    src, _ = _bands(Assoc("p1,p2,", "ip.src|a,ip.src|b,", 1.0))
    _, dst = _bands(Assoc("p3,p4,", "ip.dst|a,ip.dst|b,", 1.0))
    return src, dst


def _self_loop():
    return _bands(Assoc("p1,p1,p2,p2,", "ip.src|a,ip.dst|a,ip.src|a,"
                        "ip.dst|b,", [1.0, 1.0, 1.0, 1.0]))


CASES = {"kronecker": _kronecker, "rows_differ": _rows_differ,
         "categorical": _categorical, "mixed": _mixed,
         "duplicates": _duplicates, "disjoint": _disjoint,
         "self_loop": _self_loop}


def _builds(rows: str) -> float:
    return REGISTRY.as_dict().get(
        ("repro_adjacency_builds_total", (("rows", rows),)), 0.0)


@pytest.mark.parametrize("case", list(CASES))
def test_adjacency_bands_equals_d4m_listing(case):
    src, dst = CASES[case]()
    want = graph.square(graph.adjacency(src + dst))
    got = graph.adjacency_bands(src, dst)
    assert np.array_equal(got.row, want.row)
    assert np.array_equal(got.col, want.col)
    assert got.shape == want.shape and got.nnz == want.nnz
    assert got.val is None
    assert (got.sm != want.sm).nnz == 0
    assert np.array_equal(got.triples()[2], want.triples()[2])


def test_adjacency_bands_cases_read_as_meant():
    """Each case holds what it is named for."""
    k = graph.adjacency_bands(*_kronecker())
    assert k.nnz > 1000 and k.shape[0] > 256
    assert not {"x", "y"} & set(graph.adjacency_bands(*_rows_differ()).row)
    cat = graph.adjacency_bands(*_categorical())
    assert set(cat.sm.data) == {1.0}
    mixed = graph.adjacency_bands(*_mixed())
    assert set(mixed.sm.data) == {1.0}
    dup = graph.adjacency_bands(*_duplicates())
    assert dup.sm[0, 1] == 0.5 + 2.0 + 0.75 and dup.sm[1, 0] == 1.0
    empty = graph.adjacency_bands(*_disjoint())
    assert empty.nnz == 0 and empty.shape == (0, 0)
    loop = graph.adjacency_bands(*_self_loop())
    assert list(loop.row) == ["a", "b"] and loop.sm[0, 0] == 1.0


@pytest.mark.parametrize("case,rows", [("kronecker", "shared"),
                                       ("rows_differ", "intersected")])
def test_adjacency_builds_count_their_row_alignment(case, rows):
    other = {"shared": "intersected", "intersected": "shared"}[rows]
    bands = CASES[case]()
    n0, m0 = _builds(rows), _builds(other)
    graph.adjacency_bands(*bands)
    assert _builds(rows) == n0 + 1
    assert _builds(other) == m0
