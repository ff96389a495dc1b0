"""Plain LDBC Graphalytics CDLP, written from the specification with
dicts and ``Counter``: independent of the program and of the
benchmark's vectorised copy.

The graph is undirected and simple: an edge is an unordered pair of two
distinct vertices, however often and in whichever direction it was
given.  Every vertex starts with its own id as label; in each
synchronous iteration a vertex takes the label most frequent among its
neighbours, the smallest of those on a tie, and a vertex without
neighbours keeps its label.
"""
from collections import Counter


def neighbours(vertices, edges) -> dict:
    """Vertex -> set of its neighbours in the undirected simple graph."""
    nb = {v: set() for v in vertices}
    for u, v in edges:
        nb.setdefault(u, set())
        nb.setdefault(v, set())
        if u != v:
            nb[u].add(v)
            nb[v].add(u)
    return nb


def cdlp(nb: dict, num_iters: int) -> dict:
    """Vertex -> label after ``num_iters`` iterations."""
    label = {v: v for v in nb}
    for _ in range(num_iters):
        new = {}
        for v, ns in nb.items():
            if not ns:
                new[v] = label[v]
                continue
            counts = Counter(label[u] for u in ns)
            most = max(counts.values())
            new[v] = min(lab for lab, c in counts.items() if c == most)
        label = new
    return label
