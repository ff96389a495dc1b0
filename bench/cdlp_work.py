"""What one CDLP iteration needs, from the graph's sizes alone, whatever
groups the (vertex, neighbour) pairs: each pair's neighbour index and
that neighbour's label read once (4 B each), each vertex's row pointer
read and its new label written once (4 B each), and one compare a pair.
"""
from __future__ import annotations

from bench.work import IDX, least_seconds

LABEL = 4


def iteration(pairs: int, n: int) -> tuple:
    """(operations, bytes) of one iteration over ``pairs`` (vertex,
    neighbour) pairs and ``n`` vertices."""
    return pairs, pairs * (IDX + LABEL) + n * (IDX + LABEL)


def least_iteration_seconds(pairs: int, n: int, peaks) -> float:
    return least_seconds(*iteration(pairs, n), peaks)
