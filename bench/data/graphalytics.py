"""LDBC Graphalytics community detection (CDLP) over Graph500 Kronecker
graphs stored in the D4M schema, and the plain reference it is checked
against.

The graph is the one ``kronecker`` generates and ingests (one Tedge row
per edge, ``ip.src|u`` and ``ip.dst|v``), read as Graphalytics reads
its ``graph500-XX`` datasets: undirected and simple, so both directions
and duplicates of an edge are one edge and self-loops are dropped.  The
vertices are those in some edge; a vertex whose only edges are
self-loops has no neighbours.

CDLP as the specification defines it: every vertex starts with its own
id as label; in each synchronous iteration a vertex takes the label most
frequent among its neighbours, the smallest on a tie, and a vertex
without neighbours keeps its label.  Labels here are vertex ranks in key
order, which is id order because the keys are zero-padded.
"""
from __future__ import annotations

import hashlib

import numpy as np

from . import kronecker


class Reference:
    """Vectorised numpy CDLP over the undirected simple graph of the
    stored edges (``nodes[s] -> nodes[d]``)."""

    def __init__(self, nodes: np.ndarray, s: np.ndarray, d: np.ndarray):
        self.n = n = int(nodes.shape[0])
        self.keys = kronecker.vertex_keys(nodes)
        off = s != d
        pair = np.unique(np.concatenate([s[off] * n + d[off],
                                         d[off] * n + s[off]]))
        self.row, self.col = pair // n, pair % n

    @property
    def n_pairs(self) -> int:
        """(vertex, neighbour) pairs: twice the undirected edges."""
        return int(self.row.shape[0])

    def labels(self, num_iters: int, ties: str = "smallest") -> np.ndarray:
        """Label ranks after ``num_iters`` iterations; ``ties="largest"``
        breaks ties to the largest label (the control)."""
        n, row, col = self.n, self.row, self.col
        label = np.arange(n, dtype=np.int64)
        for _ in range(num_iters):
            run, count = np.unique(row * n + label[col], return_counts=True)
            r, lab = run // n, run % n
            start = np.flatnonzero(np.r_[True, r[1:] != r[:-1]])
            most = np.repeat(np.maximum.reduceat(count, start),
                             np.diff(np.r_[start, r.shape[0]]))
            tied = np.flatnonzero(count == most)
            if ties == "largest":
                tied = tied[::-1]
            _, first = np.unique(r[tied], return_index=True)
            pick = tied[first]
            label = label.copy()
            label[r[pick]] = lab[pick]
        return label.astype(np.int32)


def summary(keys: np.ndarray, labels: np.ndarray, top_k: int) -> dict:
    """What a ``cdlp`` job returns for these labels: node and community
    counts, the ``top_k`` largest communities by size then key, and the
    sha256 of the labels as little-endian int32."""
    sizes = np.bincount(labels, minlength=keys.shape[0])
    order = sorted(np.flatnonzero(sizes).tolist(),
                   key=lambda c: (-int(sizes[c]), c))
    return {"n_nodes": int(keys.shape[0]), "n_communities": len(order),
            "communities": [{"key": str(keys[c]), "size": int(sizes[c])}
                            for c in order[:top_k]],
            "labels_sha256": hashlib.sha256(
                labels.astype("<i4").tobytes()).hexdigest()}


def deployment(cfg: dict, seed: int, overrides: dict):
    return CdlpDeployment(cfg, seed, overrides)


class CdlpDeployment(kronecker.GraphDeployment):
    def __init__(self, cfg, seed, overrides):
        super().__init__(cfg, seed, overrides)
        self.cdlp_ref = Reference(self.ref.nodes, self.ref.s, self.ref.d)
        self._want = {}

    def expected(self, spec: dict, ties: str = "smallest") -> dict:
        """The reference's answer to the stream's job, computed once."""
        p = spec["params"]
        key = (int(p["num_iters"]), int(p["top_k"]), ties)
        if key not in self._want:
            labels = self.cdlp_ref.labels(key[0], ties)
            self._want[key] = summary(self.cdlp_ref.keys, labels, key[1])
        return self._want[key]

    def job_bad(self, spec: dict, results: list) -> tuple:
        """(results whose labels digest, node count or community count
        differ from the reference's; top communities that differ, entry
        by entry, over all results)."""
        want = self.expected(spec)
        bad, missed = 0, 0
        for res in results:
            bad += any(res[k] != want[k] for k in
                       ("labels_sha256", "n_nodes", "n_communities"))
            got, top = res["communities"], want["communities"]
            missed += sum(g != w for g, w in zip(got, top))
            missed += abs(len(got) - len(top))
        return bad, missed

    def control_job(self, spec: dict) -> dict:
        """The control: the reference with ties to the largest label."""
        return self.expected(spec, "largest")
