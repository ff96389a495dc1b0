"""Graph500 Kronecker graphs stored in the D4M schema, and the plain
PageRank reference they are checked against.

The generator is the Graph500 specification's (section 3, "Kronecker
generator"): initiator probabilities A=0.57, B=0.19, C=0.19, D=0.05,
``2**scale`` vertices and ``edge_factor * 2**scale`` directed edges,
each edge placed by ``scale`` independent quadrant choices, then vertex
labels and edge order permuted at random.

Every seed gets the same graph up to those permutations: the edges in
vertex-index space come from a fixed generator and ``--seed`` draws the
vertex relabelling and the edge order.  Duplicates and self-loops are
kept (Graphalytics would drop them; here a duplicate edge is weight).

Each edge is stored as one Tedge row holding ``ip.src|<u>`` and
``ip.dst|<v>``, so the served ``pagerank`` job reads the graph through
the same column bands as a packet capture.
"""
from __future__ import annotations

import numpy as np

from ..deploy import Deployment
from ..precision import bf16

SHAPE_SEED = 0x6500
VERTEX_DIGITS = 6
EDGE_DIGITS = 8


def edges(seed: int, scale: int, edge_factor: int = 16, a: float = 0.57,
          b: float = 0.19, c: float = 0.19):
    """(u, v) vertex labels of every edge, in the seed's order."""
    n_edges = edge_factor << scale
    fixed = np.random.default_rng([SHAPE_SEED, scale])
    u = np.zeros(n_edges, np.int64)
    v = np.zeros(n_edges, np.int64)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    for bit in range(scale):
        ii = fixed.random(n_edges) > ab
        jj = fixed.random(n_edges) > np.where(ii, c_norm, a_norm)
        u |= ii.astype(np.int64) << bit
        v |= jj.astype(np.int64) << bit
    rng = np.random.default_rng([seed, 3])
    label = rng.permutation(1 << scale)
    order = rng.permutation(n_edges)
    return label[u[order]], label[v[order]]


def vertex_keys(x: np.ndarray) -> np.ndarray:
    return np.char.zfill(np.asarray(x).astype("U"), VERTEX_DIGITS)


def incidence(u: np.ndarray, v: np.ndarray) -> tuple:
    """Two entries per edge row: ``ip.src|u`` and ``ip.dst|v``."""
    ids = np.char.zfill(np.arange(u.shape[0]).astype("U"), EDGE_DIGITS)
    r = np.repeat(ids, 2)
    c = np.stack([np.char.add("ip.src|", vertex_keys(u)),
                  np.char.add("ip.dst|", vertex_keys(v))], axis=1).ravel()
    return r, c, np.ones(r.shape[0])


class Reference:
    """PageRank of the stored graph with the served path's algorithm:
    uniform restart, dangling mass spread uniformly, duplicate edges as
    weight, over the vertices that appear in some edge."""

    def __init__(self, u: np.ndarray, v: np.ndarray):
        self.nodes = np.union1d(u, v)
        self.s = np.searchsorted(self.nodes, u)
        self.d = np.searchsorted(self.nodes, v)
        self.keys = vertex_keys(self.nodes)

    @property
    def n(self) -> int:
        return int(self.nodes.shape[0])

    @property
    def n_edges_distinct(self) -> int:
        """Entries of the adjacency: distinct (source, destination)."""
        return int(np.unique(self.s * self.n + self.d).shape[0])

    def pagerank(self, num_iters: int, damping: float,
                 precision: str = "float64") -> np.ndarray:
        """Ranks aligned with ``self.keys``.  ``precision="bfloat16"``
        keeps the ranks, the inverse degrees and every product in
        bfloat16 (sums in float32): the control."""
        import scipy.sparse as sp
        n = self.n
        A = sp.csr_matrix((np.ones(self.s.shape[0]), (self.s, self.d)),
                          shape=(n, n))
        out_deg = np.asarray(A.sum(axis=1)).ravel()
        inv = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1e-300), 0.0)
        AT = A.T.tocsr()
        p = np.full(n, 1.0 / n)
        rank = p.copy()
        low = precision == "bfloat16"
        if low:
            AT = AT.astype(np.float32)
            inv, p, rank = bf16(inv), bf16(p), bf16(rank)
        for _ in range(num_iters):
            x = rank * inv
            if low:
                x = bf16(x)
            spread = AT @ x
            dangling = rank[out_deg == 0].sum()
            rank = (1 - damping) * p + damping * (spread + dangling * p)
            if low:
                rank = bf16(rank)
        return np.asarray(rank, np.float64)


# ---------------------------------------------------------------------------
# The deployment: the graph ingested into the configured DB().
# ---------------------------------------------------------------------------

def deployment(cfg: dict, seed: int, overrides: dict):
    return GraphDeployment(cfg, seed, overrides)


class GraphDeployment(Deployment):
    def __init__(self, cfg, seed, overrides):
        from repro.core import Assoc
        super().__init__(cfg, seed)
        scale = int(overrides.get("scale", cfg["scale"]))
        a, b, c = cfg["initiator"]
        u, v = edges(seed, scale, int(cfg["edge_factor"]), a, b, c)
        self.load(Assoc(*incidence(u, v)))
        self.ref = Reference(u, v)
        self._ranks = {}

    def ranks(self, num_iters: int, damping: float,
              precision: str = "float64") -> np.ndarray:
        key = (num_iters, damping, precision)
        if key not in self._ranks:
            self._ranks[key] = self.ref.pagerank(num_iters, damping,
                                                 precision)
        return self._ranks[key]

    def job_bad(self, spec: dict, results: list) -> tuple:
        """(widest relative error of a returned rank, reference top
        nodes a result left out): every node a result lists must carry
        its reference rank, and no node whose reference rank clearly
        beats the lowest listed one may be missing."""
        p = spec["params"]
        want = self.ranks(int(p["num_iters"]), float(self.cfg["damping"]))
        pos = {k: i for i, k in enumerate(self.ref.keys.tolist())}
        rel, missed = 0.0, 0
        for res in results:
            nodes = res["nodes"]
            if res["n_nodes"] != self.ref.n or len(nodes) != min(
                    int(p["top_k"]), self.ref.n):
                missed += int(p["top_k"])
                continue
            idx = [pos.get(n["key"], -1) for n in nodes]
            if min(idx) < 0:
                missed += sum(i < 0 for i in idx)
                continue
            got = np.asarray([n["rank"] for n in nodes], np.float64)
            ref = want[idx]
            rel = max(rel, float(np.max(np.abs(got - ref) / ref)))
            floor = ref.min() * (1 + 1e-3)
            listed = set(idx)
            missed += int(sum(1 for i in np.flatnonzero(want > floor)
                              if i not in listed))
        return rel, missed

    def control_job(self, spec: dict) -> dict:
        """The control: the reference's answer computed in bfloat16."""
        p = spec["params"]
        r = self.ranks(int(p["num_iters"]), float(self.cfg["damping"]),
                       "bfloat16")
        order = np.argsort(r)[::-1][:int(p["top_k"])]
        return {"nodes": [{"key": str(self.ref.keys[i]),
                           "rank": float(r[i])} for i in order],
                "n_nodes": self.ref.n}
