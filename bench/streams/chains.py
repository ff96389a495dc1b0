"""Closed-loop analysts evaluating batches of matvec chains from code.

``clients`` threads each call ``eval_batch`` on ``chains_per_call``
chains ``T[:, band*] * x``, one call after another.  Every call is new:
call ``i`` draws its weights (float32-exact, in [0.5, 2)) from the seed
and ``i``, and no call repeats another.  ``keys_per_chain`` is ``"all"``
(x weighs every key of the band, so every call has the band's shape) or
a number of keys that each chain draws afresh by popularity (the shape
then moves with the keys).  A share ``check_share`` of the calls,
picked from the seed, keeps its answers for the check.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench.load import Stream

WARM_CALLS = 2


class Chains(Stream):
    def __init__(self, spec, dep, seed, traced, seconds):
        super().__init__(spec, dep, seed, traced, seconds)
        self.band = spec["band"]
        self.b = int(spec["chains_per_call"])
        k = spec["keys_per_chain"]
        self.k = None if k == "all" else int(k)
        self.share = float(spec["check_share"])
        self.keys_by_rank = dep.band_keys_by_rank(self.band)
        self.pop = dep.popularity(self.band)
        self.n_calls = 0
        self.n_chains = 0
        self.t_last = None
        self.done: list = []            # call indices completed
        self.kept: dict = {}            # call index -> its answers
        self._next = WARM_CALLS
        self._lock = threading.Lock()

    def draw(self, i: int) -> list:
        """The (keys, weights) of each chain of call ``i``."""
        rng = np.random.default_rng([self.seed, 10, i])
        n = self.keys_by_rank.shape[0]
        out = []
        for _ in range(self.b):
            keys = self.keys_by_rank if self.k is None else \
                self.keys_by_rank[rng.choice(n, self.k, replace=False,
                                             p=self.pop)]
            w = rng.uniform(0.5, 2.0, keys.shape[0]).astype(np.float32)
            out.append((keys, w.astype(np.float64)))
        return out

    def keep(self, i: int) -> bool:
        return np.random.default_rng([self.seed, 12, i]).random() \
            < self.share

    def exprs(self, call: list) -> list:
        from repro.core import Assoc, lazy
        T = self.dep.table
        return [T[:, f"{self.band}*,"]
                * lazy(Assoc(keys, np.full(keys.shape[0], f"q{j}"), w))
                for j, (keys, w) in enumerate(call)]

    def call(self, call: list):
        from repro.core import expr
        return expr.eval_batch(self.exprs(call))

    def warm(self) -> None:
        for i in range(WARM_CALLS):
            self.call(self.draw(i))

    def _take(self) -> int:
        with self._lock:
            i = self._next
            self._next += 1
        return i

    def _client(self, window) -> None:
        from jax.profiler import TraceAnnotation
        tracer = self.dep.tracer if self.traced else None
        while window.open():
            i = self._take()
            call = self.draw(i)
            keep = self.keep(i)
            with TraceAnnotation("bench.chains_call"):
                if tracer is not None:
                    root = tracer.start("bench.chains_call")
                    with root:
                        out = self.call(call)
                    self.spans.append(tracer.spans(root.trace_id))
                else:
                    out = self.call(call)
            t = time.perf_counter()
            with self._lock:
                self.n_calls += 1
                self.n_chains += len(out)
                self.t_last = t if self.t_last is None else max(self.t_last,
                                                                t)
                self.done.append(i)
                if keep:
                    self.kept[i] = out

    def threads(self, window):
        return [self._client] * int(self.spec["clients"])

    @property
    def n_attempted(self) -> int:
        return self.n_calls

    def work(self) -> list:
        """(nnz, rows, cols, b) of the SpMM of every completed call."""
        return [self.dep.chain_work(self.draw(i)) for i in self.done]

    def use_control(self) -> None:
        self.kept = {i: [self.dep.control_chain(k, w)
                         for k, w in self.draw(i)] for i in self.kept}

    def check(self) -> list:
        ref = self.dep.reference()
        rows_bad, rel = 0, 0.0
        for i, outs in sorted(self.kept.items()):
            for (keys, w), got in zip(self.draw(i), outs):
                b, r = compare_chain(got, *ref.chain(keys, w))
                rows_bad += b
                rel = max(rel, r)
        n = sum(len(o) for o in self.kept.values())
        return [("chain_rows_bad", rows_bad, "chain_rows_bad"),
                ("chain_rel_err", rel, "chain_rel_err"),
                ("chains_checked", n, None)]


def compare_chain(got, rows_ref: np.ndarray, vals_ref: np.ndarray):
    """(rows that differ, widest relative error over common rows)."""
    r, _, v = got.triples()
    r = np.asarray(r)
    v = np.asarray(v, np.float64)
    if r.shape == rows_ref.shape and np.array_equal(r, rows_ref):
        bad, ig, ir = 0, slice(None), slice(None)
    else:
        bad = int(np.setxor1d(r, rows_ref).shape[0])
        _, ig, ir = np.intersect1d(r, rows_ref, return_indices=True)
    if v[ig].shape[0] == 0:
        return bad, 0.0
    err = np.abs(v[ig] - vals_ref[ir]) / np.abs(vals_ref[ir])
    return bad, float(err.max())


STREAM = Chains
