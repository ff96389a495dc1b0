"""Persistent LSM backend: WAL replay after simulated crashes, spill +
compaction combiner semantics, scan agreement with EdgeStore, registry
dispatch, binding consistency, and the end-to-end kill-after-flush
pipeline recovery acceptance run."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.assoc import Assoc
from repro.db import (DB, BACKENDS, EdgeStore, LSMMultiInstanceDB,
                      LSMStore, MultiInstanceDB, bind, make_backend, put)
from repro.pipeline import PipelineConfig, TrafficConfig, run_pipeline


def rand_triples(seed, n=200, n_rows=40, n_cols=12):
    rng = np.random.default_rng(seed)
    r = np.asarray([f"p{i:03d}" for i in rng.integers(0, n_rows, n)])
    c = np.asarray([f"ip.dst|{i}" if i % 2 else f"ip.src|{i}"
                    for i in rng.integers(0, n_cols, n)])
    v = rng.integers(0, 9, n).astype(str)
    return r, c, v


def snapshot(store, transpose=False):
    return [(k, tuple(sorted(cells.items())))
            for k, cells in store.scan_everything(transpose=transpose)]


def degrees(store):
    return {k: v for k, v in store.degree_items()}


class TestWALRecovery:
    def test_reopen_replays_synced_writes(self, tmp_path):
        d = str(tmp_path / "lsm")
        s = LSMStore(d)
        r, c, v = rand_triples(0)
        s.put_triples(r, c, v)
        s.sync()
        # crash: abandon without close(); reopen from disk
        s2 = LSMStore(d)
        assert snapshot(s2) == snapshot(s)
        assert snapshot(s2, transpose=True) == snapshot(s, transpose=True)
        assert degrees(s2) == degrees(s)
        assert s2.n_entries == s.n_entries == len(r)

    def test_torn_wal_tail_truncated(self, tmp_path):
        """Kill *before* fsync completes: the WAL's last frame is torn;
        replay keeps every whole frame and drops the tail."""
        d = str(tmp_path / "lsm")
        s = LSMStore(d)
        s.put_triples(*[np.asarray(x) for x in
                        (["p1"], ["ip.dst|a"], ["1"])])
        s.sync()
        s.put_triples(*[np.asarray(x) for x in
                        (["p2"], ["ip.dst|b"], ["1"])])
        s.close()
        wal = os.path.join(d, "wal.log")
        with open(wal, "r+b") as f:
            f.seek(0, os.SEEK_END)
            f.truncate(f.tell() - 3)        # tear the second frame
        s2 = LSMStore(d)
        assert s2.row("p1") == {"ip.dst|a": "1"}
        assert s2.row("p2") == {}           # torn frame dropped
        assert s2.degree("ip.dst|b") == 0.0
        # and the store keeps working after recovery
        s2.put_triples(*[np.asarray(x) for x in
                         (["p3"], ["ip.dst|c"], ["1"])])
        s2.sync()
        assert LSMStore(d).row("p3") == {"ip.dst|c": "1"}

    def test_corrupt_frame_stops_replay(self, tmp_path):
        d = str(tmp_path / "lsm")
        s = LSMStore(d)
        s.put_triples(*[np.asarray(x) for x in
                        (["p1"], ["ip.dst|a"], ["1"])])
        s.put_triples(*[np.asarray(x) for x in
                        (["p2"], ["ip.dst|b"], ["1"])])
        s.close()
        wal = os.path.join(d, "wal.log")
        size = os.path.getsize(wal)
        with open(wal, "r+b") as f:         # flip a payload byte in frame 2
            f.seek(size - 6)
            b = f.read(1)
            f.seek(size - 6)
            f.write(bytes([b[0] ^ 0xFF]))
        s2 = LSMStore(d)
        assert s2.row("p1") == {"ip.dst|a": "1"}
        assert s2.row("p2") == {}

    def test_wal_resets_after_spill(self, tmp_path):
        """Spilled mutations live in the run, not the WAL — reopen must
        not double-apply them."""
        d = str(tmp_path / "lsm")
        s = LSMStore(d, memtable_limit=50)
        r, c, v = rand_triples(1, n=120)
        s.put_triples(r[:60], c[:60], v[:60])   # triggers a spill
        assert s.n_runs >= 1
        s.put_triples(r[60:], c[60:], v[60:])
        s.sync()
        s2 = LSMStore(d)
        assert degrees(s2) == degrees(s)
        assert s2.n_entries == 120


class TestSpillCompaction:
    def test_spill_preserves_scans_and_degrees(self, tmp_path):
        s = LSMStore(str(tmp_path / "a"), memtable_limit=10 ** 9)
        e = EdgeStore(n_tablets=4)
        r, c, v = rand_triples(2)
        s.put_triples(r, c, v)
        e.put_triples(r, c, v)
        before = snapshot(s)
        s.spill()
        assert s.n_runs == 1 and s._mem.n_mutations == 0
        assert snapshot(s) == before == snapshot(e)
        assert degrees(s) == degrees(e)

    def test_compaction_sums_degrees_and_keeps_newest_cell(self, tmp_path):
        s = LSMStore(str(tmp_path / "a"))
        for val in ("old", "mid", "new"):
            s.put_triples(np.asarray(["p1"]), np.asarray(["ip.dst|a"]),
                          np.asarray([val]))
            s.spill()                        # one run per version
        assert s.n_runs == 3
        s.compact()
        assert s.n_runs == 1
        assert s.row("p1") == {"ip.dst|a": "new"}    # newest run won
        assert s.degree("ip.dst|a") == 3.0           # combiner summed
        assert s.n_entries == 3

    def test_auto_compaction_bounds_runs(self, tmp_path):
        s = LSMStore(str(tmp_path / "a"), memtable_limit=5, max_runs=3)
        r, c, v = rand_triples(3, n=200)
        for lo in range(0, 200, 5):
            s.put_triples(r[lo:lo + 5], c[lo:lo + 5], v[lo:lo + 5])
        assert s.n_runs <= 4                 # bounded by max_runs + 1
        e = EdgeStore(n_tablets=2)
        e.put_triples(r, c, v)
        assert snapshot(s) == snapshot(e)
        assert degrees(s) == degrees(e)

    def test_reopen_after_compaction(self, tmp_path):
        d = str(tmp_path / "a")
        s = LSMStore(d)
        r, c, v = rand_triples(4)
        s.put_triples(r, c, v)
        s.spill()
        s.put_triples(r, c, v)               # second tier re-puts all
        s.spill()
        s.compact()
        expected = snapshot(s)
        s.close()
        s2 = LSMStore(d)
        assert snapshot(s2) == expected
        assert s2.degree(c[0]) == s.degree(c[0])


class TestScanAgreement:
    """Property-style cross-check: LSMStore and EdgeStore are
    observationally identical over identical triples."""

    @pytest.mark.parametrize("seed", range(5))
    def test_scans_agree_with_edgestore(self, tmp_path, seed):
        s = LSMStore(str(tmp_path / f"lsm{seed}"),
                     memtable_limit=70)       # force mixed mem/run reads
        e = EdgeStore(n_tablets=3)
        r, c, v = rand_triples(seed, n=250)
        for lo in range(0, 250, 50):          # batched, interleaved spills
            s.put_triples(r[lo:lo + 50], c[lo:lo + 50], v[lo:lo + 50])
            e.put_triples(r[lo:lo + 50], c[lo:lo + 50], v[lo:lo + 50])
        for t in (False, True):
            assert snapshot(s, t) == snapshot(e, t)
            lo_k, hi_k = ("p005", "p025") if not t else ("ip.dst|", "ip.src|5")
            assert list(s.scan_key_range(lo_k, hi_k, transpose=t)) == \
                list(e.scan_key_range(lo_k, hi_k, transpose=t))
            assert list(s.scan_prefix("p01" if not t else "ip.dst|",
                                      transpose=t)) == \
                list(e.scan_prefix("p01" if not t else "ip.dst|",
                                   transpose=t))
            assert list(s.scan_keys([r[0], r[7], "absent"], transpose=t)) \
                == list(e.scan_keys([r[0], r[7], "absent"], transpose=t))
        assert degrees(s) == degrees(e)
        assert sorted(s.keys_with_prefix("ip.dst|")) == \
            sorted(e.keys_with_prefix("ip.dst|"))
        for key in set(c[:20]):
            assert s.degree(key) == e.degree(key)
        assert s.connections("3") == e.connections("3")

    def test_put_degree_matches_edgestore(self, tmp_path):
        s = LSMStore(str(tmp_path / "lsm"))
        e = EdgeStore(n_tablets=2)
        Edeg = Assoc("ip.dst|a,ip.dst|b,", "degree,degree,",
                     np.asarray([3.0, 4.0]))
        s.put_degree(Edeg)
        e.put_degree(Edeg)
        assert degrees(s) == degrees(e)


class TestRegistry:
    def test_memory_dispatch(self):
        assert isinstance(DB("Tedge").backend, EdgeStore)
        assert isinstance(DB("Tedge", n_instances=3).backend,
                          MultiInstanceDB)

    def test_lsm_dispatch(self, tmp_path):
        T = DB("Tedge", backend="lsm", path=str(tmp_path / "a"))
        assert isinstance(T.backend, LSMStore)
        M = DB("Tedge", backend="lsm", path=str(tmp_path / "b"),
               n_instances=2)
        assert isinstance(M.backend, LSMMultiInstanceDB)
        assert len(M.backend.instances) == 2
        assert os.path.isdir(str(tmp_path / "b" / "db1"))

    def test_lsm_requires_path(self):
        with pytest.raises(ValueError, match="path"):
            DB("Tedge", backend="lsm")

    def test_memory_rejects_path(self, tmp_path):
        with pytest.raises(ValueError, match="volatile"):
            DB("Tedge", backend="memory", path=str(tmp_path))

    def test_unknown_backend(self):
        with pytest.raises(ValueError, match="unknown backend"):
            DB("Tedge", backend="nope")

    def test_backend_options_forwarded(self, tmp_path):
        T = DB("Tedge", backend="lsm", path=str(tmp_path / "a"),
               memtable_limit=7)
        assert T.backend.memtable_limit == 7

    def test_custom_registration(self):
        BACKENDS["_test"] = lambda **kw: EdgeStore(n_tablets=1)
        try:
            assert isinstance(make_backend("_test"), EdgeStore)
        finally:
            del BACKENDS["_test"]


class TestBindingOnLSM:
    def test_query_after_put_consistency(self, tmp_path):
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm",
               path=str(tmp_path / "a"), n_instances=2)
        E = Assoc("p1,p1,p2,p3,", "ip.dst|a,ip.src|b,ip.dst|a,ip.dst|c,",
                  "1,1,1,1,")
        put(T, E, sync=False)
        # query-after-put: the binding read flushes (and fsyncs) first
        assert T[:, "ip.dst|*,"].eval().nnz == 3
        assert T.degree("ip.dst|a") == 2.0
        assert T["p1,", :].eval().nnz == 2
        assert T["p1,:,p2,", :].eval().nnz == 3
        r, _, v = T.degree_assoc("ip.dst|").triples()
        assert dict(zip(r, np.asarray(v, float)))["ip.dst|c"] == 1.0
        T.close()

    def test_scan_cache_invalidation_on_lsm(self, tmp_path):
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm",
               path=str(tmp_path / "a"))
        put(T, Assoc("p1,", "ip.dst|a,", "1,"))
        assert T[:, "ip.dst|*,"].eval().nnz == 1
        T.backend.put(Assoc("p2,", "ip.dst|a,", "1,"))   # direct store put
        assert T[:, "ip.dst|*,"].eval().nnz == 2         # evicted, rescanned
        T.close()

    def test_close_syncs_without_pool(self, tmp_path):
        """Sync puts never create a writer pool; close() must still be
        a commit point on a durable backend."""
        d = str(tmp_path / "a")
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=d)
        put(T, Assoc("p1,", "ip.dst|a,", "1,"))   # sync=True, poolless
        T.close()
        assert T.backend.n_syncs >= 1

    def test_flush_is_durability_point(self, tmp_path):
        d = str(tmp_path / "a")
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=d)
        put(T, Assoc("p1,", "ip.dst|a,", "1,"), sync=False)
        T.flush()
        assert T.backend.n_syncs >= 1
        # abandon (simulated crash) and reopen: the flushed write survived
        T2 = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=d)
        assert T2[:, :].eval().nnz == 1
        assert T2.degree("ip.dst|a") == 1.0


class TestCrossProcessRouting:
    CHILD = ("import sys; sys.path.insert(0, sys.argv[2]); "
             "from repro.db import DB, put; "
             "from repro.core.assoc import Assoc; "
             "T = DB('Tedge', 'TedgeT', 'TedgeDeg', backend='lsm', "
             "path=sys.argv[1], n_instances=4); "
             "put(T, Assoc('p1,', 'ip.dst|a,', sys.argv[3] + ',')); "
             "T.close()")

    def run_child(self, dbdir, value, seed):
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        subprocess.run(
            [sys.executable, "-c", self.CHILD, dbdir, src, value],
            env={**os.environ, "PYTHONHASHSEED": seed},
            check=True, timeout=120)

    def test_instance_placement_stable_across_processes(self, tmp_path):
        """Routing uses a process-stable hash: updates to one row from
        differently-salted interpreters land in the same instance
        directory, so last-write-wins survives restarts."""
        d = str(tmp_path / "m")
        self.run_child(d, "old", "1")
        self.run_child(d, "new", "2")      # different hash salt
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=d,
               n_instances=4)
        assert sum(1 for i in T.backend.instances if i.n_entries) == 1
        _, _, v = T["p1,", :].eval().triples()
        assert list(v) == ["new"]


_CRASH_CHILD = r"""
import os, sys
sys.path.insert(0, sys.argv[4])
from repro.db import DB
from repro.pipeline import PipelineConfig, TrafficConfig, run_pipeline

workdir, dbdir, backend = sys.argv[1], sys.argv[2], sys.argv[3]
cfg = PipelineConfig(workdir=workdir, n_files=2, duration_per_file_s=1.0,
                     traffic=TrafficConfig(n_hosts=64, pkt_rate=500.0,
                                           seed=6), n_workers=2)
T = DB("Tedge", "TedgeT", "TedgeDeg", backend=backend,
       path=(dbdir if backend == "lsm" else None), n_instances=2)
stats = run_pipeline(cfg, T.backend)
print("ENTRIES", stats["db_entries"], flush=True)
os._exit(17)   # kill after the flush barrier: no close(), no atexit
"""


class TestPipelineCrashRecovery:
    def test_lsm_recovers_full_ingest_after_kill(self, tmp_path):
        """Acceptance: full stage-6 ingest through the async writer pool
        against backend='lsm', process killed right after the flush
        barrier; reopening recovers every entry — counts and degree sums
        match an identical in-memory run exactly."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        dbdir = str(tmp_path / "lsmdb")
        out = subprocess.run(
            [sys.executable, "-c", _CRASH_CHILD, str(tmp_path / "w_lsm"),
             dbdir, "lsm", src],
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 17, out.stderr
        entries = int(out.stdout.split("ENTRIES")[1].split()[0])
        assert entries > 0

        # reference: the same pipeline against the in-memory backend
        mem = MultiInstanceDB(n_instances=2, tablets_per_instance=4)
        cfg = PipelineConfig(workdir=str(tmp_path / "w_mem"), n_files=2,
                             duration_per_file_s=1.0,
                             traffic=TrafficConfig(n_hosts=64,
                                                   pkt_rate=500.0, seed=6),
                             n_workers=2)
        run_pipeline(cfg, mem)

        # reopen the killed store: WAL replay must recover everything
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=dbdir,
               n_instances=2)
        assert T.n_entries == entries == mem.n_entries
        assert degrees(T.backend) == degrees(mem)
        # column-query analytics agree cell-for-cell
        a = T[:, "ip.dst|*,"].eval()
        b = bind(mem, cache_ttl=0)[:, "ip.dst|*,"].eval()
        assert a.triples()[0].tolist() == b.triples()[0].tolist()
        assert a.triples()[1].tolist() == b.triples()[1].tolist()
        # journal committed at the barrier: a restart re-ingests nothing
        T2 = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=dbdir,
                n_instances=2)
        run_pipeline(dataclasses.replace(cfg,
                                         workdir=str(tmp_path / "w_lsm")),
                     T2.backend)
        assert T2.n_entries == entries


from _hyp import given, settings, st  # hypothesis, skipping when absent


class TestLSMProperties:
    @settings(max_examples=15, deadline=None)
    @given(trip=st.lists(st.tuples(st.integers(0, 15), st.integers(0, 6),
                                   st.integers(0, 3)),
                         min_size=1, max_size=60),
           limit=st.integers(1, 40))
    def test_random_triples_agree_with_edgestore(self, trip, limit,
                                                 tmp_path_factory):
        d = str(tmp_path_factory.mktemp("lsm"))
        s = LSMStore(d, memtable_limit=limit)
        e = EdgeStore(n_tablets=2)
        r = np.asarray([f"p{a:02d}" for a, _, _ in trip])
        c = np.asarray([f"f|{b}" for _, b, _ in trip])
        v = np.asarray([str(x) for _, _, x in trip])
        s.put_triples(r, c, v)
        e.put_triples(r, c, v)
        assert snapshot(s) == snapshot(e)
        assert snapshot(s, True) == snapshot(e, True)
        assert degrees(s) == degrees(e)
        s.sync()
        assert snapshot(LSMStore(d)) == snapshot(e)
