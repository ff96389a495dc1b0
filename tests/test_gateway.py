"""Serving gateway: endpoint families on memory and net backends, auth,
rate limiting (429 + Retry-After), the degree guard as 413, write-rate
admission, background jobs, SSE streaming, the unified stats snapshot —
and concurrent mixed load: reader threads hammering cached queries while
the WriterPool ingests, with a rate-limited tenant never blocking an
admitted one."""
import json
import os
import subprocess
import sys
import threading
import time
import http.client

import numpy as np
import pytest

from repro.core.assoc import Assoc
from repro.db import DB, put
from repro.serve import (Gateway, QueueFull, RateLimited, RateLimiter,
                        Tenant, TokenAuth, TokenBucket)
from repro.serve.app import synthetic_incidence


@pytest.fixture(scope="module")
def capture():
    """One synthetic traffic incidence shared by every gateway."""
    return synthetic_incidence(seed=3, duration=20.0, n_hosts=64, n_bots=6)


TOKENS = {
    "tok-a": Tenant("alice", rate=1000.0, burst=2000.0),
    "tok-b": Tenant("bob", rate=0.5, burst=2.0),        # 2 requests, then 429
    "tok-z": Tenant("zeno", rate=1000.0, burst=2000.0, max_jobs=0),
}


def make_gateway(capture, backend="memory", **gw_kw):
    T = DB("Tedge", "TedgeT", "TedgeDeg", backend=backend,
           n_instances=2 if backend == "net" else 1,
           tablets_per_instance=2)
    put(T, capture)
    gw = Gateway(T, TokenAuth(TOKENS), stats_interval=0.1, **gw_kw)
    gw.start()
    return gw


@pytest.fixture
def gw(capture):
    g = make_gateway(capture)
    yield g
    g.stop()


@pytest.fixture(params=["memory", "net"])
def gw_any(request, capture):
    g = make_gateway(capture, backend=request.param)
    yield g
    g.stop()
    close = getattr(g.table.backend, "close", None)
    if close is not None:
        close()


def req(gw, method, path, token="tok-a", body=None, timeout=30):
    host, port = gw.address.split(":")
    c = http.client.HTTPConnection(host, int(port), timeout=timeout)
    headers = {}
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    raw = json.dumps(body).encode() if body is not None else None
    if raw is not None:
        headers["Content-Type"] = "application/json"
    c.request(method, path, body=raw, headers=headers)
    r = c.getresponse()
    data = r.read()
    hdrs = dict(r.getheaders())
    c.close()
    return r.status, (json.loads(data) if data else None), hdrs


def get(gw, path, token="tok-a"):
    return req(gw, "GET", path, token=token)


def wait_job(gw, jid, token="tok-a", deadline=60.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        s, d, _ = get(gw, f"/v1/jobs/{jid}", token=token)
        assert s == 200
        if d["status"] in ("done", "failed"):
            return d
        time.sleep(0.05)
    raise AssertionError(f"job {jid} never finished")


# ---------------------------------------------------------------------------
# Unit level: buckets, limiter, unified stats.
# ---------------------------------------------------------------------------

class TestRateLimitUnits:
    def test_token_bucket_refills(self):
        t = [0.0]
        b = TokenBucket(rate=2.0, burst=4.0, clock=lambda: t[0])
        assert [b.try_acquire() for _ in range(4)] == [0.0] * 4
        retry = b.try_acquire()
        assert retry == pytest.approx(0.5)      # 1 token at 2/s
        t[0] += 0.5
        assert b.try_acquire() == 0.0

    def test_limiter_isolates_tenants(self):
        lim = RateLimiter()
        a, b = Tenant("a", rate=1e6, burst=1e6), Tenant("b", rate=1.0,
                                                        burst=1.0)
        lim.acquire(b)
        with pytest.raises(RateLimited):
            lim.acquire(b)
        for _ in range(100):                    # b's rejections don't bill a
            lim.acquire(a)
        assert lim.stats()["n_rejected"] == 1

    def test_unified_stats_snapshot(self, capture):
        T = DB("Tedge", "TedgeT", "TedgeDeg", tablets_per_instance=2)
        T.put(capture, sync=False)              # through the WriterPool
        T.flush()
        T[:, "ip.dst|*,"].eval()
        T[:, "ip.dst|*,"].eval()
        assert T.stats["col"] == 1 and T.stats["cache_hit"] == 1  # mapping
        merged = T.stats()                                        # callable
        assert merged["routes"]["col"] == 1
        assert merged["cache"]["hits"] == 1
        assert merged["writers"]["n_written"] > 0
        assert merged["backend"]["kind"] == "EdgeStore"
        json.dumps(merged)                    # snapshot is JSON-serializable


# ---------------------------------------------------------------------------
# Endpoint families (memory + net backends).
# ---------------------------------------------------------------------------

class TestEndpoints:
    def test_all_families_on_both_backends(self, gw_any):
        gw = gw_any
        # family 1: query endpoints
        s, d, _ = get(gw, "/v1/topk?prefix=ip.dst|&k=5")
        assert s == 200 and len(d["hosts"]) == 5
        assert d["hosts"][0]["degree"] >= d["hosts"][-1]["degree"]
        s, d, _ = get(gw, "/v1/degree?prefix=ip.dst|")
        assert s == 200 and d["fit"]["alpha"] > 0 and "resid" not in d["fit"]
        # family 2: admission-limited scans
        s, d, _ = get(gw, "/v1/scan?axis=col&prefix=ip.dst|&max_cells=10")
        assert s == 200 and d["truncated"] and len(d["triples"]) == 10
        # family 3: async jobs
        s, d, _ = req(gw, "POST", "/v1/jobs", body={"kind": "degree_fit"})
        assert s == 200 and d["status"] == "queued"
        done = wait_job(gw, d["job"])
        assert done["status"] == "done"
        s, d, _ = get(gw, f"/v1/jobs/{done['job']}/result")
        assert s == 200 and d["result"]["fit"]["alpha"] > 0
        # family 4: live stats stream (raw SSE over the socket)
        host, port = gw.address.split(":")
        c = http.client.HTTPConnection(host, int(port), timeout=30)
        c.request("GET", "/v1/stream/stats?n=2",
                  headers={"Authorization": "Bearer tok-a"})
        r = c.getresponse()
        assert r.status == 200
        assert r.getheader("Content-Type") == "text/event-stream"
        frames = [l for l in r.read().decode().splitlines()
                  if l.startswith("data: ")]
        c.close()
        assert len(frames) == 2
        sample = json.loads(frames[0][len("data: "):])
        assert {"rows_written_window", "queue_depth",
                "writes_per_s"} <= set(sample)

    def test_topk_matches_degree_table(self, gw):
        s, d, _ = get(gw, "/v1/topk?prefix=ip.dst|&k=3")
        deg = gw.table.degree_assoc("ip.dst|")
        r, _, v = deg.triples()
        v = np.asarray(v, np.float64)
        best = r[np.argmax(v)]
        assert d["hosts"][0]["key"] == str(best)
        assert d["hosts"][0]["degree"] == float(v.max())

    def test_c2_and_scanners_json(self, gw):
        s, d, _ = get(gw, "/v1/c2?top_k=3")
        assert s == 200 and len(d["report"]["hosts"]) == 3
        assert isinstance(d["report"]["scores"][0], float)
        s, d, _ = get(gw, "/v1/scanners?min_fanout=16")
        assert s == 200 and d["report"]["min_fanout"] == 16

    def test_scan_selectors(self, gw):
        s, d, _ = get(gw, "/v1/scan?axis=row&start=000000000&stop=000000010")
        assert s == 200 and d["nnz"] > 0
        a_key = d["triples"][0][0]
        s, d2, _ = get(gw, f"/v1/scan?axis=row&keys={a_key},")
        assert s == 200 and all(t[0] == a_key for t in d2["triples"])

    def test_pagerank_job(self, gw):
        s, d, _ = req(gw, "POST", "/v1/jobs",
                      body={"kind": "pagerank",
                            "params": {"num_iters": 5, "top_k": 5}})
        assert s == 200
        done = wait_job(gw, d["job"])
        assert done["status"] == "done"
        s, d, _ = get(gw, f"/v1/jobs/{d['job']}/result")
        assert s == 200 and len(d["result"]["nodes"]) == 5
        ranks = [n["rank"] for n in d["result"]["nodes"]]
        assert ranks == sorted(ranks, reverse=True)


    def test_traced_pagerank_job_owns_its_stages(self, gw):
        s, d, hdrs = req(gw, "POST", "/v1/jobs?trace=1",
                         body={"kind": "pagerank",
                               "params": {"num_iters": 5, "top_k": 5}})
        assert s == 200
        tid = hdrs["X-Trace-Id"]
        assert wait_job(gw, d["job"])["status"] == "done"
        s, d, _ = get(gw, f"/v1/trace/{tid}")
        assert s == 200
        root = d["tree"]
        assert root["name"] == "POST /v1/jobs"
        (job,) = [c for c in root["children"] if c["name"] == "job.pagerank"]
        assert [c["name"] for c in job["children"]] == [
            "analytics.pagerank.scan", "analytics.pagerank.adjacency",
            "analytics.pagerank.device"]
        names = set()

        def walk(node):
            names.add(node["name"])
            for c in node["children"]:
                walk(c)
        walk(job["children"][0])
        assert any(n.startswith("db.scan") for n in names)

    def test_stats_count_xla_compiles(self, gw):
        import jax
        s, st, _ = get(gw, "/v1/stats")
        assert s == 200
        n0 = st["xla_compiles"]
        jax.jit(lambda x: x * 3.25 + 1.0)(np.ones(7, np.float32))
        s, st, _ = get(gw, "/v1/stats")
        assert st["xla_compiles"] >= n0 + 1


# ---------------------------------------------------------------------------
# Error surface: 400/401/404/413/429/503.
# ---------------------------------------------------------------------------

class TestErrors:
    def test_health_needs_no_auth(self, gw):
        assert req(gw, "GET", "/healthz", token=None)[0] == 200

    def test_401_missing_and_bad_token(self, gw):
        assert get(gw, "/v1/topk", token=None)[0] == 401
        assert get(gw, "/v1/topk", token="wrong")[0] == 401

    def test_404_unknown_route_and_job(self, gw):
        assert get(gw, "/v1/nope")[0] == 404
        assert get(gw, "/v1/jobs/deadbeef")[0] == 404

    def test_400_bad_params(self, gw):
        assert get(gw, "/v1/topk?k=banana")[0] == 400
        assert get(gw, "/v1/scan?axis=diag")[0] == 400
        s, d, _ = req(gw, "POST", "/v1/jobs", body={"kind": "mine-bitcoin"})
        assert s == 400

    def test_413_degree_guard(self, capture):
        g = make_gateway(capture, degree_limit=3.0)
        try:
            s, d, _ = get(g, "/v1/scan?axis=col&prefix=ip.dst|")
            assert s == 413
            assert "degree guard" in d["error"]
        finally:
            g.stop()

    def test_429_rate_limit_sets_retry_after(self, gw):
        codes = [get(gw, "/v1/topk", token="tok-b")[0] for _ in range(4)]
        assert codes.count(429) >= 1            # bob: burst 2 at cost 1
        s, d, hdrs = get(gw, "/v1/topk", token="tok-b")
        assert s == 429 and float(hdrs["Retry-After"]) > 0

    def test_429_admission_on_write_pressure(self, gw):
        cache = gw.table.backend._scan_cache
        cache.full_scan_wps_limit = 0.0     # any trailing write trips it
        gw.table.put(Assoc("px,", "ip.dst|adm,", "1,"))
        s, d, hdrs = get(gw, "/v1/scan")
        assert s == 429 and "inadmissible" in d["error"]
        assert float(hdrs["Retry-After"]) > 0
        # selective scans stay admitted — only full-table work is shed
        assert get(gw, "/v1/scan?axis=col&prefix=ip.dst|&max_cells=5")[0] \
            == 200

    def test_503_tenant_job_bound(self, gw):
        s, d, _ = req(gw, "POST", "/v1/jobs", token="tok-z",
                      body={"kind": "degree_fit"})
        assert s == 503                         # zeno: max_jobs=0

    def test_job_result_202_while_pending(self, gw):
        gate = threading.Event()
        job = gw.jobs.submit("slow", lambda: gate.wait(10) or {"ok": 1},
                             TOKENS["tok-a"])
        try:
            s, _, _ = get(gw, f"/v1/jobs/{job.id}/result")
            assert s == 202
        finally:
            gate.set()


# ---------------------------------------------------------------------------
# Coherence: cache invalidation through the serving path.
# ---------------------------------------------------------------------------

class TestCoherence:
    def test_gateway_reads_see_new_writes(self, gw):
        key = "ip.dst|fresh-host"
        s, d, _ = get(gw, f"/v1/topk?prefix={key}")
        assert d["hosts"] == []
        gw.table.put(Assoc("q1,q2,", f"{key},{key},", "1,1,"), sync=False)
        s, d, _ = get(gw, f"/v1/topk?prefix={key}")    # read barrier drains
        assert d["hosts"][0]["degree"] == 2.0

    def test_cached_band_invalidated_by_write(self, gw):
        path = "/v1/scan?axis=col&prefix=ip.dst|cache-band&max_cells=99"
        get(gw, path)
        hits0 = gw.table.stats["cache_hit"]
        get(gw, path)
        assert gw.table.stats["cache_hit"] == hits0 + 1   # served hot
        gw.table.put(Assoc("q9,", "ip.dst|cache-band,", "1,"))
        s, d, _ = get(gw, path)                 # write evicted the band
        assert [t[:2] for t in d["triples"]] == [["q9", "ip.dst|cache-band"]]


# ---------------------------------------------------------------------------
# Concurrent mixed load — the tentpole's concurrency contract.
# ---------------------------------------------------------------------------

class TestMixedLoad:
    N_READERS = 8
    N_REQS = 12

    def test_readers_vs_ingest_no_torn_reads(self, gw):
        """N reader threads during active WriterPool ingest: every read
        succeeds, and the sum-combined degree of the hammered key is
        non-decreasing per thread (a torn read would regress it)."""
        stop = threading.Event()
        wrote = [0]

        def ingest():
            i = 0
            while not stop.is_set():
                rows = np.asarray([f"ld{i}-{j}" for j in range(50)], str)
                cols = np.asarray(["ip.dst|hammered"] * 50, str)
                gw.table.put(Assoc(rows, cols, np.asarray(["1"] * 50)),
                             sync=False)
                wrote[0] += 50
                i += 1
                time.sleep(0.005)

        failures = []

        def reader(tid):
            last = 0.0
            for _ in range(self.N_REQS):
                s, d, _ = get(gw, "/v1/topk?prefix=ip.dst|hammered&k=1")
                if s != 200:
                    failures.append((tid, s))
                    return
                if d["hosts"]:
                    deg = d["hosts"][0]["degree"]
                    if deg < last:
                        failures.append((tid, "regressed", last, deg))
                        return
                    last = deg

        t_ing = threading.Thread(target=ingest)
        t_ing.start()
        readers = [threading.Thread(target=reader, args=(i,))
                   for i in range(self.N_READERS)]
        for t in readers:
            t.start()
        for t in readers:
            t.join()
        stop.set()
        t_ing.join()
        assert failures == []
        gw.table.flush()
        assert gw.table.degree("ip.dst|hammered") == wrote[0]

    def test_rejected_tenant_never_blocks_admitted_one(self, gw):
        """bob hammers past his budget and collects 429s; alice's
        concurrent requests all succeed — rejection is per-tenant."""
        bob_codes, alice_codes = [], []

        def bob():
            for _ in range(25):
                bob_codes.append(get(gw, "/v1/topk", token="tok-b")[0])

        def alice():
            for _ in range(25):
                alice_codes.append(get(gw, "/v1/topk", token="tok-a")[0])

        threads = [threading.Thread(target=bob),
                   threading.Thread(target=alice)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert bob_codes.count(429) >= 1
        assert all(c in (200, 429) for c in bob_codes)
        assert alice_codes == [200] * 25

    def test_read_barrier_not_serialized_behind_ingest(self, gw):
        """A reader that arrives while ingest keeps streaming must wait
        only for writes that preceded it — with the old queue-empty
        barrier this read would block for the whole ingest run."""
        pool = gw.table.writer()
        stop = threading.Event()

        def ingest():
            i = 0
            while not stop.is_set():
                rows = np.asarray([f"rb{i}-{j}" for j in range(200)], str)
                gw.table.put(Assoc(rows,
                                   np.asarray(["ip.dst|rb"] * 200, str),
                                   np.asarray(["1"] * 200)), sync=False)
                i += 1

        t = threading.Thread(target=ingest)
        t.start()
        try:
            time.sleep(0.05)                # let the queue build up
            t0 = time.monotonic()
            s, _, _ = get(gw, "/v1/topk?prefix=ip.dst|rb&k=1")
            dt = time.monotonic() - t0
            assert s == 200
            assert dt < 5.0                 # snapshot wait, not queue-empty
        finally:
            stop.set()
            t.join()
            pool.flush()

    def test_concurrent_wave_lands_in_one_batch(self, capture):
        """More concurrent clients than socketserver's default listen
        backlog (5): every connection is accepted at once, so the whole
        wave reaches the coalescer inside one window."""
        g = make_gateway(capture, coalesce_window=0.5)
        try:
            cols = sorted({c for c in capture.col.tolist()
                           if c.startswith("ip.dst|")})[:12]
            gate = threading.Barrier(len(cols))
            status = []

            def reader(c):
                gate.wait()
                status.append(get(g, f"/v1/scan?axis=col&keys={c},")[0])

            threads = [threading.Thread(target=reader, args=(c,))
                       for c in cols]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert status == [200] * len(cols)
            st = g.coalescer.stats()
            assert st["n_batches"] == 1 and st["max_batch"] == len(cols)
        finally:
            g.stop()


def test_served_path_imports_leave_xla_flags_alone():
    """launch.dryrun/calibrate/hillclimb rewrite XLA_FLAGS when imported;
    nothing the gateway, the stores or the analytics import may pull
    them in."""
    code = ("import os, sys; before = os.environ.get('XLA_FLAGS'); "
            "import repro.serve, repro.db, repro.core, repro.analytics, "
            "repro.analytics.distributed, repro.stream, repro.pipeline, "
            "repro.kernels.spmm, repro.kernels.spmv, repro.device; "
            "print(os.environ.get('XLA_FLAGS') == before, "
            "sorted(m for m in sys.modules if m.startswith('repro.launch')))")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": src},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "True []"
