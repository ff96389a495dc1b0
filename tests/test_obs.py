"""Observability plane: metrics-registry and tracer units, the
/metrics endpoint, end-to-end trace propagation over the net backend
(gateway → planner → scan → per-shard RPC), the /metrics ↔ T.stats()
identity contract, and WriterPool.stats() coherence under live ingest."""
import gc
import http.client
import json
import threading
import time

import numpy as np
import pytest

from repro.core.expr import launch_counts
from repro.db import DB, EdgeStore, put
from repro.db.writer import WriterPool
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               REGISTRY, obj_label)
from repro.obs.trace import Tracer, current_ctx, span, stage, traced_iter
from repro.serve import Gateway, Tenant, TokenAuth
from repro.serve.app import synthetic_incidence


# ---------------------------------------------------------------------------
# Metrics units.
# ---------------------------------------------------------------------------

class TestCounter:
    def test_inc_and_value(self):
        c = Counter()
        c.inc()
        c.inc(41)
        assert c.value == 42

    def test_concurrent_incs_are_atomic(self):
        c = Counter()
        n_threads, per = 8, 10_000

        def hammer():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * per


class TestGauge:
    def test_set_inc_dec(self):
        g = Gauge()
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value == 6

    def test_set_function_reads_live(self):
        g = Gauge()
        box = [0]
        g.set_function(lambda: box[0])
        box[0] = 7
        assert g.value == 7.0

    def test_dying_owner_never_breaks_scrape(self):
        g = Gauge()
        g.set_function(lambda: (_ for _ in ()).throw(AttributeError("dead")))
        assert g.value == 0.0


class TestHistogram:
    def test_bucket_placement_and_cumulative(self):
        h = Histogram(base=1e-6, n_buckets=4)     # bounds 1,2,4,8 µs
        for v in (1e-6, 3e-6, 3e-6, 100.0):       # last is over-range
            h.observe(v)
        samples = list(h.samples())
        by_le = {extra[0][1]: val for sfx, extra, val in samples
                 if sfx == "_bucket"}
        assert by_le["1e-06"] == 1
        assert by_le["4e-06"] == 3                # cumulative
        assert by_le["8e-06"] == 3                # over-range not in finite
        assert by_le["+Inf"] == 4
        assert h.count == 4
        assert h.sum == pytest.approx(1e-6 + 6e-6 + 100.0)


class TestRegistry:
    def test_get_or_create_idempotent(self):
        reg = Registry()
        a = reg.counter("t_total", "help")
        b = reg.counter("t_total")
        assert a is b

    def test_kind_mismatch_rejected(self):
        reg = Registry()
        reg.counter("t_total")
        with pytest.raises(ValueError):
            reg.gauge("t_total")

    def test_label_schema_enforced(self):
        reg = Registry()
        fam = reg.counter("t_total", labels=("who",))
        with pytest.raises(ValueError):
            fam.labels(other="x")

    def test_weak_children_leave_with_owner(self):
        reg = Registry()
        fam = reg.counter("t_total", "h", labels=("who",))
        child = fam.labels(who="alice")
        child.inc(3)
        assert 'who="alice"' in reg.render()
        del child
        gc.collect()
        assert 'who="alice"' not in reg.render()

    def test_unlabeled_child_is_pinned(self):
        reg = Registry()
        reg.counter("t_total", "h").inc()
        gc.collect()
        assert "t_total 1" in reg.render()

    def test_render_format(self):
        reg = Registry()
        reg.counter("t_total", "things done").inc(2)
        reg.histogram("t_seconds", "latency", base=1e-3, n_buckets=2) \
           .observe(0.0015)
        text = reg.render()
        assert "# HELP t_total things done" in text
        assert "# TYPE t_total counter" in text
        assert "t_total 2" in text
        assert "# TYPE t_seconds histogram" in text
        assert 't_seconds_bucket{le="0.001"} 0' in text
        assert 't_seconds_bucket{le="0.002"} 1' in text
        assert 't_seconds_bucket{le="+Inf"} 1' in text
        assert "t_seconds_count 1" in text
        assert text.endswith("\n")

    def test_as_dict(self):
        reg = Registry()
        fam = reg.counter("t_total", labels=("who",))
        child = fam.labels(who="x")
        child.inc(9)
        assert reg.as_dict()[("t_total", (("who", "x"),))] == 9

    def test_obj_label_unique(self):
        assert obj_label("cache") != obj_label("cache")


# ---------------------------------------------------------------------------
# Tracer units.
# ---------------------------------------------------------------------------

class TestTracerUnits:
    def test_untraced_span_is_shared_noop(self):
        assert current_ctx() is None
        s1, s2 = span("a"), span("b", x=1)
        assert s1 is s2                     # no allocation on the hot path
        with s1 as s:
            s.tag(y=2)                      # all no-ops

    def test_nesting_records_parentage(self):
        tr = Tracer()
        with tr.start("root") as root:
            tid = root.trace_id
            with span("child"):
                with span("grandchild", k="v"):
                    pass
            with span("sibling"):
                pass
        recs = {r["name"]: r for r in tr.spans(tid)}
        assert recs["root"]["parent_id"] == 0
        rid = recs["root"]["span_id"]
        assert recs["child"]["parent_id"] == rid
        assert recs["sibling"]["parent_id"] == rid
        assert recs["grandchild"]["parent_id"] == recs["child"]["span_id"]
        assert recs["grandchild"]["tags"] == {"k": "v"}
        tree = tr.tree(tid)
        assert tree["name"] == "root"
        assert sorted(c["name"] for c in tree["children"]) == \
            ["child", "sibling"]

    def test_error_span_tagged(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.start("root") as root:
                tid = root.trace_id
                with span("boom"):
                    raise RuntimeError("kaput")
        recs = {r["name"]: r for r in tr.spans(tid)}
        assert recs["boom"]["tags"]["error"] == "RuntimeError: kaput"

    def test_traced_iter_records_one_span(self):
        tr = Tracer()
        with tr.start("root") as root:
            tid = root.trace_id
            assert list(traced_iter("gen", iter(range(3)), k="v")) == \
                [0, 1, 2]
        names = [r["name"] for r in tr.spans(tid)]
        assert names.count("gen") == 1

    def test_traced_iter_untraced_passthrough(self):
        assert list(traced_iter("gen", iter(range(3)))) == [0, 1, 2]

    def test_max_spans_drops_and_counts(self):
        tr = Tracer(max_spans=3)
        with tr.start("root") as root:
            tid = root.trace_id
            for i in range(10):
                with span(f"s{i}"):
                    pass
        assert len(tr.spans(tid)) == 3
        assert tr.tree(tid)["dropped"] == 8     # 7 children + the root
        assert tr.stats()["n_spans_dropped"] == 8

    def test_lru_trace_eviction(self):
        tr = Tracer(max_traces=2)
        tids = []
        for i in range(3):
            with tr.start(f"r{i}") as root:
                tids.append(root.trace_id)
        assert tr.tree(tids[0]) is None         # evicted
        assert tr.tree(tids[2]) is not None
        assert tr.stats()["live_traces"] == 2
        assert tr.stats()["n_traces"] == 3

    def test_slow_log_keeps_slowest(self):
        tr = Tracer(slow_log_size=2, slow_threshold_s=0.0)
        tr.note_slow("a", 0.0, 0.5)
        tr.note_slow("b", 0.0, 2.0)
        tr.note_slow("c", 0.0, 1.0)
        tr.note_slow("d", 0.0, 0.1)             # slower than nothing kept
        slow = tr.slow()
        assert [e["name"] for e in slow] == ["b", "c"]
        assert all(e["tree"] is None for e in slow)

    def test_traced_root_over_threshold_keeps_tree(self):
        tr = Tracer(slow_threshold_s=0.0)       # everything is "slow"
        with tr.start("root"):
            with span("child"):
                pass
        (entry,) = tr.slow()
        assert entry["tree"]["name"] == "root"
        assert entry["tree"]["children"][0]["name"] == "child"

    def test_note_slow_respects_threshold(self):
        tr = Tracer(slow_threshold_s=10.0)
        tr.note_slow("fast", 0.0, 0.01)
        assert tr.slow() == []

    def test_incoming_trace_id_sanitized(self):
        tr = Tracer()
        with tr.start("r", trace_id="abc-123_X") as root:
            assert root.trace_id == "abc-123_X"
        with tr.start("r", trace_id='ev"il\nid{}' + "x" * 100) as root:
            # capped at 64 raw chars, then the unsafe ones are dropped
            assert root.trace_id == "evilid" + "x" * 54
        with tr.start("r", trace_id="!!!") as root:
            assert len(root.trace_id) == 16     # nothing survived: minted


# ---------------------------------------------------------------------------
# Stages: spans that are always timed.
# ---------------------------------------------------------------------------

def _stage_hist(name):
    d = REGISTRY.as_dict()
    key = (("stage", name),)
    return (d.get(("repro_stage_seconds_count", key), 0),
            d.get(("repro_stage_seconds_sum", key), 0.0))


class TestStages:
    def test_untraced_stage_observes_its_histogram(self):
        assert current_ctx() is None
        n0, s0 = _stage_hist("test.stage.untraced")
        with stage("test.stage.untraced", k="v") as st:
            st.tag(more=1)                  # nowhere to go: a no-op
            time.sleep(0.002)
        n1, s1 = _stage_hist("test.stage.untraced")
        assert n1 == n0 + 1
        assert s1 - s0 >= 0.002

    def test_traced_stage_records_a_parented_span(self):
        tr = Tracer()
        n0, _ = _stage_hist("test.stage.traced")
        with tr.start("root") as root:
            tid = root.trace_id
            with span("outer"):
                with stage("test.stage.traced", k="v"):
                    with span("inner"):
                        pass
        assert _stage_hist("test.stage.traced")[0] == n0 + 1
        recs = {r["name"]: r for r in tr.spans(tid)}
        st = recs["test.stage.traced"]
        assert st["parent_id"] == recs["outer"]["span_id"]
        assert recs["inner"]["parent_id"] == st["span_id"]
        assert st["tags"] == {"k": "v"}

    def test_stage_exception_is_tagged_and_observed(self):
        tr = Tracer()
        n0, _ = _stage_hist("test.stage.boom")
        with pytest.raises(RuntimeError):
            with tr.start("root") as root:
                tid = root.trace_id
                with stage("test.stage.boom"):
                    raise RuntimeError("kaput")
        with pytest.raises(ValueError):
            with stage("test.stage.boom"):  # untraced: observed too
                raise ValueError("no")
        assert _stage_hist("test.stage.boom")[0] == n0 + 2
        recs = {r["name"]: r for r in tr.spans(tid)}
        assert recs["test.stage.boom"]["tags"]["error"] == \
            "RuntimeError: kaput"

    def test_stage_children_are_pinned(self):
        with stage("test.stage.pinned"):
            pass
        gc.collect()
        assert _stage_hist("test.stage.pinned")[0] >= 1

    def test_stages_are_annotated_on_the_profiler_host_plane(self,
                                                             tmp_path):
        import jax
        from jax.profiler import ProfileData
        tr = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with stage("test.stage.annotated"):
                jax.numpy.ones(4).block_until_ready()
            with tr.start("test.root.annotated"):
                with span("test.span.annotated"):
                    pass
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        names = {e.name for p in ProfileData.from_file(str(path)).planes
                 if p.name.startswith("/host") for ln in p.lines
                 for e in ln.events}
        assert {"test.stage.annotated", "test.root.annotated",
                "test.span.annotated"} <= names

    def test_obs_imports_without_jax(self):
        import subprocess
        import sys
        code = ("import sys\n"
                "class NoJax:\n"
                "    def find_spec(self, name, path=None, target=None):\n"
                "        if name == 'jax' or name.startswith('jax.'):\n"
                "            raise ImportError('jax is blocked')\n"
                "sys.meta_path.insert(0, NoJax())\n"
                "import repro.obs\n"
                "from repro.obs import stage, Tracer\n"
                "with stage('x'):\n"
                "    pass\n"
                "with Tracer().start('r'):\n"
                "    with stage('y'):\n"
                "        pass\n"
                "assert 'jax' not in sys.modules\n"
                "print('ok')\n")
        import os
        import repro.obs
        src = os.path.dirname(os.path.dirname(
            os.path.dirname(repro.obs.__file__)))
        env = dict(os.environ, PYTHONPATH=src)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# WriterPool.stats() coherence under live ingest (the snapshot is taken
# under the pool lock, so pending/queue_depth can't tear mid-spill).
# ---------------------------------------------------------------------------

class TestWriterStatsCoherence:
    def test_stats_consistent_while_ingesting(self):
        db = EdgeStore(n_tablets=2)
        pool = WriterPool(db, spill_rows=64)
        n_blocks, rows = 60, 32
        stop = threading.Event()
        errors = []

        def ingest():
            try:
                for i in range(n_blocks):
                    r = np.asarray([f"r{i:03d}-{j}" for j in range(rows)])
                    c = np.asarray(["ip.src|x"] * rows)
                    v = np.asarray(["1"] * rows)
                    pool.submit(r, c, v)
            finally:
                stop.set()

        t = threading.Thread(target=ingest)
        t.start()
        last_written = 0
        while not stop.is_set() or t.is_alive():
            s = pool.stats()
            assert s["pending"] >= 0
            assert s["queue_depth"] >= 0
            assert s["n_written"] >= last_written    # monotone
            assert s["n_errors"] == 0
            last_written = s["n_written"]
            if not t.is_alive():
                break
        t.join()
        assert not errors
        pool.flush()
        assert pool.stats()["pending"] == 0
        assert pool.n_written == n_blocks * rows
        pool.close()


# ---------------------------------------------------------------------------
# Gateway integration: /metrics, trace propagation, identity contract.
# ---------------------------------------------------------------------------

TOKENS = {"tok-a": Tenant("alice", rate=1000.0, burst=2000.0)}


@pytest.fixture(scope="module")
def capture():
    return synthetic_incidence(seed=5, duration=10.0, n_hosts=32, n_bots=4)


def make_gateway(capture, backend="memory", **gw_kw):
    T = DB("Tedge", "TedgeT", "TedgeDeg", backend=backend,
           n_instances=2 if backend == "net" else 1,
           tablets_per_instance=2)
    put(T, capture, sync=False)     # async → the WriterPool exists
    T.flush()
    gw = Gateway(T, TokenAuth(TOKENS), stats_interval=0.1, **gw_kw)
    gw.start()
    return gw


def close_gateway(gw):
    gw.stop()
    close = getattr(gw.table.backend, "close", None)
    if close is not None:
        close()


def raw_get(gw, path, token="tok-a", headers=None):
    host, port = gw.address.split(":")
    c = http.client.HTTPConnection(host, int(port), timeout=30)
    h = dict(headers or {})
    if token is not None:
        h["Authorization"] = f"Bearer {token}"
    c.request("GET", path, headers=h)
    r = c.getresponse()
    data = r.read()
    hdrs = dict(r.getheaders())
    c.close()
    return r.status, data, hdrs


def get_json(gw, path, token="tok-a", headers=None):
    status, data, hdrs = raw_get(gw, path, token=token, headers=headers)
    return status, (json.loads(data) if data else None), hdrs


def tree_paths(tree, depth=1):
    """Flatten a span tree into (name, depth) pairs."""
    out = [(tree["name"], depth)]
    for child in tree.get("children", ()):
        out.extend(tree_paths(child, depth + 1))
    return out


@pytest.fixture(scope="module")
def net_gw(capture):
    # coalescing off so the traced request's own thread runs the planner
    g = make_gateway(capture, backend="net", coalesce_window=0.0)
    yield g
    close_gateway(g)


class TestMetricsEndpoint:
    def test_scrape_is_unauthenticated_prometheus_text(self, net_gw):
        s, d, _ = get_json(net_gw, "/v1/topk?k=5")      # traffic first
        assert s == 200
        status, body, hdrs = raw_get(net_gw, "/metrics", token=None)
        assert status == 200
        assert hdrs["Content-Type"].startswith("text/plain")
        text = body.decode()
        # one sample from every layer, per the acceptance checklist
        assert "repro_cache_hits_total{" in text or \
            "repro_cache_misses_total{" in text
        assert "repro_writer_written_total{" in text
        assert "repro_rpc_total{" in text
        assert "# TYPE repro_http_requests_total counter" in text
        assert 'repro_http_requests_total{route="/v1/topk",status="200"}' \
            in text
        assert 'repro_http_request_seconds_bucket{route="/v1/topk",le=' \
            in text

    def test_http_metrics_use_route_pattern_not_raw_path(self, net_gw):
        s, d, _ = get_json(net_gw, "/v1/jobs/nonexistent")
        assert s == 404
        _, body, _ = raw_get(net_gw, "/metrics", token=None)
        text = body.decode()
        assert 'route="/v1/jobs/{id}"' in text           # bounded label
        assert 'route="/v1/jobs/nonexistent"' not in text


class TestTracePropagation:
    def test_trace_spans_gateway_to_shard_rpc(self, net_gw):
        s, d, hdrs = get_json(net_gw, "/v1/scan?prefix=ip.src|&trace=1")
        assert s == 200
        tid = hdrs.get("X-Trace-Id")
        assert tid
        s, d, _ = get_json(net_gw, f"/v1/trace/{tid}")
        assert s == 200 and d["trace"] == tid
        flat = tree_paths(d["tree"])
        names = {n for n, _ in flat}
        assert d["tree"]["name"] == "GET /v1/scan"       # gateway root
        assert "planner.eval" in names                   # planner layer
        assert "db.scan" in names                        # binding layer
        assert any(n.startswith("rpc.") for n in names)  # shard RPC layer
        depth = {n: dep for n, dep in flat}
        assert depth["planner.eval"] == 2
        assert depth["db.scan"] == 3
        assert max(dep for n, dep in flat
                   if n.startswith("rpc.")) >= 4          # ≥ 4 layers deep
        # per-shard RPCs carry their shard address as a tag
        recs = net_gw.tracer.spans(tid)
        rpc_shards = {r["tags"].get("shard") for r in recs
                      if r["name"].startswith("rpc.")}
        addrs = {i.address for i in net_gw.table.backend.instances}
        assert rpc_shards <= addrs and rpc_shards

    def test_incoming_trace_id_is_honored(self, net_gw):
        s, d, hdrs = get_json(net_gw, "/v1/topk?k=3",
                              headers={"X-Trace-Id": "my-trace-42"})
        assert s == 200
        assert hdrs["X-Trace-Id"] == "my-trace-42"
        s, d, _ = get_json(net_gw, "/v1/trace/my-trace-42")
        assert s == 200
        assert d["tree"]["name"] == "GET /v1/topk"

    def test_unknown_trace_404(self, net_gw):
        s, d, _ = get_json(net_gw, "/v1/trace/deadbeef00000000")
        assert s == 404

    def test_slow_log_endpoint_shape(self, net_gw):
        s, d, _ = get_json(net_gw, "/v1/debug/slow")
        assert s == 200
        assert d["threshold_s"] == net_gw.tracer.slow_threshold_s
        assert isinstance(d["slow"], list)

    def test_stats_exposes_tracer(self, net_gw):
        s, d, _ = get_json(net_gw, "/v1/stats")
        assert s == 200
        assert d["trace"]["max_traces"] == 256

    def test_sampling_off_records_zero_spans(self, capture):
        gw = make_gateway(capture)      # trace_sample defaults to 0.0
        try:
            for _ in range(3):
                s, _, hdrs = get_json(gw, "/v1/topk?k=3")
                assert s == 200
                assert "X-Trace-Id" not in hdrs
            assert gw.tracer.stats()["n_spans"] == 0
            assert gw.tracer.stats()["n_traces"] == 0
        finally:
            close_gateway(gw)


class TestStatsMetricsIdentity:
    """/metrics and T.stats() read the SAME underlying counts — locked
    here for every shared counter (the satellite-6 contract)."""

    def test_cache_and_writer_counters_identical(self, capture):
        T = DB("Tedge", "TedgeT", "TedgeDeg", tablets_per_instance=2)
        put(T, capture, sync=False)
        T.flush()
        T[:, "ip.src|*,"].eval()
        T[:, "ip.src|*,"].eval()        # a hit
        T[:, "ip.dst|*,"].eval()        # a miss
        st = T.stats()
        d = REGISTRY.as_dict()
        cache = T._cache
        pool = T.backend._writer_pool
        ck = (("cache", cache.metrics_label),)
        pk = (("pool", pool.metrics_label),)
        assert st["cache"]["hits"] == \
            d[("repro_cache_hits_total", ck)] > 0
        assert st["cache"]["misses"] == \
            d[("repro_cache_misses_total", ck)] > 0
        assert st["cache"]["evictions"] == \
            d[("repro_cache_evictions_total", ck)]
        assert st["writers"]["n_written"] == \
            d[("repro_writer_written_total", pk)] > 0
        assert st["writers"]["n_retried"] == \
            d[("repro_writer_retried_total", pk)]
        assert st["writers"]["tap_errors"] == \
            d[("repro_writer_tap_errors_total", pk)]

    def test_rpc_counters_identical(self, net_gw):
        get_json(net_gw, "/v1/topk?k=3")
        st = net_gw.table.stats()
        d = REGISTRY.as_dict()
        total = 0
        for inst in net_gw.table.backend.instances:
            key = (("shard", inst.address),
                   ("client", inst.metrics_label))
            assert inst.n_rpcs == d[("repro_rpc_total", key)] > 0
            total += inst.n_rpcs
        assert st["backend"]["n_rpcs"] == total

    def test_kernel_launch_counters_identical(self):
        d = REGISTRY.as_dict()
        for kernel, count in launch_counts().items():
            assert d[("repro_kernel_launches_total",
                      (("kernel", kernel),))] == count
