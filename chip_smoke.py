#!/usr/bin/env python3
"""End-to-end smoke of the served D4M analytics path on a TPU.

    python3 chip_smoke.py [--seed 0]
    python3 chip_smoke.py --chips 4

With no ``--chips`` option the script uses one chip.  It generates a
seeded synthetic capture (``repro.pipeline``), ingests it with ``put``
into ``DB("Tedge", "TedgeT", "TedgeDeg", backend="net", n_instances=2)``
(two in-process shard servers), and then:

* serves it through the ``Gateway`` over real HTTP: ``/v1/topk``,
  ``/v1/degree``, ``/v1/scan``, ``/v1/c2``, a wave of concurrent column
  scans that the coalescer folds into one batch, and a ``pagerank`` job;
* evaluates fused and solo matvec chains ``T[:, "ip.dst|*,"] * x``
  through ``eval_batch`` on the device, once on the COO path and once
  through the Pallas kernels;
* runs ``pagerank_table`` on an explicit one-device mesh.

Every answer is checked against a plain numpy/scipy reference built
from the same packet records.  ``--chips 4`` runs only the sharded
PageRank, over a four-device mesh, against the one-device run and the
reference.

The script refuses to run unless JAX's default backend is a TPU.  It
exits nonzero on the first failed check, and its last line of standard
output is the JSON record
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.device import enable_compile_cache  # noqa: E402

PACKETS = 1 << 20            # about 9.4M Tedge entries (9 fields/packet)
N_CHAINS = 8                 # fused matvec chains (one SpMM launch)
VEC_KEYS = 32                # destinations per fused query vector
N_WAVE = 8                   # concurrent /v1/scan column queries
COALESCE_WINDOW = 0.1        # s; wide enough to catch the whole wave
PR_ITERS = 20
PR_DAMPING = 0.85
TOKEN = "smoke-token"

# Each chain output is a single product 1 * w per packet row (every
# packet has exactly one ip.dst), and every w is float32-exact, so a
# float32 device path reproduces it exactly; one float32 ulp of slack.
# One bf16 pass on the MXU would be off by up to 2^-9 and fails this.
CHAIN_RTOL = 2.0 ** -23
# PageRank ranks form a probability vector.  Each of the 20 iterations
# sums at most (number of hosts) float32 terms per node, in an order
# the device chooses; that leaves the float32 result about 1e-6 from
# the float64 reference in L1.  1e-4 is far below the error a wrong
# edge, weight or damping would cause (> 1e-2).
PR_L1_TOL = 1e-4
# The C2 score is log1p(fanin) * regularity * port_conc^2 in float32 on
# the device.  The TPU's float32 transcendentals are approximations, not
# correctly rounded (on a v5e the score differed from the float64
# formula by 3.5e-5 relative); a wrong factor moves it by far more.
C2_SCORE_RTOL = 1e-4


class Mismatch(AssertionError):
    """An answer disagreed with the reference, or a request failed."""


def say(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Mismatch(what)
    say(f"  match: {what}")


class CompileMeter:
    """Seconds spent getting executables (compiling, or loading from
    the persistent cache) and the cache's hits and misses, from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def report(self, phase: str) -> None:
        with self._lock:
            say(f"compile after {phase}: {self.seconds:.3f}s for "
                f"{self.compiles} programs; persistent cache "
                f"hits={self.hits} misses={self.misses}")


# ---------------------------------------------------------------------------
# Data and the plain reference.
# ---------------------------------------------------------------------------

def capture(seed: int, n_packets: int):
    """Seeded packet records at the pipeline's default traffic shape
    (4096 hosts, Zipf 1.3 destinations, 100k packets/s, injected C2)."""
    from repro.pipeline import TrafficConfig
    from repro.pipeline.pcap import synth_packets
    cfg = TrafficConfig(seed=seed)
    return synth_packets(cfg, n_packets / cfg.pkt_rate)


def incidence(rec):
    """The stage-5 incidence Assoc the pipeline ingests."""
    from repro.core.schema import parse_tsv, val2col
    from repro.pipeline.pcap import records_to_tsv
    return val2col(parse_tsv(records_to_tsv(rec)))


class Reference:
    """Answers computed with numpy/scipy straight from the records."""

    def __init__(self, rec):
        from repro.pipeline.pcap import ip_str
        n = rec.shape[0]
        self.n = n
        self.pid = np.char.zfill(np.arange(n).astype("U9"), 9)
        self.src_ip = ip_str(rec["src"])
        self.dst_ip = ip_str(rec["dst"])
        self.src_u = rec["src"].astype(np.uint64)
        self.dst_u = rec["dst"].astype(np.uint64)
        self.dport = rec["dport"].astype(np.uint64)
        self.dst_key = np.char.add("ip.dst|", self.dst_ip)
        self.dst_keys, self.dst_deg = np.unique(self.dst_key,
                                                return_counts=True)
        src_key = np.char.add("ip.src|", self.src_ip)
        self.src_keys, self.src_deg = np.unique(src_key, return_counts=True)

    def degrees(self, field: str) -> dict:
        keys, deg = ((self.dst_keys, self.dst_deg) if field == "ip.dst"
                     else (self.src_keys, self.src_deg))
        return dict(zip(keys.tolist(), deg.astype(float).tolist()))

    def column(self, key: str) -> set:
        """The (row, col) cells of one Tedge column."""
        return {(p, key) for p in self.pid[self.dst_key == key].tolist()}

    def c2_features(self) -> dict:
        """dst IP -> (distinct sources, Herfindahl index over dst ports)."""
        from repro.pipeline.pcap import ip_str
        pairs = np.unique((self.dst_u << np.uint64(32)) | self.src_u)
        d_of_pair, fanin = np.unique(pairs >> np.uint64(32),
                                     return_counts=True)
        dp, cnt = np.unique((self.dst_u << np.uint64(16)) | self.dport,
                            return_counts=True)
        d, inv = np.unique(dp >> np.uint64(16), return_inverse=True)
        tot = np.bincount(inv, weights=cnt)
        conc = np.bincount(inv, weights=cnt.astype(float) ** 2) / tot ** 2
        if not np.array_equal(d, d_of_pair):
            raise Mismatch("reference: port and source tables disagree "
                           "on the destination set")
        return dict(zip(ip_str(d.astype(np.uint32)).tolist(),
                        zip(fanin.tolist(), conc.tolist())))

    def chain(self, keys, w) -> dict:
        """Packet row -> x[dst] for the matvec T[:, 'ip.dst|*,'] * x."""
        pos = {k: i for i, k in enumerate(keys.tolist())}
        hit = np.isin(self.dst_key, keys)
        return {p: w[pos[k]] for p, k in zip(self.pid[hit].tolist(),
                                             self.dst_key[hit].tolist())}

    def pagerank(self) -> dict:
        """Power iteration in float64 with the served path's algorithm:
        uniform restart, dangling mass spread uniformly."""
        import scipy.sparse as sp
        from repro.pipeline.pcap import ip_str
        nodes = np.union1d(self.src_u, self.dst_u)
        s = np.searchsorted(nodes, self.src_u)
        d = np.searchsorted(nodes, self.dst_u)
        n = nodes.shape[0]
        A = sp.csr_matrix((np.ones(s.shape[0]), (s, d)), shape=(n, n))
        out_deg = np.asarray(A.sum(axis=1)).ravel()
        inv_deg = np.where(out_deg > 0, 1.0 / np.maximum(out_deg, 1e-300),
                           0.0)
        p = np.full(n, 1.0 / n)
        rank = p.copy()
        AT = A.T.tocsr()
        for _ in range(PR_ITERS):
            spread = AT @ (rank * inv_deg)
            dangling = rank[out_deg == 0].sum()
            rank = (1 - PR_DAMPING) * p + PR_DAMPING * (spread + dangling * p)
        return dict(zip(ip_str(nodes.astype(np.uint32)).tolist(),
                        rank.tolist()))


def pagerank_l1(keys, ranks, ref: dict) -> float:
    keys = np.asarray(keys).tolist()
    if sorted(keys) != sorted(ref):
        raise Mismatch("PageRank node set differs from the reference")
    got = np.asarray(ranks, np.float64)
    want = np.asarray([ref[k] for k in keys])
    return float(np.abs(got - want).sum())


# ---------------------------------------------------------------------------
# HTTP against the gateway.
# ---------------------------------------------------------------------------

def call(addr: str, method: str, path: str, body=None, quiet=False):
    host, port = addr.split(":")
    c = http.client.HTTPConnection(host, int(port), timeout=900)
    headers = {"Authorization": f"Bearer {TOKEN}"}
    raw = None
    if body is not None:
        raw = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    t0 = time.perf_counter()
    c.request(method, path, body=raw, headers=headers)
    r = c.getresponse()
    data = r.read()
    dt = time.perf_counter() - t0
    ctype = r.getheader("Content-Type", "")
    c.close()
    if r.status != 200:
        raise Mismatch(f"{method} {path} -> HTTP {r.status}: "
                       f"{data[:300]!r}")
    if not quiet:
        say(f"  {method} {path} -> 200 in {dt:.3f}s ({len(data)} bytes)")
    return json.loads(data) if "json" in ctype else data.decode()


def kernel_launches(addr: str) -> dict:
    """``repro_kernel_launches_total`` by kernel, scraped from /metrics."""
    out = {}
    for line in call(addr, "GET", "/metrics", quiet=True).splitlines():
        if line.startswith("repro_kernel_launches_total{"):
            kernel = line.split('kernel="', 1)[1].split('"', 1)[0]
            out[kernel] = float(line.rsplit(" ", 1)[1])
    return out


def cells(triples) -> set:
    for r, c, v in triples:
        if float(v) != 1.0:
            raise Mismatch(f"Tedge cell ({r}, {c}) holds {v!r}, not 1")
    return {(r, c) for r, c, _ in triples}


def gateway_phase(addr: str, ref: Reference, seed: int) -> None:
    say("gateway:")
    got = call(addr, "GET", "/v1/topk?prefix=ip.dst|&k=10")
    want = ref.degrees("ip.dst")
    top = sorted(want.values(), reverse=True)[:10]
    check([h["degree"] for h in got["hosts"]] == top
          and all(want[h["key"]] == h["degree"] for h in got["hosts"]),
          "/v1/topk: top-10 destination degrees")

    got = call(addr, "GET",
               f"/v1/topk?prefix=ip.src|&k={len(ref.src_keys)}")
    check({h["key"]: h["degree"] for h in got["hosts"]}
          == ref.degrees("ip.src"),
          f"/v1/topk: all {len(ref.src_keys)} source degrees (TedgeDeg)")

    got = call(addr, "GET", "/v1/degree?prefix=ip.dst|&bins=32")
    check(got["n"] == len(ref.dst_keys)
          and sum(got["histogram"]["counts"]) == len(ref.dst_keys)
          and got["fit"] is not None,
          f"/v1/degree: {len(ref.dst_keys)} destinations binned, fit "
          f"alpha={got['fit']['alpha']:.4f}")

    rng = np.random.default_rng([seed, 1])
    modest = ref.dst_keys[(ref.dst_deg >= 2) & (ref.dst_deg <= 20000)]
    wave = rng.choice(modest, size=N_WAVE + 1, replace=False).tolist()
    key = wave.pop()
    got = call(addr, "GET",
               f"/v1/scan?axis=col&keys={key},&max_cells=1000000")
    check(not got["truncated"] and cells(got["triples"]) == ref.column(key),
          f"/v1/scan: column {key} ({got['nnz']} cells)")

    before = call(addr, "GET", "/v1/stats", quiet=True)["coalesce"]
    results = [None] * N_WAVE
    errors = []
    gate = threading.Barrier(N_WAVE)

    def reader(i):
        try:
            gate.wait()
            results[i] = call(
                addr, "GET",
                f"/v1/scan?axis=col&keys={wave[i]},&max_cells=1000000",
                quiet=True)
        except Exception as e:      # surfaced below, on the main thread
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(N_WAVE)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    say(f"  wave of {N_WAVE} concurrent /v1/scan in "
        f"{time.perf_counter() - t0:.3f}s")
    after = call(addr, "GET", "/v1/stats", quiet=True)["coalesce"]
    for k, got in zip(wave, results):
        if got["truncated"] or cells(got["triples"]) != ref.column(k):
            raise Mismatch(f"/v1/scan wave: column {k}")
    check(True, f"/v1/scan wave: {N_WAVE} columns cell-for-cell")
    check(after["n_batches"] - before["n_batches"] == 1
          and after["n_coalesced"] - before["n_coalesced"] == N_WAVE,
          f"coalescer folded the wave into one eval_batch "
          f"(max_batch={after['max_batch']})")

    got = call(addr, "GET", "/v1/c2?top_k=10")["report"]
    feats = ref.c2_features()
    for h, f, c, r, s in zip(got["hosts"], got["fanin"], got["port_conc"],
                             got["regularity"], got["scores"]):
        fanin, conc = feats[h]
        if f != fanin or not np.isclose(c, conc, rtol=1e-12, atol=0):
            raise Mismatch(f"/v1/c2: host {h} fanin/port_conc {f}/{c} "
                           f"vs reference {fanin}/{conc}")
        want = np.log1p(f) * r * c * c
        if not np.isclose(s, want, rtol=C2_SCORE_RTOL, atol=0):
            raise Mismatch(f"/v1/c2: host {h} score {s} vs {want}")
    check(got["scores"] == sorted(got["scores"], reverse=True),
          f"/v1/c2: top-10 fan-in exact, port concentration, fused score "
          f"within rtol {C2_SCORE_RTOL}, ranked by score")


def pagerank_job(addr: str, pr_ref: dict) -> None:
    job = call(addr, "POST", "/v1/jobs",
               body={"kind": "pagerank",
                     "params": {"num_iters": PR_ITERS, "top_k": 20}})
    t0 = time.perf_counter()
    while True:
        st = call(addr, "GET", f"/v1/jobs/{job['job']}", quiet=True)
        if st["status"] == "failed":
            raise Mismatch(f"pagerank job failed: {st.get('error')}")
        if st["status"] == "done":
            break
        time.sleep(0.2)
    say(f"  pagerank job done in {time.perf_counter() - t0:.3f}s")
    res = call(addr, "GET", f"/v1/jobs/{job['job']}/result")["result"]
    err = max(abs(n["rank"] - pr_ref[n["key"]]) for n in res["nodes"])
    check(res["n_nodes"] == len(pr_ref) and err <= PR_L1_TOL,
          f"pagerank job: {res['n_nodes']} nodes, top-20 max abs error "
          f"{err:.3e} <= {PR_L1_TOL}")


# ---------------------------------------------------------------------------
# The device path through the planner.
# ---------------------------------------------------------------------------

def query_vectors(ref: Reference, seed: int):
    """N_CHAINS seeded vectors over VEC_KEYS destinations each, then
    the solo chain's vector over every destination.  The fused group
    goes to the device on the whole block's nnz; a solo matvec on the
    nnz of the columns its vector selects, so it spans all of them.
    Weights are float32-exact."""
    rng = np.random.default_rng([seed, 2])
    keys = [np.sort(rng.choice(ref.dst_keys, size=VEC_KEYS, replace=False))
            for _ in range(N_CHAINS)] + [ref.dst_keys]
    return [(k, rng.uniform(0.5, 2.0, k.shape[0]).astype(np.float32)
             .astype(np.float64)) for k in keys]


def chains(T, vecs):
    from repro.core import Assoc, lazy
    return [T[:, "ip.dst|*,"]
            * lazy(Assoc(k, np.full(k.shape[0], f"q{j}"), w))
            for j, (k, w) in enumerate(vecs)]


def check_chain(got, want: dict, what: str) -> None:
    r, _, v = got.triples()
    v = np.asarray(v, np.float64)
    if sorted(r.tolist()) != sorted(want):
        raise Mismatch(f"{what}: rows differ from the reference")
    ref = np.asarray([want[k] for k in r.tolist()])
    if not np.allclose(v, ref, rtol=CHAIN_RTOL, atol=0):
        bad = np.abs(v - ref) / ref
        raise Mismatch(f"{what}: max relative error {bad.max():.3e} > "
                       f"{CHAIN_RTOL:.3e}")


def device_phase(T, addr: str, ref: Reference, seed: int, path: str):
    from repro.core import eval_batch
    say(f"device chains ({path}):")
    vecs = query_vectors(ref, seed)
    wants = [ref.chain(k, w) for k, w in vecs]
    c0 = kernel_launches(addr)
    t0 = time.perf_counter()
    fused = eval_batch(chains(T, vecs[:N_CHAINS]))
    t1 = time.perf_counter()
    solo = chains(T, vecs[N_CHAINS:])[0].eval()
    t2 = time.perf_counter()
    c1 = kernel_launches(addr)
    say(f"  first eval_batch of {N_CHAINS} chains {t1 - t0:.3f}s, solo "
        f"chain {t2 - t1:.3f}s (compile included)")
    for j, got in enumerate(fused):
        check_chain(got, wants[j], f"fused chain {j}")
    check_chain(solo, wants[N_CHAINS], "solo chain")
    check(True, f"{N_CHAINS} fused + 1 solo chain within rtol "
                f"{CHAIN_RTOL:.3e} over {ref.n} packet rows")
    d = {k: c1.get(k, 0) - c0.get(k, 0) for k in ("spmm", "spmv")}
    check(d == {"spmm": 1, "spmv": 1},
          f"repro_kernel_launches_total: spmm +{d['spmm']:g}, "
          f"spmv +{d['spmv']:g}")
    t0 = time.perf_counter()
    eval_batch(chains(T, vecs[:N_CHAINS]))
    t1 = time.perf_counter()
    chains(T, vecs[N_CHAINS:])[0].eval()
    say(f"  warm eval_batch {t1 - t0:.3f}s, solo chain "
        f"{time.perf_counter() - t1:.3f}s")
    n_union = len(set().union(*(k.tolist() for k, _ in vecs[:N_CHAINS])))
    return n_union


def assert_mosaic_kernel(n_rows: int, n_union: int, n_solo: int) -> None:
    """The Pallas path compiled to Mosaic kernels, not interpreted."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.spmm import spmm_ell
    from repro.kernels.spmv import spmv_ell
    ecols = jax.ShapeDtypeStruct((n_rows, 1), jnp.int32)
    evals = jax.ShapeDtypeStruct((n_rows, 1), jnp.float32)
    for name, fn, x in (
            ("spmm_ell", spmm_ell,
             jax.ShapeDtypeStruct((n_union, N_CHAINS), jnp.float32)),
            ("spmv_ell", spmv_ell,
             jax.ShapeDtypeStruct((n_solo,), jnp.float32))):
        txt = fn.lower(ecols, evals, x).compile().as_text()
        check("tpu_custom_call" in txt,
              f"{name} compiled to a Mosaic kernel (tpu_custom_call)")


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def one_chip(seed: int, n_packets: int, meter: CompileMeter) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.analytics.distributed import pagerank_table
    from repro.core import expr as X
    from repro.db import DB, put
    from repro.serve import Gateway, Tenant, TokenAuth

    t0 = time.perf_counter()
    rec = capture(seed, n_packets)
    E = incidence(rec)
    ref = Reference(rec)
    say(f"data: {ref.n} packets, {E.nnz} incidence entries, "
        f"{len(ref.src_keys)} sources, {len(ref.dst_keys)} destinations "
        f"({time.perf_counter() - t0:.3f}s)")

    t0 = time.perf_counter()
    T = DB("Tedge", "TedgeT", "TedgeDeg", backend="net", n_instances=2)
    put(T, E)
    T.flush()
    n_entries = T.n_entries
    del E
    say(f"ingest: {n_entries} Tedge entries into 2 net shards in "
        f"{time.perf_counter() - t0:.3f}s")
    check(n_entries == 9 * ref.n, "Tedge holds 9 entries per packet")

    gw = Gateway(T, TokenAuth({TOKEN: Tenant("smoke", rate=1e6,
                                             burst=1e6, max_jobs=8)}),
                 coalesce_window=COALESCE_WINDOW)
    addr = gw.start()
    try:
        t0 = time.perf_counter()
        gateway_phase(addr, ref, seed)
        pr_ref = ref.pagerank()
        pagerank_job(addr, pr_ref)
        say(f"gateway phase {time.perf_counter() - t0:.3f}s")
        meter.report("gateway")

        n_union = device_phase(T, addr, ref, seed, "COO segment reduction")
        X.USE_PALLAS_SPMV = True
        try:
            device_phase(T, addr, ref, seed, "Pallas ELL kernels")
        finally:
            X.USE_PALLAS_SPMV = False
        assert_mosaic_kernel(ref.n, n_union, len(ref.dst_keys))
        meter.report("device chains")

        t0 = time.perf_counter()
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        keys, ranks = pagerank_table(T, mesh=mesh, num_iters=PR_ITERS,
                                     damping=PR_DAMPING)
        ranks = np.asarray(ranks)
        l1 = pagerank_l1(keys, ranks, pr_ref)
        check(l1 <= PR_L1_TOL,
              f"pagerank_table on 1 device: {len(keys)} nodes, L1 "
              f"{l1:.3e} <= {PR_L1_TOL} vs scipy "
              f"({time.perf_counter() - t0:.3f}s)")

        stats = call(addr, "GET", "/v1/stats", quiet=True)
        w = stats["table"]["writers"]
        failed = stats["jobs"]["by_status"].get("failed", 0)
        check(w["n_errors"] == 0 and w["tap_errors"] == 0 and failed == 0,
              "writer n_errors=0, tap_errors=0, no failed jobs")
    finally:
        gw.stop()
        T.close()
        T.backend.close()
    meter.report("run")


def four_chips(seed: int, n_packets: int, meter: CompileMeter) -> None:
    """Sharded PageRank over a 4-device mesh vs 1 device vs scipy."""
    import jax
    from jax.sharding import Mesh

    from repro.analytics.distributed import pagerank_table
    devs = jax.devices()
    if len(devs) < 4:
        raise Mismatch(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    t0 = time.perf_counter()
    rec = capture(seed, n_packets)
    E = incidence(rec)
    ref = Reference(rec)
    pr_ref = ref.pagerank()
    say(f"data: {ref.n} packets, {E.nnz} incidence entries "
        f"({time.perf_counter() - t0:.3f}s)")
    runs = {}
    for n in (1, 4):
        mesh = Mesh(np.asarray(devs[:n]), ("data",))
        t0 = time.perf_counter()
        keys, ranks = pagerank_table(E, mesh=mesh, num_iters=PR_ITERS,
                                     damping=PR_DAMPING)
        runs[n] = (np.asarray(keys), np.asarray(ranks, np.float64))
        l1 = pagerank_l1(*runs[n], pr_ref)
        check(l1 <= PR_L1_TOL,
              f"pagerank_table on {n} device(s): {len(keys)} nodes, L1 "
              f"{l1:.3e} <= {PR_L1_TOL} vs scipy "
              f"({time.perf_counter() - t0:.3f}s)")
    (k1, r1), (k4, r4) = runs[1], runs[4]
    l1 = float(np.abs(r4 - r1).sum())
    check(np.array_equal(k1, k4) and l1 <= PR_L1_TOL,
          f"4-device ranks vs 1-device ranks: L1 {l1:.3e} <= {PR_L1_TOL}")
    meter.report("run")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded PageRank on 4 chips")
    args = p.parse_args(argv)

    cache = enable_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX's default backend is "
              f"{jax.default_backend()!r}, not a TPU; refusing to run",
              file=sys.stderr)
        return 2
    meter = CompileMeter()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}; seed {args.seed}; {PACKETS} packets")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.seed, PACKETS, meter)
        else:
            one_chip(args.seed, PACKETS, meter)
    except Mismatch as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
