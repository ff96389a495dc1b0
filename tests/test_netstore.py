"""Networked shard backend: scan-protocol agreement with the memory
backend, chunked streaming, registry dispatch, binding consistency,
kill-one-shard failover through the WriterPool retry path, the
cross-shard sync barrier as durability commit point, and a standalone
CLI shard server driven over a real subprocess boundary."""
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core.assoc import Assoc
from repro.db import (DB, AsyncWriterError, EdgeStore, LSMStore,
                      MultiInstanceDB, NetMultiInstanceDB, ShardClient,
                      ShardError, ShardServer, WriterPool, put)

from test_lsmstore import degrees, rand_triples, snapshot


@pytest.fixture
def net3():
    """3 memory-backed local shards; always torn down."""
    db = NetMultiInstanceDB(n_instances=3, tablets_per_instance=3)
    yield db
    db.close()


class TestScanAgreement:
    """The net backend is observationally identical to the in-process
    memory backend over identical triples (mirrors the LSM cross-check:
    shard placement may differ, merged scans may not)."""

    @pytest.mark.parametrize("seed", range(5))
    def test_scans_agree_with_memory_backend(self, net3, seed):
        mem = MultiInstanceDB(n_instances=3, tablets_per_instance=3)
        r, c, v = rand_triples(seed, n=250)
        for lo in range(0, 250, 50):        # batched, interleaved
            net3.put_triples(r[lo:lo + 50], c[lo:lo + 50], v[lo:lo + 50])
            mem.put_triples(r[lo:lo + 50], c[lo:lo + 50], v[lo:lo + 50])
        for t in (False, True):
            assert snapshot(net3, t) == snapshot(mem, t)
            lo_k, hi_k = ("p005", "p025") if not t \
                else ("ip.dst|", "ip.src|5")
            assert list(net3.scan_key_range(lo_k, hi_k, transpose=t)) == \
                list(mem.scan_key_range(lo_k, hi_k, transpose=t))
            assert list(net3.scan_prefix("p01" if not t else "ip.dst|",
                                         transpose=t)) == \
                list(mem.scan_prefix("p01" if not t else "ip.dst|",
                                     transpose=t))
            assert list(net3.scan_keys([r[0], r[7], "absent"],
                                       transpose=t)) == \
                list(mem.scan_keys([r[0], r[7], "absent"], transpose=t))
        assert degrees(net3) == degrees(mem)
        assert sorted(net3.keys_with_prefix("ip.dst|")) == \
            sorted(mem.keys_with_prefix("ip.dst|"))
        for key in set(c[:20]):
            assert net3.degree(key) == mem.degree(key)
        assert net3.connections("3") == mem.connections("3")
        assert net3.n_entries == mem.n_entries == len(r)

    def test_put_degree_matches_edgestore(self, tmp_path):
        e = EdgeStore(n_tablets=2)
        srv = ShardServer(EdgeStore(n_tablets=2)).start()
        client = ShardClient(srv.address)
        Edeg = Assoc("ip.dst|a,ip.dst|b,", "degree,degree,",
                     np.asarray([3.0, 4.0]))
        client.put_degree(Edeg)
        e.put_degree(Edeg)
        try:
            assert degrees(client) == degrees(e)
        finally:
            client.close()
            srv.stop()

    def test_chunked_streaming_covers_full_scan(self):
        """Results spanning many chunk frames arrive complete and in
        order (chunk_items far below the key count)."""
        db = NetMultiInstanceDB(n_instances=2, chunk_items=16)
        try:
            r, c, v = rand_triples(9, n=400, n_rows=300, n_cols=40)
            db.put_triples(r, c, v)
            keys = [k for k, _ in db.scan_everything()]
            assert keys == sorted(keys)
            assert set(keys) == set(r.tolist())
        finally:
            db.close()

    def test_abandoned_scan_does_not_poison_pool(self, net3):
        """A generator dropped mid-stream discards its connection; the
        next RPC on the shard still works."""
        r, c, v = rand_triples(3, n=300, n_rows=280)
        net3.put_triples(r, c, v)
        it = net3.instances[0].scan_everything()
        next(it)
        it.close()                          # abandon mid-stream
        assert net3.instances[0].ping()
        assert snapshot(net3)               # full scans still complete


class TestRegistry:
    def test_net_dispatch_local(self):
        T = DB("Tedge", backend="net", n_instances=2)
        try:
            assert isinstance(T.backend, NetMultiInstanceDB)
            assert len(T.backend.instances) == 2
            assert len(T.backend.servers) == 2      # auto-started, owned
        finally:
            T.backend.close()

    def test_net_dispatch_addresses(self):
        srv = ShardServer(EdgeStore(n_tablets=2)).start()
        T = DB("Tedge", backend="net", addresses=[srv.address])
        try:
            assert T.backend.servers == []          # not owned
            assert T.backend.instances[0].ping()
        finally:
            T.backend.close()
            srv.stop()

    def test_remote_addresses_reject_engine_opts(self):
        with pytest.raises(ValueError, match="engine options"):
            NetMultiInstanceDB(addresses=["127.0.0.1:1"],
                               memtable_limit=5)

    def test_unknown_op_is_shard_error(self):
        srv = ShardServer(EdgeStore(n_tablets=1)).start()
        client = ShardClient(srv.address)
        try:
            with pytest.raises(ShardError, match="unknown op"):
                client._rpc("nope")
        finally:
            client.close()
            srv.stop()

    def test_stable_routing_hash(self):
        """Shard placement must agree across producer processes."""
        import zlib
        assert NetMultiInstanceDB.key_hash("p1") == zlib.crc32(b"p1")


class TestBindingOnNet:
    def test_query_after_put_consistency(self, tmp_path):
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="net",
               path=str(tmp_path / "a"), n_instances=2)
        try:
            E = Assoc("p1,p1,p2,p3,",
                      "ip.dst|a,ip.src|b,ip.dst|a,ip.dst|c,", "1,1,1,1,")
            put(T, E, sync=False)
            # query-after-put: the binding read flushes (and syncs) first
            assert T[:, "ip.dst|*,"].eval().nnz == 3
            assert T.degree("ip.dst|a") == 2.0
            assert T["p1,", :].eval().nnz == 2
            assert T["p1,:,p2,", :].eval().nnz == 3
            r, _, v = T.degree_assoc("ip.dst|").triples()
            assert dict(zip(r, np.asarray(v, float)))["ip.dst|c"] == 1.0
            T.close()
        finally:
            T.backend.close()

    def test_scan_cache_invalidation_on_net(self):
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="net",
               n_instances=2)
        try:
            put(T, Assoc("p1,", "ip.dst|a,", "1,"))
            assert T[:, "ip.dst|*,"].eval().nnz == 1
            # direct client put (bypasses the binding) still invalidates
            T.backend.route("x").put(Assoc("p2,", "ip.dst|a,", "1,"))
            assert T[:, "ip.dst|*,"].eval().nnz == 2
            T.close()
        finally:
            T.backend.close()


class TestFailover:
    def test_dead_shard_raises_async_writer_error(self):
        """Kill one shard; blocks routed to it exhaust the WriterPool's
        bounded-backoff retries and surface AsyncWriterError at the
        barrier — with the shard's address in the message."""
        db = NetMultiInstanceDB(n_instances=2)
        pool = WriterPool(db, max_retries=1, retry_backoff_s=0.01)
        try:
            r, c, v = rand_triples(0, n=40)
            pool.submit(r, c, v)
            pool.flush()                    # healthy cluster: all applied
            n0 = pool.n_written
            assert n0 == 40
            dead = db.servers[0]
            dead.stop()
            pool.submit(r, c, v)            # some rows route to shard 0
            with pytest.raises(AsyncWriterError, match=dead.address):
                pool.flush()
        finally:
            db.close()

    def test_restarted_shard_picks_up_retried_block(self, tmp_path):
        """The retry path re-dials per attempt, so a shard that comes
        back before retries exhaust receives the block — no data loss,
        n_retried records the recovery."""
        store = LSMStore(str(tmp_path / "s0"))
        srv = ShardServer(store).start()
        port = srv.port
        db = NetMultiInstanceDB(addresses=[srv.address])
        pool = WriterPool(db, max_retries=8, retry_backoff_s=0.05)
        try:
            srv.stop()                      # shard down before any RPC
            r, c, v = rand_triples(1, n=30)
            pool.submit(r, c, v)

            def revive():
                time.sleep(0.2)
                ShardServer(store, port=port).start()
            t = threading.Thread(target=revive)
            t.start()
            pool.flush()                    # retries until the revival
            t.join()
            assert pool.n_written == 30
            assert pool.n_retried >= 1
            assert db.n_entries == 30
        finally:
            pool.close()
            db.close()

    def test_dead_shard_scan_raises_connection_error(self, net3):
        net3.put_triples(*rand_triples(2, n=30))
        net3.servers[1].stop()
        with pytest.raises(ConnectionError, match="db1"):
            snapshot(net3)


class TestSyncBarrier:
    def test_flush_is_cross_shard_durability_point(self, tmp_path):
        """flush() fans the sync barrier to every shard (WAL fsync);
        abandoning the cluster afterwards loses nothing — reopening the
        shard directories recovers every entry and degree sum."""
        d = str(tmp_path / "m")
        T = DB("Tedge", "TedgeT", "TedgeDeg", backend="net", path=d,
               n_instances=2, cache_ttl=0)
        r, c, v = rand_triples(4, n=120)
        n_put = put(T, Assoc(r, c, v), sync=False)  # Assoc dedups cells
        T.flush()
        before = snapshot(T.backend)
        deg = degrees(T.backend)
        for srv in T.backend.servers:       # crash: no close(), no sync
            assert srv.store.n_syncs >= 1   # the barrier already fsync'd
            srv.stop()
        for inst in T.backend.instances:
            inst.close()

        R = DB("Tedge", "TedgeT", "TedgeDeg", backend="lsm", path=d,
               n_instances=2, cache_ttl=0)
        assert snapshot(R.backend) == before
        assert degrees(R.backend) == deg
        assert R.n_entries == n_put

    def test_clean_barrier_skips_rpcs(self, net3):
        """A sync with no outstanding client writes is a pure local
        check — no RPC per shard, so read-path flushes stay cheap."""
        net3.put_triples(*rand_triples(5, n=20))
        net3.sync()
        n0 = sum(i.n_rpcs for i in net3.instances)
        for _ in range(10):
            net3.sync()
        assert sum(i.n_rpcs for i in net3.instances) == n0
        net3.put_triples(*rand_triples(5, n=5))
        net3.sync()
        assert sum(i.n_rpcs for i in net3.instances) > n0


class TestWriterRouting:
    def test_pool_fallback_hash_is_process_stable(self):
        """A backend with instances but no key_hash hook must get the
        crc32 fallback — pin= routing has to agree across producers
        (abs(hash(k)) is salted per process)."""
        import zlib

        class Bare:
            def __init__(self):
                self.instances = [EdgeStore(n_tablets=1, name=f"db{i}")
                                  for i in range(4)]
        b = Bare()
        pool = WriterPool(b)
        try:
            assert pool._key_hash("file-007") == zlib.crc32(b"file-007")
            pool.submit(np.asarray(["p1"]), np.asarray(["c|a"]),
                        np.asarray(["1"]), pin="file-007")
            pool.flush()
            want = zlib.crc32(b"file-007") % 4
            assert [i for i, inst in enumerate(b.instances)
                    if inst.n_entries] == [want]
        finally:
            pool.close()


class TestStandaloneServer:
    @pytest.mark.slow
    def test_cli_shard_server_over_subprocess(self, tmp_path):
        """The real deployment shape: a shard server in its own process
        (LSM-backed), a client binding in this one, SIGTERM shutdown."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.db.netstore", "--port", "0",
             "--path", str(tmp_path / "shard0")],
            env={**os.environ, "PYTHONPATH": src},
            stdout=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            assert line.startswith("LISTENING "), line
            addr = line.split()[1]
            T = DB("Tedge", "TedgeT", "TedgeDeg", backend="net",
                   addresses=[addr], cache_ttl=0)
            put(T, Assoc("p1,p2,", "ip.dst|a,ip.dst|b,", "1,1,"),
                sync=False)
            T.flush()                       # commits on the server's WAL
            assert T[:, "ip.dst|*,"].eval().nnz == 2
            assert T.degree("ip.dst|a") == 1.0
            T.close()
            T.backend.close()
        finally:
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=30) == 0
        # the server-side store is durable past the server's lifetime
        s = LSMStore(str(tmp_path / "shard0"))
        assert s.n_entries == 2
        s.close()

    def test_standalone_shard_never_starts_a_jax_backend(self):
        """A shard process on the chip host must not take the TPU from
        the gateway there: serving puts and scans through the CLI entry
        point initializes no JAX backend."""
        code = """
import os, signal, socket, threading
from jax._src import xla_bridge
from repro.db import ShardClient, netstore
sock = socket.socket(); sock.bind(("127.0.0.1", 0))
port = sock.getsockname()[1]; sock.close()
seen = {}
def client():
    c = ShardClient(f"127.0.0.1:{port}")
    for _ in range(500):
        try:
            c.ping(); break
        except OSError:
            threading.Event().wait(0.02)
    c.put_triples(["p1", "p2"], ["ip.dst|a", "ip.dst|b"], ["1", "1"])
    seen["cells"] = sum(1 for _ in c.scan_everything())
    seen["deg"] = c.degree("ip.dst|a")
    c.close()
    os.kill(os.getpid(), signal.SIGTERM)
threading.Thread(target=client, daemon=True).start()
netstore.main(["--port", str(port)])
print(seen["cells"], seen["deg"], xla_bridge.backends_are_initialized())
"""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        out = subprocess.run([sys.executable, "-c", code],
                             env={**os.environ, "PYTHONPATH": src},
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.split()[-3:] == ["2", "1.0", "False"]
