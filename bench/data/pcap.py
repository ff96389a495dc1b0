"""Seeded packet captures, their D4M incidence triples, and the plain
reference the pcap deployments are checked against.

The traffic model is the pipeline's default shape (``TrafficConfig()``
of ``repro.pipeline``: 4096 hosts, Zipf 1.3 destination popularity, a
source popularity rolled by a third of the hosts, 100k packets/s, 90%
TCP, 24 bots beaconing one C2 host on port 6667), copied here so that
the yardstick cannot move with the program.

Every seed gets the same sizes.  The pairs of host *ranks* (source,
destination) of every packet, the C2 rank and the bot ranks come from a
fixed generator; ``--seed`` picks the host addresses, assigns them to
the ranks, orders the packets and draws timestamps, ports, lengths and
flags.  So a query named by popularity rank touches the same number of
entries under every seed, and every program shape is the same.

The first ``n_hosts`` packets of a capture's base segment cover every
host once as a destination and once as a source, so every host is a
key of both bands.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..deploy import Deployment
from ..precision import bf16

SHAPE_SEED = 0x5EED      # the fixed generator of ranks (sizes)
FIELDS = ("frame.time_relative", "frame.time", "ip.dst", "ip.len",
          "ip.proto", "ip.src", "tcp.dstport", "tcp.flags", "tcp.srcport")
ID_DIGITS = 9            # packet row keys: zero-padded global index
WELL_KNOWN = np.asarray([80, 443, 53, 22, 25, 8080], dtype=np.uint16)
T0 = 1_492_000_000.0
BAND = "ip.dst|"         # the column band every chain reads


@dataclasses.dataclass(frozen=True)
class Shape:
    n_hosts: int = 4096
    zipf_a: float = 1.3
    pkt_rate: float = 100_000.0
    tcp_fraction: float = 0.9
    n_bots: int = 24
    c2_port: int = 6667


def popularity(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


@dataclasses.dataclass
class Segment:
    """Packet header columns of one contiguous run of a capture."""
    first_id: int
    ts: np.ndarray
    src: np.ndarray          # uint32 addresses
    dst: np.ndarray
    sport: np.ndarray
    dport: np.ndarray
    proto: np.ndarray
    length: np.ndarray
    flags: np.ndarray

    @property
    def n(self) -> int:
        return int(self.ts.shape[0])


class Capture:
    """The host table of one seed and the segments cut from it."""

    def __init__(self, seed: int, shape: Shape = Shape()):
        self.seed = int(seed)
        self.shape = shape
        n = shape.n_hosts
        rng = np.random.default_rng([self.seed, 1])
        ips = rng.choice(0xDF000000 - 0x0B000000, size=n, replace=False)
        self.pool = (ips + 0x0B000000).astype(np.uint32)   # rank -> address
        fixed = np.random.default_rng([SHAPE_SEED, 0])
        self.c2_rank = int(fixed.integers(0, n))
        others = np.delete(np.arange(n), self.c2_rank)
        self.bot_ranks = fixed.choice(others, size=shape.n_bots,
                                      replace=False)
        self.t_end = T0

    @property
    def c2(self) -> np.uint32:
        return self.pool[self.c2_rank]

    def _rank_pairs(self, n: int, index: int, cover: bool):
        """Fixed (src_rank, dst_rank) pairs of segment ``index``."""
        s = self.shape
        fixed = np.random.default_rng([SHAPE_SEED, 1, index])
        pop = popularity(s.n_hosts, s.zipf_a)
        dst = fixed.choice(s.n_hosts, size=n, p=pop)
        src = fixed.choice(s.n_hosts, size=n, p=np.roll(pop, s.n_hosts // 3))
        if cover:
            k = min(n, s.n_hosts)
            dst[:k] = np.arange(k)
            src[:k] = (np.arange(k) + 1) % s.n_hosts
        same = src == dst
        src[same] = (src[same] + 1) % s.n_hosts
        return src, dst

    def segment(self, first_id: int, n: int, index: int,
                beacons: bool = False) -> Segment:
        """``n`` packets (plus one beacon per bot when ``beacons``) that
        follow every segment cut before, in time."""
        s = self.shape
        src_r, dst_r = self._rank_pairs(n, index, cover=beacons)
        rng = np.random.default_rng([self.seed, 2, index])
        order = rng.permutation(n)
        src_r, dst_r = src_r[order], dst_r[order]
        duration = n / s.pkt_rate
        ts = self.t_end + np.sort(rng.uniform(0.0, duration, n))
        length = np.minimum(40 + rng.pareto(1.2, n).astype(np.int64) * 64,
                            1500)
        proto = np.where(rng.random(n) < s.tcp_fraction, 6, 17).astype(
            np.uint8)
        sport = rng.integers(1024, 65535, n, dtype=np.uint32).astype(
            np.uint16)
        dport = WELL_KNOWN[rng.integers(0, WELL_KNOWN.shape[0], n)]
        flags = np.full(n, 0x5010, np.uint16)
        src, dst = self.pool[src_r], self.pool[dst_r]
        if beacons:
            nb = s.n_bots
            bts = self.t_end + rng.uniform(0.0, duration, nb)
            ts = np.concatenate([ts, bts])
            src = np.concatenate([src, self.pool[self.bot_ranks]])
            dst = np.concatenate([dst, np.full(nb, self.c2, np.uint32)])
            length = np.concatenate([length, np.full(nb, 60)])
            proto = np.concatenate([proto, np.full(nb, 6, np.uint8)])
            sport = np.concatenate(
                [sport, rng.integers(40000, 50000, nb).astype(np.uint16)])
            dport = np.concatenate(
                [dport, np.full(nb, s.c2_port, np.uint16)])
            flags = np.concatenate([flags, np.full(nb, 0x5018, np.uint16)])
            o = np.argsort(ts, kind="stable")
            ts, src, dst, length, proto, sport, dport, flags = (
                x[o] for x in (ts, src, dst, length, proto, sport, dport,
                               flags))
        self.t_end = float(ts[-1]) + 1.0 / s.pkt_rate
        return Segment(first_id, ts, src, dst, sport, dport, proto, length,
                       flags)


def ip_str(ip_u32: np.ndarray) -> np.ndarray:
    """uint32 addresses -> dotted quads."""
    ip = np.asarray(ip_u32, dtype=np.uint32)
    parts = [((ip >> s) & 0xFF).astype("U3") for s in (24, 16, 8, 0)]
    out = parts[0]
    for p in parts[1:]:
        out = np.char.add(np.char.add(out, "."), p)
    return out


def row_keys(first_id: int, n: int) -> np.ndarray:
    return np.char.zfill(np.arange(first_id, first_id + n).astype("U"),
                         ID_DIGITS)


def incidence(seg: Segment) -> tuple:
    """The paper's incidence triples of a segment (tshark's field set,
    ``val2col`` with ``|``): 9 entries per packet, value ``1``."""
    cols = {
        "frame.time_relative": np.char.mod("%.9f", seg.ts - T0),
        "frame.time": np.char.mod("%.6f", seg.ts),
        "ip.dst": ip_str(seg.dst),
        "ip.len": seg.length.astype("U6"),
        "ip.proto": seg.proto.astype("U3"),
        "ip.src": ip_str(seg.src),
        "tcp.dstport": seg.dport.astype("U5"),
        "tcp.flags": np.char.mod("0x%08x", seg.flags.astype(np.int64)),
        "tcp.srcport": seg.sport.astype("U5"),
    }
    ids = row_keys(seg.first_id, seg.n)
    r = np.repeat(ids, len(FIELDS))
    c = np.stack([np.char.add(f + "|", cols[f]) for f in FIELDS],
                 axis=1).ravel()
    return r, c, np.ones(r.shape[0])


class Reference:
    """Plain numpy answers over the packets of a list of segments."""

    def __init__(self, segments):
        self.ids = np.concatenate([row_keys(s.first_id, s.n)
                                   for s in segments])
        self.dst = np.concatenate([s.dst for s in segments])
        self.dst_key = np.char.add("ip.dst|", ip_str(self.dst))
        self._match: dict = {}

    @property
    def n(self) -> int:
        return int(self.ids.shape[0])

    def _positions(self, keys: np.ndarray):
        """(order of ``keys``, index into the sorted keys of each
        packet's destination, packets whose destination is a key)."""
        tag = keys.tobytes()
        if tag not in self._match:
            order = np.argsort(keys)
            ks = keys[order]
            pos = np.minimum(np.searchsorted(ks, self.dst_key),
                             ks.shape[0] - 1)
            self._match = {tag: (order, pos, ks[pos] == self.dst_key)}
        return self._match[tag]

    def chain(self, keys: np.ndarray, w: np.ndarray):
        """(rows, values) of ``T[:, 'ip.dst|*,'] * x`` with x[keys] = w:
        each packet whose destination is in ``keys`` gets its weight."""
        order, pos, hit = self._positions(keys)
        return self.ids[hit], w[order][pos[hit]]


# ---------------------------------------------------------------------------
# The deployment: the capture ingested into the configured DB().
# ---------------------------------------------------------------------------

def deployment(cfg: dict, seed: int, overrides: dict):
    return PcapDeployment(cfg, seed, overrides)


class _Triples:
    """An answer in the shape the comparison reads (``.triples()``)."""

    def __init__(self, rows, vals):
        self.rows, self.vals = rows, vals

    def triples(self):
        return self.rows, None, self.vals


class PcapDeployment(Deployment):
    def __init__(self, cfg, seed, overrides):
        from repro.core import Assoc
        super().__init__(cfg, seed)
        self.shape = Shape(**cfg["traffic_shape"])
        n = int(overrides.get("packets", cfg["packets"]))
        self.capture = Capture(seed, self.shape)
        base = self.capture.segment(0, n, 0, beacons=True)
        self.base = base
        self.n_base = base.n
        self.load(Assoc(*incidence(base)))
        self._ref = None
        self.dst_keys = np.char.add(BAND, ip_str(self.capture.pool))
        order = np.argsort(self.capture.pool)
        rank = order[np.searchsorted(self.capture.pool[order], base.dst)]
        self.base_deg = np.bincount(rank, minlength=self.shape.n_hosts)

    # -- sizes ---------------------------------------------------------------
    @staticmethod
    def _served(band: str) -> None:
        if band != BAND:
            raise ValueError(f"the reference answers chains over {BAND!r}"
                             f" alone, not {band!r}")

    def band_keys_by_rank(self, band: str) -> np.ndarray:
        self._served(band)
        return self.dst_keys

    def popularity(self, band: str) -> np.ndarray:
        self._served(band)
        return popularity(self.shape.n_hosts, self.shape.zipf_a)

    def chain_work(self, call) -> tuple:
        """(nnz, rows, cols, b) of the SpMM a call of chains needs over
        the static ``ip.dst|`` band: the union of the call's keys is the
        column set, every packet row is a row."""
        keys = self.dst_keys
        order = np.argsort(keys)
        union = np.unique(np.concatenate([k for k, _ in call]))
        rank = order[np.searchsorted(keys[order], union)]
        nnz = int(self.base_deg[rank].sum())
        return nnz, self.n_base, int(union.shape[0]), len(call)

    def reference(self) -> Reference:
        """The reference over the base table (what set-up ingested)."""
        if self._ref is None:
            self._ref = Reference([self.base])
        return self._ref

    def control_chain(self, keys, w) -> _Triples:
        """The control: the reference with its weights in bfloat16."""
        rows, vals = self.reference().chain(
            keys, bf16(w).astype(np.float64))
        return _Triples(rows, vals)
