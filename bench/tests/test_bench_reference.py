"""Each plain reference agrees with the program's answers at a tiny
size, on the CPU."""
import numpy as np
import pytest

from _bench_small import CHAINS, PAGERANK, config


@pytest.fixture(scope="module")
def pcap_dep():
    from bench.data import pcap
    dep = pcap.deployment(config(CHAINS, backend="memory"), 11,
                          {"packets": 33000})
    yield dep
    dep.close()


def test_capture_sizes_do_not_depend_on_the_seed():
    from bench.data import pcap
    a, b = pcap.Capture(1), pcap.Capture(2 ** 31 + 5)
    sa, sb = a.segment(0, 5000, 0, beacons=True), b.segment(0, 5000, 0,
                                                             beacons=True)
    assert sa.n == sb.n == 5000 + 24
    da = np.sort(np.unique(sa.dst, return_counts=True)[1])
    db = np.sort(np.unique(sb.dst, return_counts=True)[1])
    assert np.array_equal(da, db)
    assert not np.array_equal(sa.dst, sb.dst)


def test_incidence_is_nine_entries_per_packet():
    from bench.data import pcap
    seg = pcap.Capture(3).segment(0, 100, 0)
    r, c, v = pcap.incidence(seg)
    assert r.shape[0] == c.shape[0] == 900 and (v == 1).all()
    assert {x.split("|")[0] for x in c.tolist()} == set(pcap.FIELDS)


def test_ingested_band_is_the_capture(pcap_dep):
    """The ``ip.dst|`` band that every chain reads holds each packet's
    destination, and every host is one of its keys."""
    ref = pcap_dep.reference()
    r, c, v = pcap_dep.table[:, "ip.dst|*,"].eval().triples()
    got = dict(zip(np.asarray(r).tolist(), np.asarray(c).tolist()))
    assert got == dict(zip(ref.ids.tolist(), ref.dst_key.tolist()))
    assert (np.asarray(v, float) == 1).all()
    assert set(c.tolist()) == set(pcap_dep.dst_keys.tolist())
    with pytest.raises(ValueError):
        pcap_dep.band_keys_by_rank("ip.src|")


@pytest.mark.parametrize("keys_per_chain", ["all", 5])
def test_chain_reference_matches_the_planner(pcap_dep, keys_per_chain):
    from bench.streams.chains import Chains, compare_chain
    spec = {"kind": "chains", "clients": 1, "chains_per_call": 3,
            "keys_per_chain": keys_per_chain, "band": "ip.dst|",
            "check_share": 1.0}
    s = Chains(spec, pcap_dep, 11, False, 1.0)
    ref = pcap_dep.reference()
    for i in (0, 1):
        call = s.draw(i)
        for (keys, w), got in zip(call, s.call(call)):
            assert compare_chain(got, *ref.chain(keys, w)) == (0, 0.0)


def test_every_call_is_new_and_the_sample_spans_the_calls(pcap_dep):
    """No call repeats another, within a seed or across seeds, and the
    calls kept for the check are drawn from the seed across all of
    them."""
    from bench.streams.chains import Chains
    spec = {"kind": "chains", "clients": 1, "chains_per_call": 2,
            "keys_per_chain": "all", "band": "ip.dst|",
            "check_share": 0.25}
    a = Chains(spec, pcap_dep, 11, False, 1.0)
    b = Chains(spec, pcap_dep, 2 ** 31 + 11, False, 1.0)
    ws = {a.draw(i)[j][1].tobytes() for i in range(200) for j in (0, 1)}
    ws |= {b.draw(i)[j][1].tobytes() for i in range(200) for j in (0, 1)}
    assert len(ws) == 800
    kept = [i for i in range(2000) if a.keep(i)]
    assert 400 <= len(kept) <= 600
    assert kept[0] < 20 and kept[-1] > 1980
    assert kept == [i for i in range(2000) if a.keep(i)]
    assert kept != [i for i in range(2000) if b.keep(i)]


def test_pagerank_reference_matches_the_served_program():
    from bench.data import kronecker
    from repro.analytics.distributed import pagerank_table
    dep = kronecker.deployment(config(PAGERANK, backend="memory"), 5,
                               {"scale": 8})
    try:
        keys, ranks = pagerank_table(dep.table, num_iters=20)
        want = dep.ranks(20, 0.85)
        assert keys.tolist() == dep.ref.keys.tolist()
        np.testing.assert_allclose(np.asarray(ranks, np.float64), want,
                                   rtol=1e-5)
        low = dep.ranks(20, 0.85, "bfloat16")
        assert np.max(np.abs(low - want) / want) > 1e-3
    finally:
        dep.close()
