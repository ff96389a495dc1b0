"""Operation and byte counts of the roofline shares, by hand, and their
independence from the kernel that runs the product."""
import numpy as np
import pytest

from bench import work
from bench.peaks import PEAKS, peaks


def test_spmm_hand_count():
    # 3 entries, 4 x 5 matrix, 2 right-hand columns
    flops, nbytes = work.spmm(nnz=3, rows=4, cols=5, b=2)
    assert flops == 2 * 3 * 2
    assert nbytes == 3 * 8 + 4 * 4 + 5 * 2 * 4 + 4 * 2 * 4


def test_pagerank_iteration_is_spmm_with_one_column():
    assert work.pagerank_iteration(100, 10) == work.spmm(100, 10, 10, 1)
    flops, nbytes = work.pagerank_iteration(100, 10)
    assert flops == 200 and nbytes == 100 * 8 + 10 * 4 + 10 * 4 + 10 * 4


def test_least_seconds_takes_the_binding_roof():
    pk = peaks("TPU v5 lite")
    assert pk.hbm_bytes_per_s == 819e9 and pk.bf16_flops == 197e12
    f, b = work.spmm(2 ** 18, 2 ** 18, 256, 8)
    assert work.least_seconds(f, b, pk) == b / pk.hbm_bytes_per_s


def test_unknown_device_kind_raises():
    with pytest.raises(LookupError):
        peaks("cpu")
    assert "TPU v5 lite" in PEAKS


@pytest.mark.parametrize("keys_per_chain", ["all", 8])
def test_chain_work_does_not_depend_on_the_kernel(monkeypatch,
                                                  keys_per_chain):
    """The chains stream counts a call's work from the reference's
    sizes; replacing the device kernel leaves the count as it was, and
    the count matches the nnz the program's own span reports."""
    from bench.data import pcap
    from bench.streams.chains import Chains
    from repro.core import expr
    from repro.obs.trace import Tracer
    from _bench_small import CHAINS, config
    dep = pcap.deployment(config(CHAINS, backend="memory"), 7,
                          {"packets": 40000})
    try:
        spec = {"kind": "chains", "clients": 1, "chains_per_call": 4,
                "keys_per_chain": keys_per_chain, "band": "ip.dst|",
                "check_share": 1.0}
        s = Chains(spec, dep, 7, False, 1.0)
        calls = [s.draw(i) for i in range(2)]
        counts = [dep.chain_work(c) for c in calls]
        tracer = Tracer()
        root = tracer.start("t")
        with root:
            s.call(calls[0])
        tags = [sp["tags"] for sp in tracer.spans(root.trace_id)
                if sp["name"] == "kernel.spmm"]
        assert tags and tags[0]["nnz"] == counts[0][0]
        assert tags[0]["b"] == counts[0][3]

        def dense(asm, X):
            import jax.numpy as jnp
            return jnp.asarray(asm.toarray(), jnp.float32) @ X
        monkeypatch.setattr(expr, "_device_spmm_dev", dense)
        s.call(calls[1])
        assert [dep.chain_work(c) for c in calls] == counts
    finally:
        dep.close()
