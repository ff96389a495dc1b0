"""Pallas kernel micro-benchmarks.  Off the TPU the kernels run in
interpret mode (a correctness path: wall numbers there are not TPU
perf).  Compares each kernel's call against its compiled pure-jnp
oracle to document overhead and validate at benchmark shapes.

The SpMV-loop vs batched-SpMM section is the CI perf gate for the
batched analytics layer: answering b column queries as one SpMM launch
must beat b sequential SpMV launches (the per-query dispatch the
gateway used to pay) by ≥ 2x at b=8.  On a TPU it also reports achieved
HBM bandwidth against the device's published peak
(``repro.device.peaks``); off the TPU no peak share is reported, since a
CPU time is not a device metric.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.segsum import segsum
from repro.kernels.flash_attention import flash_attention
from repro.kernels.spmm import spmm_ell
from repro.kernels.spmv import spmv_ell

from .common import emit, smoke, timeit, write_trajectory


def spmm_roofline() -> None:
    """SpMV-loop vs batched SpMM at b ∈ {1, 8, 64}: wall time (interpret
    mode — dispatch-bound, which is exactly what batching removes) plus
    the HBM-traffic roofline model (achieved GB/s vs the TPU's peak)."""
    from repro.device import peaks

    dev = jax.devices()[0]
    hbm_bw = (peaks(dev.device_kind).hbm_bytes_per_s
              if dev.platform == "tpu" else None)

    def roofline(gbs):
        if hbm_bw is None:
            return {}
        return {"peak_gb_s": hbm_bw / 1e9,
                "pct_peak": round(100 * gbs * 1e9 / hbm_bw, 4)}

    R, C, K = (1024, 1024, 4) if smoke() else (2048, 2048, 4)
    br, bc = 256, 1024
    rng = np.random.default_rng(42)
    ecols = jnp.asarray(rng.integers(0, C, (R, K)), jnp.int32)
    evals = jnp.asarray(rng.normal(0, 1, (R, K)).astype(np.float32))
    ell_bytes = R * K * (4 + 4)                 # cols int32 + vals f32

    ratio_at_8 = None
    for b in (1, 8) if smoke() else (1, 8, 64):
        X = jnp.asarray(rng.normal(0, 1, (C, b)).astype(np.float32))

        def loop():
            for j in range(b):
                spmv_ell(ecols, evals, X[:, j], block_rows=br,
                         block_cols=bc).block_until_ready()

        def batched():
            spmm_ell(ecols, evals, X, block_rows=br,
                     block_cols=bc).block_until_ready()

        # equivalence at bench shape before timing it
        Y = np.stack([np.asarray(spmv_ell(ecols, evals, X[:, j],
                                          block_rows=br, block_cols=bc))
                      for j in range(b)], axis=1)
        ok = np.allclose(np.asarray(spmm_ell(ecols, evals, X,
                                             block_rows=br, block_cols=bc)),
                         Y, atol=1e-4)
        t_loop = timeit(loop, repeat=3)
        t_spmm = timeit(batched, repeat=3)
        # HBM traffic model: the loop streams the ELL block per query,
        # the batch streams it once
        bytes_loop = b * (ell_bytes + C * 4 + R * 4)
        bytes_spmm = ell_bytes + C * b * 4 + R * b * 4
        gbs_loop = bytes_loop / t_loop / 1e9
        gbs_spmm = bytes_spmm / t_spmm / 1e9
        speedup = t_loop / t_spmm
        emit(f"spmv_loop_b{b}", t_loop / b * 1e6,
             f"allclose={ok} gbs={gbs_loop:.3f}",
             achieved_gb_s=round(gbs_loop, 4), **roofline(gbs_loop))
        emit(f"spmm_batched_b{b}", t_spmm / b * 1e6,
             f"speedup={speedup:.2f}x gbs={gbs_spmm:.3f}",
             achieved_gb_s=round(gbs_spmm, 4), **roofline(gbs_spmm),
             speedup_vs_loop=round(speedup, 3))
        if b == 8:
            ratio_at_8 = speedup
    # the CI gate: one launch for 8 queries ≥ 2x the 8-launch loop
    assert ratio_at_8 is not None and ratio_at_8 >= 2.0, \
        f"batched SpMM only {ratio_at_8:.2f}x the SpMV loop at b=8 (< 2x)"


def main() -> None:
    key = jax.random.key(0)
    ids = jnp.sort(jax.random.randint(key, (50_000,), 0, 4096))
    vals = jnp.ones((50_000,))
    out = segsum(ids, vals, 4096, block_nnz=2048, block_seg=1024)
    exp = ref.segsum_ref(ids, vals, 4096)
    ok = bool(jnp.allclose(out, exp, atol=1e-3))
    t = timeit(lambda: ref.segsum_ref(ids, vals, 4096).block_until_ready(),
               repeat=5)
    emit("segsum_oracle_50k", t * 1e6, f"kernel_allclose={ok}")

    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    exp = ref.flash_attention_ref(q, k, v)
    ok = bool(jnp.allclose(out, exp, atol=1e-4))
    t = timeit(lambda: ref.flash_attention_ref(q, k, v).block_until_ready(),
               repeat=5)
    emit("flash_attn_oracle_256", t * 1e6, f"kernel_allclose={ok}")

    spmm_roofline()
    write_trajectory("kernels")


if __name__ == "__main__":
    main()
