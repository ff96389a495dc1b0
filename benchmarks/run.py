"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see each module's
docstring for the paper artifact it reproduces):

* bench_pipeline_scaling — Fig. 5 (stage speedup vs workers)
* bench_ingest           — §IV-F (multi-instance DB topology)
* bench_expansion        — §IV-A/C/D (per-stage data expansion)
* bench_loc              — §IV-G (135-line user pipeline claim)
* bench_query            — Fig. 2 (connection queries)
* bench_lsm              — persistent LSM backend vs memory (+ recovery)
* bench_net              — networked shard backend (batched RPC ingest,
                           chunk-streamed scans, sync barrier)
* bench_analytics        — §III-A (device-side graph algebra)
* bench_kernels          — Pallas kernels vs oracles
* bench_stream           — streaming rollup tap overhead + detector
                           latency per closed window
* bench_obs              — metrics/tracing overhead gates (untraced
                           hot path ≤5%, traced ≤25%)
"""
from __future__ import annotations

import sys
import traceback


def main() -> int:
    """Run every module; returns the number of modules that failed."""
    from repro.device import enable_compile_cache

    from . import (bench_analytics, bench_expansion, bench_ingest,
                   bench_kernels, bench_loc, bench_lsm, bench_net,
                   bench_obs, bench_pipeline_scaling, bench_query,
                   bench_serving, bench_stream)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    for mod in (bench_loc, bench_expansion, bench_query, bench_ingest,
                bench_lsm, bench_net, bench_analytics, bench_kernels,
                bench_serving, bench_stream, bench_obs,
                bench_pipeline_scaling):
        try:
            mod.main()
        except Exception:
            print(f"{mod.__name__},FAILED,")
            traceback.print_exc()
            failed += 1
    return failed


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
