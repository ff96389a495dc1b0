"""Shared benchmark utilities: timing, CSV emission, and machine-readable
JSON trajectory files (``BENCH_<name>.json``, one run appended per line)."""
from __future__ import annotations

import json
import os
import time

import jax

_RECORDS: list = []


def timeit(fn, *, repeat: int = 3, warmup: int = 1) -> float:
    """Median wall seconds of fn()."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def smoke() -> bool:
    """True in CI's reduced-size bench smoke mode (BENCH_SMOKE=1)."""
    return os.environ.get("BENCH_SMOKE") == "1"


def emit(name: str, us_per_call: float, derived: str = "",
         **metrics) -> None:
    """Print the CSV line and record it (plus structured ``metrics`` like
    ``entries_per_s`` or ``cache_hit_rate``) for :func:`write_trajectory`."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    _RECORDS.append({"name": name, "us_per_call": round(us_per_call, 3),
                     "derived": derived, **metrics})


def write_trajectory(bench: str) -> str:
    """Append this run's records to ``BENCH_<bench>.json`` (JSONL — one
    run object per line, so successive runs form a trajectory).  The
    output directory defaults to cwd; override with BENCH_OUT_DIR.  Each
    run names the device it ran on."""
    path = os.path.join(os.environ.get("BENCH_OUT_DIR", "."),
                        f"BENCH_{bench}.json")
    devs = jax.devices()
    run = {"bench": bench, "unix_time": round(time.time(), 3),
           "smoke": smoke(),
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "records": list(_RECORDS)}
    with open(path, "a") as f:
        f.write(json.dumps(run) + "\n")
    _RECORDS.clear()
    return path
