#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's deployment from the seed (data made by ``bench/data``,
ingested through the program's own ``put``), warms up the shapes its
traffic uses, measures for ``--seconds`` under the traffic mix, checks
what the window produced against the plain reference, and prints one
JSON object as the last line of standard output.  ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` takes a profiler trace of
the window and reports its per-layer metrics.  The numbers compared,
each with its limit, are the last lines on standard error and the last
key of the result.

Exits 2, printing no result, when JAX finds no TPU, or another number
of chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import manifest as M  # noqa: E402
from bench.load import Window, make_streams, run_window  # noqa: E402

TRACE_DIR = ROOT / "bench_out" / "trace"


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def stream(self, kind: str):
        for s in self.streams:
            if s.kind == kind:
                return s
        return None

    def counter(self, name: str, when: str, **labels) -> float:
        snap = self.counters_before if when == "before" \
            else self.counters_after
        tot = 0.0
        for (n, lab), v in snap.items():
            if n == name and all(dict(lab).get(k) == x
                                 for k, x in labels.items()):
                tot += v
        return tot

    def delta(self, name: str, **labels) -> float:
        return (self.counter(name, "after", **labels)
                - self.counter(name, "before", **labels))


def _device_record(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, overrides: dict | None = None,
             control: bool = False, root: Path = ROOT,
             traffic: dict | None = None):
    """Run one cell; returns the result dict, or None when no chip.
    ``overrides`` (configuration sizes) and ``traffic`` (a whole mix)
    let tests drive a run at a size a CPU holds."""
    cell = M.Cell(M.load(root), workload, root)
    if traffic is not None:
        cell.traffic = traffic
    import jax
    devs = jax.devices()
    if (require_chip and devs[0].platform != "tpu") \
            or len(devs) != cell.chips:
        # the program shards over every device it sees, so a cell runs
        # only where it sees exactly the chips it names
        print(f"bench: {workload} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return None
    from bench.compile_meter import CompileMeter
    from repro.obs.metrics import REGISTRY
    meter = CompileMeter()
    dep = cell.data_module().deployment(cell.config, seed, overrides or {})
    try:
        streams = make_streams(cell.traffic, dep, seed, trace, seconds,
                               root)
        for s in streams:
            s.warm()
        trace_dir = TRACE_DIR / f"{workload}-{seed}"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        window = Window(seconds)
        before = REGISTRY.as_dict()
        m0 = meter.snapshot()
        setup_s = time.perf_counter() - T_START
        with jax.profiler.TraceAnnotation("bench.window"):
            errors = run_window(streams, window)
        m1 = meter.snapshot()
        after = REGISTRY.as_dict()
        reduced = None
        if trace:
            jax.profiler.stop_trace()
            from bench import trace_reduce
            reduced = trace_reduce.reduce(trace_reduce.load(str(trace_dir)))
        device = _device_record(jax, cell.chips)
        run = Run(cell=cell, dep=dep, streams=streams, window=window,
                  setup_s=setup_s, trace=reduced, counters_before=before,
                  counters_after=after, compiles=m1["compiles"]
                  - m0["compiles"], device=device, meter=m1)
        metrics = {}
        for m in cell.metrics(trace):
            v = M.reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if control:
            for s in streams:
                s.use_control()
        checks, checked = _checks(streams, root)
    finally:
        dep.close()
    attempted = sum(s.n_attempted for s in streams)
    failed = sum(s.n_failed for s in streams) + len(errors)
    correct = (not errors and all(c["value"] <= c["limit"]
                                  for c in checks.values()))
    for e in errors:
        print(f"bench: stream error: {type(e).__name__}: {e}",
              file=sys.stderr)
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["info"] = {"compiles_in_window": run.compiles,
                   "setup_compile_s": m0["compile_s"],
                   "setup_compiles": m0["compiles"],
                   "cache_hits": m1["cache_hits"],
                   "ingest_s": dep.timings.get("ingest_s"), **checked}
    for s in streams:
        out["info"].update(s.info())
    out["checks"] = checks
    return out


def _checks(streams, root) -> tuple:
    """(numbers compared, each with its limit; counts of what was
    compared)."""
    lim = M.limits(root)
    out, counts = {}, {}
    for s in streams:
        for name, value, key in s.check():
            if key is None:
                counts[name] = int(value)
            else:
                out[name] = {"value": float(value), "limit": lim[key]}
    return out, counts


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="1: compare the control's answers in place of the "
                        "program's (for setting limits; never a measurement)")
    a = p.parse_args(argv)
    # a fixed directory inside the checkout, whatever the environment
    # names: the program's cache switch takes the benchmark's directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.device import enable_compile_cache
    enable_compile_cache()
    out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                   control=bool(a.control))
    if out is None:
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
