"""The last line a run prints: its keys and their types, with the
profiler trace's keys in a traced run; and no result without a chip."""
import json
import os
import subprocess
import sys

from bench.manifest import ROOT
from _bench_small import CHAINS, PAGERANK, run


def _common(out):
    assert isinstance(out["correct"], bool)
    assert isinstance(out["attempted"], int) and out["attempted"] > 0
    assert isinstance(out["failed"], int)
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for name, m in out["metrics"].items():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


def test_untraced_line_reports_end_to_end_metrics():
    out = run(CHAINS)
    _common(out)
    assert set(out["metrics"]) == {"chain_rate", "setup_s"}
    assert "breakdown" not in out


def test_traced_line_reports_per_layer_metrics_and_trace():
    out = run(CHAINS, trace=True)
    _common(out)
    assert "chain_rate" not in out["metrics"]
    assert {"planner_ms.chains", "compiles_in_window.chains"} <= set(
        out["metrics"])
    # every shape is warmed in set-up: nothing compiles in the window
    assert out["metrics"]["compiles_in_window.chains"]["value"] == 0
    dev = out["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] >= 0
    b = out["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CHAINS,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_cell_sees_exactly_its_chips():
    """The program shards over every device it sees: a one-chip cell on
    a host that shows four devices is refused, printing no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import sys; sys.path[:0] = ['src', '.']\n"
            "from bench.run import run_cell\n"
            f"print(run_cell({PAGERANK!r}, 1, 0.1, False, "
            "require_chip=False, overrides={'scale': 6}))")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "None"
    assert "needs 1 TPU chip(s); JAX finds 4 cpu device(s)" in p.stderr
