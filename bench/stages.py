"""The program's stages over a run: their mean times from the
``repro_stage_seconds`` histogram, and the device-idle time of a traced
window attributed to the stage open on the host.

A stage (``repro.obs.stage``) times itself into
``repro_stage_seconds{stage=<name>}`` and, while a profiler trace runs,
is a host annotation of the same name on the device's clock.  So each
stretch of the window in which the device ran nothing is named by the
innermost stage open on any host thread, or by none: that share is
what no stage explains yet.

    python3 bench/stages.py <trace dir or .xplane.pb> [stage ...]

prints the device-idle seconds of the window by innermost stage (by
default every host annotation named like a program stage).
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce as R  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / "bench_out" / "trace"      # as bench/run.py names it
NONE = ""                                      # idle time under no stage
# the program's stage names: <layer>.<what>[.<part>]
PROGRAM_STAGE = re.compile(r"^(planner|kernel|job|analytics)\.[\w.]+$")


def has(run, name: str) -> bool:
    """Whether the program exposes the family ``name`` at all."""
    return any(n == name for n, _ in run.counters_after)


def mean_ms(run, stage: str):
    """Mean milliseconds of ``stage`` over the window, or None when the
    window observed none."""
    n = run.delta("repro_stage_seconds_count", stage=stage)
    if n <= 0:
        return None
    return 1e3 * run.delta("repro_stage_seconds_sum", stage=stage) / n


def stage_names(run) -> set:
    """Every stage the program had observed by the window's end."""
    return {dict(lab)["stage"] for n, lab in run.counters_after
            if n == "repro_stage_seconds_count"}


def _device_busy(planes: list, w0: float, w1: float) -> list:
    """Union of the device operations' intervals, clipped to the window,
    over every device plane (the line ``trace_reduce`` reads)."""
    ivs = []
    for name, lines in planes:
        if not R.DEVICE_PLANE.match(name):
            continue
        names = [ln for ln, _ in lines]
        use = next((n for n in R.OPS_LINES if n in names), None)
        for lname, events in lines:
            if lname != use:
                continue
            for _, s, d in events:
                s, e = max(s, w0), min(s + d, w1)
                if e > s:
                    ivs.append((s, e))
    return R._union(ivs)


def idle_by_stage(planes: list, stages, marker: str = R.WINDOW) -> dict:
    """Device-idle seconds of the window by the innermost (shortest) of
    ``stages`` open on any host thread; key ``NONE`` for idle time under
    no stage.  ``stages`` is a set of names or a predicate on a name."""
    win = R.window_of(planes, marker)
    if win is None:
        raise ValueError(f"no host annotation {marker!r} in the trace")
    w0, w1 = win
    is_stage = stages if callable(stages) else set(stages).__contains__
    marks = []          # (t, order, key): ends sort before starts at a t
    for name, lines in planes:
        if not name.startswith("/host"):
            continue
        for _, events in lines:
            for ev, s, d in events:
                s, e = max(s, w0), min(s + d, w1)
                if e > s and is_stage(ev):
                    key = (d, len(marks), ev)
                    marks += [(s, 2, key), (e, 0, key)]
    edges = [w0] + [x for iv in _device_busy(planes, w0, w1)
                    for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            marks += [(a, 3, None), (b, 1, None)]
    marks.sort(key=lambda m: (m[0], m[1]))
    out: dict = {}
    active: set = set()
    idle, prev = False, w0
    for t, order, key in marks:
        if idle and t > prev:
            name = min(active)[2] if active else NONE
            out[name] = out.get(name, 0.0) + (t - prev) * 1e-9
        prev = t
        if key is None:
            idle = order == 3
        elif order == 2:
            active.add(key)
        else:
            active.discard(key)
    return out


def idle_unattributed_pct(run):
    """Share of the window's device-idle time under no stage, in %;
    None where the program has no stages or the run took no trace."""
    if run.trace is None:
        return None
    names = stage_names(run)
    if not names:
        return None
    planes = R.load(str(TRACE_DIR / f"{run.cell.name}-{run.dep.seed}"))
    idle = idle_by_stage(planes, names)
    total = sum(idle.values())
    return 100.0 * idle.get(NONE, 0.0) / total if total > 0 else 0.0


def main(argv: list) -> int:
    if not argv:
        print("usage: python3 bench/stages.py <trace dir or .xplane.pb> "
              "[stage ...]", file=sys.stderr)
        return 2
    stages = set(argv[1:]) or PROGRAM_STAGE.match
    idle = idle_by_stage(R.load(argv[0]), stages)
    total = sum(idle.values())
    for name, s in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"{name or '(no stage)'}\t{s:.6f} s\t"
              f"{100.0 * s / total:.3f}%")
    print(f"device idle\t{total:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
