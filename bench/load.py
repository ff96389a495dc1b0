"""The one general load generator.  A traffic mix is a JSON file under
``bench/traffic/`` that lists streams; each stream names its ``kind``,
the module ``bench/streams/<kind>.py`` that drives it (found by name, so
a new kind is a new file), and gives its parameters.  Every stream:

* draws its requests from ``--seed``, with sizes that do not depend on
  the seed, so every seed does the same amount of work;
* warms up the shapes it will use (``warm``), in set-up;
* runs until the window's deadline (``threads``), finishing what it
  started;
* keeps the answers it is to be checked on, and compares them with the
  deployment's plain reference once the window has closed (``check``),
  returning ``(name, reading, limit key)`` triples.
"""
from __future__ import annotations

import importlib.util
import re
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
TOKEN = "bench-token"
KIND = re.compile(r"^[a-z][a-z0-9_]{0,63}$")


class Window:
    """The measured interval, on the host's clock."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t0 = None
        self.deadline = None

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds

    def open(self) -> bool:
        return time.perf_counter() < self.deadline


class Stream:
    """Common plumbing: spans collected per request in a traced run."""

    def __init__(self, spec: dict, dep, seed: int, traced: bool,
                 seconds: float):
        self.spec = spec
        self.kind = spec["kind"]
        self.dep = dep
        self.seed = int(seed)
        self.traced = traced
        self.seconds = float(seconds)
        self.spans: list = []           # one list of span dicts per request

    def warm(self) -> None:
        pass

    def threads(self, window: Window) -> list:
        raise NotImplementedError

    def check(self) -> list:
        return []

    def use_control(self) -> None:
        """Put the control's answers in the program's place."""

    def info(self) -> dict:
        """What the run's ``info`` should show of this stream."""
        return {}

    n_failed = 0


def stream_class(kind: str, root: Path = ROOT):
    """The ``STREAM`` class of ``bench/streams/<kind>.py``."""
    if not KIND.match(kind):
        raise ValueError(f"bad stream kind {kind!r}")
    path = root / "bench" / "streams" / f"{kind}.py"
    spec = importlib.util.spec_from_file_location(f"bench_stream_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.STREAM


def make_streams(traffic: dict, dep, seed: int, traced: bool,
                 seconds: float, root: Path = ROOT) -> list:
    return [stream_class(spec["kind"], root)(spec, dep, seed, traced,
                                             seconds)
            for spec in traffic["streams"]]


def run_window(streams: list, window: Window) -> list:
    """Start every stream's threads at once, wait for all; returns the
    errors they raised."""
    errors = []

    def guard(fn):
        def body():
            try:
                fn(window)
            except BaseException as e:     # noqa: BLE001 — reported
                errors.append(e)
        return body

    threads = [threading.Thread(target=guard(fn), daemon=True)
               for s in streams for fn in s.threads(window)]
    window.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors
