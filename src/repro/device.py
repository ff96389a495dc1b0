"""The accelerator side of a run: the persistent compile cache, the
counters of what crosses to the device, and the published peaks of each
device kind.

Nothing here touches ``jax`` at import.  Entry points (``chip_smoke.py``,
``python -m repro.serve``, ``python -m benchmarks.run``) call
:func:`enable_compile_cache` once before their first compile; the
gateway calls :func:`count_compiles`.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from pathlib import Path

from .obs.metrics import REGISTRY

_H2D_BYTES = REGISTRY.counter(
    "repro_h2d_bytes_total",
    "Bytes handed to the device by the served paths, at their size on "
    "the device", labels=("site",))
# the sites are fixed; the family holds children weakly, so pin them
_H2D = {site: _H2D_BYTES.labels(site=site)
        for site in ("coo", "dense", "pagerank", "cdlp")}
_COMPILES = REGISTRY.counter(
    "repro_xla_compiles_total",
    "XLA programs built by this process: compiled, or loaded from the "
    "persistent compile cache")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles_lock = threading.Lock()
_compiles_watched = False


def count_h2d(site: str, *arrays) -> None:
    """Count ``arrays``, just placed on the device, as bytes handed over
    at ``site`` (``coo``, ``dense``, ``pagerank`` or ``cdlp``)."""
    _H2D[site].inc(sum(int(a.nbytes) for a in arrays))


def _on_compile(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _COMPILES.inc()


def count_compiles() -> None:
    """Count every XLA program this process builds (JAX's
    ``backend_compile_duration`` event: a compile, or a load from the
    persistent cache) in ``repro_xla_compiles_total``.  Idempotent: JAX's
    listeners cannot be removed, so one is registered once."""
    global _compiles_watched
    with _compiles_lock:
        if _compiles_watched:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _compiles_watched = True


def compiles() -> int:
    """Programs counted since :func:`count_compiles` was first called."""
    return int(_COMPILES.value)

# A fixed directory inside the checkout (listed in .gitignore).  The
# cache key includes the path, so a directory named after a pid, a temp
# name or the time would never hit.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and then no other
    path is configured.  Every compile is cached, however short: a cold
    process on the chip compiles dozens of small programs.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    bf16_flops: float              # FLOP/s
    hbm_bytes_per_s: float         # B/s
    ici_link_bytes_per_s: float    # B/s per chip-to-chip link
    source: str


# Keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9,
        ici_link_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect over 4 links"),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None
