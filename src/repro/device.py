"""The accelerator side of a run: the persistent compile cache and the
published peaks of each device kind.

Nothing here runs at import.  Entry points (``chip_smoke.py``,
``python -m repro.serve``, ``python -m benchmarks.run``) call
:func:`enable_compile_cache` once before their first compile.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path

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
