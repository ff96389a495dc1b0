"""Reductions over the program's spans, one list of span records
(``repro.obs.Tracer.spans``) per request or call."""
from __future__ import annotations


def _covered(iv: list) -> float:
    tot, end = 0.0, None
    for s, e in sorted(iv):
        if end is None or s > end:
            tot += e - s
            end = e
        elif e > end:
            tot += e - end
            end = e
    return tot


def self_seconds(spans: list, prefix: str) -> float:
    """Time inside spans named ``prefix*`` not covered by their children."""
    kids: dict = {}
    for sp in spans:
        kids.setdefault(sp["parent_id"], []).append(sp)
    tot = 0.0
    for sp in spans:
        if not sp["name"].startswith(prefix):
            continue
        iv = [(c["start"], c["start"] + c["dur_s"])
              for c in kids.get(sp["span_id"], [])]
        tot += max(sp["dur_s"] - _covered(iv), 0.0)
    return tot


def mean_ms(per_request: list, fn) -> float | None:
    if not per_request:
        return None
    return 1e3 * sum(fn(s) for s in per_request) / len(per_request)
