"""Closed-loop ``cdlp`` gateway jobs (``bench.streams.jobs``), each
completed job checked against the deployment's CDLP reference: the
labels digest and community count exactly, and the top communities
entry by entry."""
from __future__ import annotations

import time

from bench.streams.jobs import Jobs

SCAN_STAGE = ("repro_stage_seconds_sum", (("stage", "analytics.cdlp.scan"),))


def _scan_seconds() -> float:
    from repro.obs.metrics import REGISTRY
    return REGISTRY.as_dict().get(SCAN_STAGE, 0.0)


class CdlpJobs(Jobs):
    def __init__(self, spec, dep, seed, traced, seconds):
        super().__init__(spec, dep, seed, traced, seconds)
        # the job metrics (job_s among them) read the job stream by kind
        self.kind = "jobs"

    def warm(self) -> None:
        """The first job scans the bands, then compiles the label
        program.  A cold compile (about 40 s on one v5e) ages the scans'
        cache entries so far that the scan cache's TTL would run out
        inside the window, and one job there would re-scan the bands.
        Then wait for the entries to expire and warm up once more, so
        that every window starts, as in the other job cells, on entries
        that outlive it."""
        t0, s0 = time.perf_counter(), _scan_seconds()
        super().warm()
        scanned = t0 + _scan_seconds() - s0
        expires = scanned + float(self.dep.table.cache_ttl)
        if time.perf_counter() + self.seconds > expires:
            time.sleep(max(0.0, expires - time.perf_counter()) + 1.0)
            super().warm()

    def check(self) -> list:
        bad, missed = self.dep.job_bad(self.spec, [r for _, r in self.done])
        return [("jobs_failed", self.n_failed, "jobs_failed"),
                ("labels_bad", bad, "jobs_failed"),
                ("communities_missed", missed, "top_missed"),
                ("jobs_checked", len(self.done), None)]


STREAM = CdlpJobs
