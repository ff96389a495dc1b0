"""Closed-loop gateway jobs: ``clients`` analysts each submitting
``POST /v1/jobs``, polling every ``poll_s`` until done, fetching the
result, then submitting the next.  Every completed job is checked."""
from __future__ import annotations

import http.client
import json
import threading
import time

from bench.load import TOKEN, Stream


class Jobs(Stream):
    def __init__(self, spec, dep, seed, traced, seconds):
        super().__init__(spec, dep, seed, traced, seconds)
        self.done: list = []            # (t_done, result)
        self.took: list = []            # seconds of each job, in order
        self.n_failed = 0
        self._lock = threading.Lock()

    def _call(self, method: str, path: str, body=None):
        host, port = self.dep.gateway_address().split(":")
        c = http.client.HTTPConnection(host, int(port), timeout=600)
        headers = {"Authorization": f"Bearer {TOKEN}"}
        raw = None
        if body is not None:
            raw = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        try:
            c.request(method, path, body=raw, headers=headers)
            r = c.getresponse()
            data = r.read()
        finally:
            c.close()
        return r.status, json.loads(data)

    def job(self):
        status, job = self._call("POST", "/v1/jobs", {
            "kind": self.spec["job"], "params": self.spec["params"]})
        if status != 200:
            raise RuntimeError(f"job submit -> {status}: {job}")
        while True:
            _, st = self._call("GET", f"/v1/jobs/{job['job']}")
            if st["status"] in ("done", "failed"):
                break
            time.sleep(float(self.spec["poll_s"]))
        status, res = self._call("GET", f"/v1/jobs/{job['job']}/result")
        return (res["result"] if status == 200 else None), st

    def warm(self) -> None:
        res, st = self.job()
        if res is None:
            raise RuntimeError(f"warm-up job failed: {st}")

    def _client(self, window) -> None:
        from jax.profiler import TraceAnnotation
        while window.open():
            t0 = time.perf_counter()
            with TraceAnnotation(f"bench.job {self.spec['job']}"):
                res, _ = self.job()
            t = time.perf_counter()
            with self._lock:
                self.took.append(t - t0)
                if res is None:
                    self.n_failed += 1
                else:
                    self.done.append((t, res))

    def threads(self, window):
        return [self._client] * int(self.spec["clients"])

    @property
    def n_attempted(self) -> int:
        return len(self.done) + self.n_failed

    def use_control(self) -> None:
        self.done = [(t, self.dep.control_job(self.spec))
                     for t, _ in self.done]

    def info(self) -> dict:
        return {"job_seconds": self.took}

    def check(self) -> list:
        rel, missed = self.dep.job_bad(self.spec, [r for _, r in self.done])
        return [("jobs_failed", self.n_failed, "jobs_failed"),
                ("rank_rel_err", rel, "rank_rel_err"),
                ("top_missed", missed, "top_missed"),
                ("jobs_checked", len(self.done), None)]


STREAM = Jobs
