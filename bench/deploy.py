"""What every deployment under test shares: the ``DB()`` binding over
the configured backend, the gateway when a stream speaks HTTP, and the
tracer that collects the program's spans in a traced run."""
from __future__ import annotations

import time


class Deployment:
    def __init__(self, cfg: dict, seed: int):
        from repro.db import DB
        from repro.obs.trace import Tracer
        self.cfg = cfg
        self.seed = int(seed)
        self.table = DB(*cfg["tables"], backend=cfg["backend"],
                        n_instances=int(cfg["n_instances"]))
        self.tracer = Tracer(max_traces=4096, max_spans=4096)
        self.gateway = None
        self.timings: dict = {}

    # -- set-up ------------------------------------------------------------
    def load(self, A) -> int:
        """Ingest ``A`` through the program's synchronous ``put``."""
        from repro.db import put
        t0 = time.perf_counter()
        n = put(self.table, A)
        self.table.flush()
        self.timings["ingest_s"] = time.perf_counter() - t0
        return n

    def gateway_address(self) -> str:
        if self.gateway is None:
            from repro.serve import Gateway, Tenant, TokenAuth
            from .load import TOKEN
            tenant = Tenant("bench", rate=1e9, burst=1e9, max_jobs=64)
            self.gateway = Gateway(self.table, TokenAuth({TOKEN: tenant}))
            self.gateway.start()
        return self.gateway.address

    def close(self) -> None:
        if self.gateway is not None:
            self.gateway.stop()
        self.table.close()
        close = getattr(self.table.backend, "close", None)
        if close is not None:           # shard servers and their clients
            close()
