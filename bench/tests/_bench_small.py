"""Tiny sizes at which a CPU drives a whole benchmark run, and the
manifest's cells by the data they run on."""
import copy
import json

from bench import manifest as M

SIZES = {"pcap": {"packets": 33000}, "kronecker": {"scale": 8}}


def _cells_of(data: str) -> list:
    man = M.load()
    return [w["name"] for w in man["workloads"]
            if M.Cell(man, w["name"]).config["data"] == data]


CHAINS = _cells_of("pcap")[0]
PAGERANK = _cells_of("kronecker")[0]


def config(workload: str, **kw) -> dict:
    return dict(M.Cell(M.load(), workload).config, **kw)


def traffic(workload: str) -> dict:
    t = copy.deepcopy(M.Cell(M.load(), workload).traffic)
    for s in t["streams"]:
        if s["kind"] == "chains":
            s.update(check_share=0.5)
    return t


def run(workload: str, seconds: float = 0.5, trace: bool = False,
        control: bool = False, seed: int = 2 ** 31 + 77) -> dict:
    from bench.run import run_cell
    data = M.Cell(M.load(), workload).config["data"]
    return run_cell(workload, seed, seconds, trace, require_chip=False,
                    overrides=SIZES[data], control=control,
                    traffic=traffic(workload))
