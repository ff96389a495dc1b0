"""The manifest: every name in BENCHMARK.json resolves to its files, the
file keeps to the benchmark contract's shape, and a new cell is added
by adding files and one entry."""
import json
import re
import shutil

import pytest

from bench import manifest as M

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return M.load()


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["bench"]
    assert man["command"][1] == "bench/run.py"
    assert 1 <= man["run_seconds"] <= 51


def test_names_units_and_bounds(man):
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    names += [w["name"] for w in man["workloads"]]
    names += [c["name"] for c in man["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
    for x in man["workloads"] + man["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", [w["name"] for w in M.load()["workloads"]])
def test_cell_resolves_every_file(man, cell):
    c = M.Cell(man, cell)
    assert c.chips in (1, 4)
    assert c.config["name"] == c.spec["config"]
    assert set(c.config_entry["reduced"]) <= set(c.config["reduced"])
    assert c.data_module().deployment
    assert c.traffic["streams"]
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(M.reader(m["name"]))
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= set(e2e)


def test_limits_cover_every_check():
    lim = M.limits()
    assert {"chain_rows_bad", "chain_rel_err", "jobs_failed",
            "rank_rel_err", "top_missed"} == set(lim)


def test_new_cell_is_files_and_one_entry(tmp_path):
    """A new traffic mix, a new stream kind, a new metric and a new cell
    resolve without touching a file that exists."""
    from bench.load import stream_class
    shutil.copytree(M.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = M.load()
    (tmp_path / "bench" / "traffic" / "chains_wide.json").write_text(
        json.dumps({"streams": [{"kind": "chains", "clients": 2,
                                 "chains_per_call": 16,
                                 "keys_per_chain": 64, "band": "ip.dst|",
                                 "check_share": 0.1},
                                {"kind": "bursts", "on_s": 1.0}]}))
    (tmp_path / "bench" / "streams" / "bursts.py").write_text(
        "from bench.load import Stream\n\n\n"
        "class Bursts(Stream):\n    pass\n\n\nSTREAM = Bursts\n")
    (tmp_path / "bench" / "metrics" / "calls_done.chains.py").write_text(
        "def read(run):\n    return run.calls\n")
    man["workloads"].append({"name": "pcap_2shard.chains_wide",
                             "config": "pcap_2shard",
                             "traffic": "chains_wide", "chips": 1,
                             "why": "wider batches"})
    man["per_layer"].append({"name": "calls_done.chains", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "planner", "moves": "chain_rate",
                             "workloads": ["pcap_2shard.chains_wide"]})
    man["end_to_end"][[m["name"] for m in man["end_to_end"]].index(
        "chain_rate")]["workloads"].append("pcap_2shard.chains_wide")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    c = M.Cell(M.load(tmp_path), "pcap_2shard.chains_wide", tmp_path)
    assert c.traffic["streams"][0]["chains_per_call"] == 16
    kinds = [stream_class(sp["kind"], tmp_path).__name__
             for sp in c.traffic["streams"]]
    assert kinds == ["Chains", "Bursts"]
    assert [m["name"] for m in c.per_layer] == ["calls_done.chains"]
    assert sorted(m["name"] for m in c.end_to_end) == ["chain_rate",
                                                       "setup_s"]
    read = M.reader("calls_done.chains", tmp_path)

    class Run:
        calls = 7
    assert read(Run()) == 7
