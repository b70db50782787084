"""BENCHMARK.json keeps the benchmark contract's shape and limits."""
import json
import re

from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_contract():
    raw = (ROOT / "BENCHMARK.json").read_text()
    assert len(raw.encode()) <= 64 * 1024
    bm = json.loads(raw)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert all(line(w) for w in bm["command"]) and len(bm["command"]) <= 32
    assert 1 <= bm["run_seconds"] <= 51
    for p in bm["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(bm["paths"][0] + "/")
        assert (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                                for k in c["reduced"])
        data = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size")), k
            assert data["published"][k] != data[k], k
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    assert {w["config"] for w in bm["workloads"]} == set(configs)
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= {w["name"] for w in
                                               bm["workloads"]}
    for m in bm["per_layer"]:
        assert line(m["layer"]) and m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in bm["workloads"]:
        mine = [m for m in bm["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        assert any(w["name"] in m["workloads"] for m in bm["per_layer"])
