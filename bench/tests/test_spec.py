"""Cells, configurations, traffic, limits and metric readers are found
by the names in BENCHMARK.json; a new one is new files plus entries."""
import json
import shutil
import types

import pytest

from bench import flops, spec
from bench.tests.conftest import FIXTURES, ROOT


def test_every_cell_and_metric_resolves():
    bm = spec.load_benchmark(ROOT)
    for w in bm["workloads"]:
        c = spec.cell(bm, w["name"], ROOT)
        assert c["config"]["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in c["end_to_end"])
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
        assert c["limits"] and all(v > 0 for v in c["limits"].values())
        assert set(c["limits"]) <= {"served_gap", "mean_gap"}
    for m in bm["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_metric_falls_back_to_its_quantity_reader():
    assert spec.metric_reader("step_ms.anything").__module__ == \
        "bench_metric_step_ms"
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric.decode")


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.cell(spec.load_benchmark(ROOT), "no.such.cell", ROOT)


def _copy_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "bench"


def test_a_new_cell_and_metric_need_only_new_files(tmp_path):
    """Copy the benchmark, add an architecture, a configuration, a traffic
    mix, a limit, a metric reader and their entries -- editing no file
    that exists."""
    b = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    shutil.copy(FIXTURES / "arch_window_mixed.py",
                b / "arch" / "window_mixed.py")
    cfg = json.loads((b / "configs" / "phi4-mini-3.8b.json").read_text())
    cfg.update(name="phi4-mini-bf16", architecture="window_mixed",
               sliding_window=1024)
    cfg["policy"]["weight_bits"] = [16]
    (b / "configs" / "phi4-mini-bf16.json").write_text(json.dumps(cfg))
    t = json.loads((b / "traffic" / "prefill-long.json").read_text())
    t["clients"] = 16
    t["engine"]["max_slots"] = 16
    (b / "traffic" / "decode-16.json").write_text(json.dumps(t))
    (b / "limits" / "phi4-mini-bf16.decode-16.json").write_text(
        '{"served_gap": 0.5}')
    (b / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps))\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "phi4-mini-bf16", "source": "x",
                          "file": "bench/configs/phi4-mini-bf16.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "phi4-mini-bf16.decode-16",
                            "config": "phi4-mini-bf16",
                            "traffic": "decode-16", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "steps_seen.decode16", "unit": "steps",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler", "moves": "out_tok_s",
                            "workloads": ["phi4-mini-bf16.decode-16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    c = spec.cell(spec.load_benchmark(tmp_path), "phi4-mini-bf16.decode-16",
                  tmp_path)
    assert c["config"]["policy"]["weight_bits"] == [16]
    assert [k.kind for k in c["arch"].lm_config(c["config"]).pattern] == \
        ["local_attn", "attn"]
    dense = spec.cell(spec.load_benchmark(tmp_path),
                      "phi4-mini.prefill-long", tmp_path)["arch"]
    step = [(0, 4096, True)]
    mixed = flops.step_work(c["arch"], c["config"], step, 0.0)
    full = flops.step_work(dense, c["config"], step, 0.0)
    assert mixed["attn_flops"] < full["attn_flops"]
    assert mixed["model_flops"] - mixed["attn_flops"] == \
        full["model_flops"] - full["attn_flops"]
    assert c["traffic"]["clients"] == 16
    assert [m["name"] for m in c["per_layer"]] == ["steps_seen.decode16"]
    read = spec.metric_reader("steps_seen.decode16", b)
    assert read(types.SimpleNamespace(steps=[[], []])) == 2.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


@pytest.mark.parametrize("architecture", [None, "no_such_arch"])
def test_a_configuration_must_name_an_architecture_it_has(tmp_path,
                                                          architecture):
    b = _copy_benchmark(tmp_path)
    path = b / "configs" / "phi4-mini-3.8b.json"
    cfg = json.loads(path.read_text())
    del cfg["architecture"]
    if architecture:
        cfg["architecture"] = architecture
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="architecture"):
        spec.cell(spec.load_benchmark(tmp_path), "phi4-mini.prefill-long",
                  tmp_path)
