"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's correctness limits is a file of its own,
found by name, so a new cell or metric is new files plus new entries:

* ``configs/<config>.json``  -- via the ``file`` of the config entry;
* ``traffic/<traffic>.json`` -- the mix the general generator reads;
* ``metrics/<metric>.py``    -- the reader of one per-layer metric; a
  metric named ``<quantity>.<suffix>`` falls back to ``<quantity>.py``
  when it has no file of its own (one reader per quantity, split by cell);
* ``limits/<cell>.json``     -- the limits of the cell's correctness check.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(LookupError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"unknown {what} {name!r}; known: "
                    f"{sorted(e['name'] for e in entries)}")


def cell(bm: dict, workload: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one run of ``workload`` needs, loaded from its files."""
    w = _by_name(bm["workloads"], workload, "workload")
    c = _by_name(bm["configs"], w["config"], "config")
    bench = root / "bench"
    cfg = json.loads((root / c["file"]).read_text())
    traffic_path = bench / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"no traffic file {traffic_path}")
    limits_path = bench / "limits" / f"{workload}.json"
    if not limits_path.is_file():
        raise SpecError(f"no limits file {limits_path}")
    limits = json.loads(limits_path.read_text())
    if not {"served_gap", "mean_gap"} & set(limits):
        raise SpecError(f"{limits_path} names no number to compare")
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bm["per_layer"]
             if workload in m.get("workloads", [workload])]
    return {"workload": w, "config": cfg,
            "traffic": json.loads(traffic_path.read_text()),
            "limits": limits,
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(name: str, bench: pathlib.Path = BENCH_DIR):
    """The ``read(ctx)`` function of one per-layer metric."""
    metrics = bench / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file():
        path = metrics / f"{name.split('.')[0]}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} in {metrics}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
