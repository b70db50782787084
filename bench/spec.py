"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's correctness limits is a file of its own,
found by name, so a new cell or metric is new files plus new entries:

* ``configs/<config>.json``  -- via the ``file`` of the config entry;
* ``arch/<architecture>.py`` -- the architecture the configuration names
  under ``architecture``: everything the harness does that depends on
  the model's layers (see ``arch/dense.py``);
* ``traffic/<traffic>.json`` -- the mix the general generator reads;
* ``metrics/<metric>.py``    -- the reader of one per-layer metric; a
  metric named ``<quantity>.<suffix>`` falls back to ``<quantity>.py``
  when it has no file of its own (one reader per quantity, split by cell);
* ``limits/<cell>.json``     -- the limits of the cell's correctness check.
"""
from __future__ import annotations

import functools
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
    if "architecture" not in cfg:
        raise SpecError(f"{c['file']} names no architecture")
    arch = architecture(cfg["architecture"], bench / "arch")
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
    return {"workload": w, "config": cfg, "arch": arch,
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
    return _load(path, "bench_metric").read


def architecture(name: str, arch_dir: pathlib.Path = BENCH_DIR / "arch"):
    """The module of one architecture, ``<arch_dir>/<name>.py``."""
    path = arch_dir / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no architecture {name!r} in {arch_dir}")
    return _load(path, "bench_arch")


@functools.cache
def _load(path: pathlib.Path, prefix: str):
    """The module at ``path``, loaded once per process: the jitted
    functions of an architecture then compile once."""
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
