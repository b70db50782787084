"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with random weights from ``--seed``
(packed under the configuration's kernel-wise policy), warms up every
program the window runs (set-up, timed as ``setup_s`` from process
start), then serves the cell's traffic through ``ServeEngine.serve`` for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` traces the same window and reports its per-layer metrics.
After the window the engine is freed and a sample of finished requests
is compared with the plain reference (``bench.correctness``).

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``busy_s``/``window_s`` when
traced), ``breakdown`` (traced), and last ``checks`` -- each compared
number beside its limit, which also close stderr.  Exits non-zero with
no result line unless JAX sees a TPU with as many chips as the cell asks
for.  Progress goes to stderr.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import jax  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def require_chips(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    desc = f"{d.platform} / {d.device_kind} x {len(devs)}"
    if d.platform != "tpu":
        raise NoChip(f"JAX backend is {desc}, not a TPU: nothing measured")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {desc}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table["devices"][kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at one fixed path: the environment's
    ``JAX_COMPILATION_CACHE_DIR`` or ``.bench_cache/jax`` in the checkout;
    every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak(chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class WindowCompiled(RuntimeError):
    """A program compiled, loaded or traced while the window served: the
    warm-up missed a shape, and the window's timings are not the
    system's."""

    def __init__(self, checks: dict):
        super().__init__("programs compiled or traced while serving: "
                         + ", ".join(f"{k} {c['value']}" for k, c in
                                     checks.items()))
        self.checks = checks


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, control: bool = False) -> dict:
    """One run of a cell on ``device``; returns the result object.

    ``control`` puts the fp8 control in the program's place in the
    comparison: the tokens it puts first at the served positions are
    judged against the same limit (``bench/control.py``)."""
    from bench import correctness, e2e, serving, spec, trace_reduce
    cfg, traffic, arch = cell["config"], cell["traffic"], cell["arch"]
    log(f"cell {cell['workload']['name']} seed {seed}: build + pack weights")
    eng = serving.build_engine(arch, cfg, traffic, seed)
    weight_bytes = eng.weight_hbm_bytes()
    log(f"weights stored: {weight_bytes}; warm-up")
    serving.warm_up(eng, cfg, traffic, cfg["vocab_size"])
    trace_dir = CACHE / "trace" if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.monotonic() - T_START
    log(f"set-up done in {setup_s:.1f} s; ramp of "
        f"{traffic.get('ramp_s', 0)} s, window of {seconds} s")
    win = serving.run_window(eng, cfg, traffic, seed, seconds, trace_dir)
    mem = memory_peak(device["count"])
    log(f"window closed: {len(win['reqs'])} requests submitted; "
        f"compiles while serving {win['compiles_in_window']}, "
        f"new traces {win['traced_new']}; peak bytes {mem}")
    checks = {
        "compiles_in_window": {"value": win["compiles_in_window"],
                               "limit": 0},
        "jit_traces_in_window": {"value": win["traces_in_window"],
                                 "limit": 0}}
    if not correctness.judge(checks):
        raise WindowCompiled(checks)
    del eng
    gc.collect()
    t0, t1, reqs = win["t0"], win["t1"], win["reqs"]
    counts = e2e.counts(reqs, t0, t1)
    failed = sum(1 for r in reqs if any(
        not 0 <= t < cfg["vocab_size"] for t in r["tokens"]))
    out = {"device": dict(device, memory_peak_bytes=mem)}
    metrics = {}
    if not trace:
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            else:
                metrics[m["name"]] = {"value": e2e.METRICS[m["name"]](
                    reqs, t0, t1), "unit": m["unit"]}
    else:
        red = trace_reduce.reduce_planes(
            trace_reduce.load(trace_dir).planes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            trace=red, steps=serving.step_entries(win), cfg=cfg, arch=arch,
            peak=peaks(device["kind"]),
            weight_bytes=weight_bytes["packed"] + weight_bytes["int8"],
            reqs=reqs, t0=t0, t1=t1, admitted_s=win["admitted_s"])
        for m in cell["per_layer"]:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {
            "device_ops": trace_reduce.top(red["ops"]),
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"].most_common(10)]}
    log(f"metrics {metrics}; reference check")
    sample = correctness.pick_sample(reqs, t1, traffic["sample"]["requests"],
                                     seed)
    extra, length = {}, correctness.row_len(traffic)
    if not sample:
        gap = mean = None
    elif control:
        res = correctness.control_gap(arch, cfg, seed, sample, length)
        gap, mean = res["control_gap"], res["control_mean_gap"]
        extra = {"program_gap": res["served_gap"],
                 "program_mean_gap": res["mean_gap"]}
    else:
        res = correctness.served_gap(arch, cfg, seed, sample, length)
        gap, mean = res["served_gap"], res["mean_gap"]
    log(f"reference: gap {gap}, mean gap {mean} over {len(sample)} "
        f"requests, {sum(r['n_new'] for r in sample)} served tokens {extra}")
    for name, value in (("served_gap", gap), ("mean_gap", mean)):
        if name in cell["limits"]:
            checks[name] = {"value": value, "limit": cell["limits"][name]}
    extra = {"widest_gap": gap, **extra}
    correct = failed == 0 and correctness.judge(checks)
    out = {"correct": correct, "attempted": counts["attempted"],
           "failed": failed, "metrics": metrics, **out, **extra,
           "checks": checks}
    return out


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    try:
        cell = spec.cell(spec.load_benchmark(ROOT), args.workload, ROOT)
        import repro  # noqa: F401  (the system under test must be here)
        device = require_chips(cell["workload"]["chips"])
    except (spec.SpecError, ImportError, NoChip) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peaks(device["kind"])
    enable_compile_cache()
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       device)
    except WindowCompiled as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        print_checks(e.checks)
        return 3
    print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
