"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Fast mode by default (seconds per
bench); the full-scale reproduction runs live in benchmarks/repro_autoq.py
(--full) and are summarized into EXPERIMENTS.md.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.time()
    for _ in range(n):
        fn()
    return (time.time() - t0) / n * 1e6      # us


def _substrate():
    from benchmarks.repro_autoq import train_substrate
    return train_substrate(steps=80)


def bench_table2_quant(model, params, val, full_acc):
    """Table 2: one kernel-wise quantization search episode."""
    from repro.core import (HierarchicalAgent, QuantEnv, RewardCfg,
                            make_cnn_evaluator)
    graph = model.graph()
    ev = make_cnn_evaluator(model, params, graph, val)
    env = QuantEnv(graph, params, ev, RewardCfg.accuracy_guaranteed())
    agent = HierarchicalAgent(env, seed=0, updates_per_episode=4)
    agent.run_episode(noise=0.5)             # compile warmup
    us = _time(lambda: agent.run_episode(noise=0.3), n=3, warmup=0)
    log, _ = agent.run_episode(noise=0.1)
    return us, f"ep_acc={log.acc:.1f}%_avg_wbits={log.avg_wbits:.2f}"


def bench_table3_binarize(model, params, val, full_acc):
    """Table 3: one kernel-wise binarization search episode."""
    from repro.core import (HierarchicalAgent, QuantEnv, RewardCfg,
                            make_cnn_evaluator)
    from repro.quant.policy import QuantMode
    graph = model.graph()
    ev = make_cnn_evaluator(model, params, graph, val,
                            mode=QuantMode.BINARIZE)
    env = QuantEnv(graph, params, ev, RewardCfg.accuracy_guaranteed(),
                   mode=QuantMode.BINARIZE)
    agent = HierarchicalAgent(env, seed=0, updates_per_episode=4)
    agent.run_episode(noise=0.5)
    us = _time(lambda: agent.run_episode(noise=0.3), n=3, warmup=0)
    log, _ = agent.run_episode(noise=0.1)
    return us, f"ep_acc={log.acc:.1f}%_avg_bbn={log.avg_wbits:.2f}"


def bench_table4_compare(model, params, val, full_acc):
    """Table 4: evaluator throughput (the search bottleneck) + stored
    cost-at-iso-accuracy if the full run exists."""
    from repro.core import make_cnn_evaluator
    from repro.quant.policy import QuantPolicy
    graph = model.graph()
    ev = make_cnn_evaluator(model, params, graph, val)
    p = QuantPolicy.uniform(graph, 5.0)
    us = _time(lambda: ev(p), n=10)
    f = pathlib.Path("results/repro/table4_compare.json")
    if f.exists():
        d = json.loads(f.read_text())
        derived = (f"autoq_logic={d['autoq_channel']['norm_logic']:.4f}_"
                   f"haq_logic={d['haq_like_layer']['norm_logic']:.4f}")
    else:
        derived = f"uniform5_acc={ev(p):.1f}%"
    return us, derived


def bench_fig8_convergence(model, params, val, full_acc):
    """Fig 8: hierarchical-vs-flat episode cost at channel granularity."""
    from repro.core import (FlatAgent, HierarchicalAgent, QuantEnv, RewardCfg,
                            make_cnn_evaluator)
    graph = model.graph()
    ev = make_cnn_evaluator(model, params, graph, val)
    env = QuantEnv(graph, params, ev, RewardCfg.accuracy_guaranteed())
    hier = HierarchicalAgent(env, seed=0, updates_per_episode=2)
    flat = FlatAgent(env, seed=0, granularity="channel",
                     updates_per_episode=2)
    hier.run_episode(noise=0.5)
    flat.run_episode(noise=0.5)
    us_h = _time(lambda: hier.run_episode(noise=0.3), n=2, warmup=0)
    us_f = _time(lambda: flat.run_episode(noise=0.3), n=2, warmup=0)
    return us_h, f"flat_episode_us={us_f:.0f}"


def bench_kernel_quant_matmul(*_):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 1024)), jnp.float32)
    qw = jnp.asarray(rng.integers(-127, 128, size=(1024, 1024)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, size=(1024,)), jnp.float32)
    jitted = jax.jit(lambda a, b, c: ref.quant_matmul_ref(a, b, c))
    jitted(x, qw, s).block_until_ready()
    us = _time(lambda: jitted(x, qw, s).block_until_ready(), n=10)
    y = ops.quant_matmul(x[:128, :128], qw[:128, :128], s[:128])
    yr = ref.quant_matmul_ref(x[:128, :128], qw[:128, :128], s[:128])
    err = float(jnp.max(jnp.abs(y - yr)))
    return us, f"pallas_interpret_maxerr={err:.1e}"


def bench_kernel_binary_matmul(*_):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    B = jnp.asarray(rng.choice([-1, 1], size=(4, 512, 512)), jnp.int8)
    a = jnp.asarray(rng.uniform(0.1, 1.0, size=(4, 512)), jnp.float32)
    jitted = jax.jit(lambda p, q, r: ref.binary_matmul_ref(p, q, r))
    jitted(x, B, a).block_until_ready()
    us = _time(lambda: jitted(x, B, a).block_until_ready(), n=10)
    y = ops.binary_matmul(x[:128, :128], B[:, :128, :128], a[:, :128])
    err = float(jnp.max(jnp.abs(
        y - ref.binary_matmul_ref(x[:128, :128], B[:, :128, :128],
                                  a[:, :128]))))
    return us, f"pallas_interpret_maxerr={err:.1e}"


def bench_kernel_fake_quant(*_):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2048, 1024)), jnp.float32)
    bits = jnp.asarray(rng.integers(1, 9, size=(1024,)), jnp.float32)
    lv = jnp.maximum(2.0 ** (bits - 1) - 1, 1.0)
    amax = jnp.max(jnp.abs(x), axis=0)
    sc = jnp.where(amax > 0, amax / lv, 1.0)
    jitted = jax.jit(lambda a, b, c, d: ref.fake_quant_ref(a, b, c, d))
    jitted(x, sc, lv, bits).block_until_ready()
    us = _time(lambda: jitted(x, sc, lv, bits).block_until_ready(), n=10)
    y = ops.fake_quant_channels(x[:256, :128], sc[:128], lv[:128], bits[:128])
    err = float(jnp.max(jnp.abs(
        y - ref.fake_quant_ref(x[:256, :128], sc[:128], lv[:128],
                               bits[:128]))))
    return us, f"pallas_interpret_maxerr={err:.1e}"


def bench_fig9_roofline_serving(model, params, val, full_acc):
    """Figs 9-12 analog: TPU-roofline FPS/energy of quantized vs binarized
    policies (replaces the paper's FPGA measurements; DESIGN.md section 3)."""
    from repro.core.roofline import TPURoofline
    from repro.quant.policy import QuantMode, QuantPolicy
    graph = model.graph()
    rl = TPURoofline()
    t0 = time.time()
    rows = {}
    for name, bits in (("Q8", 8), ("Q4", 4), ("B4", 4), ("F", 16)):
        mode = QuantMode.BINARIZE if name.startswith("B") else QuantMode.QUANT
        p = QuantPolicy.uniform(graph, float(bits), mode=mode)
        rows[name] = (rl.throughput_fps(graph, p), rl.energy(graph, p))
    us = (time.time() - t0) / len(rows) * 1e6
    derived = "_".join(f"{k}:fps={v[0]:.2e}:J={v[1]:.2e}"
                       for k, v in rows.items())
    return us, derived


def bench_dryrun_roofline(*_):
    """Roofline section: summarize results/roofline.json."""
    f = pathlib.Path("results/roofline.json")
    if not f.exists():
        return 0.0, "run_launch.roofline_first"
    rows = json.loads(f.read_text())
    t0 = time.time()
    doms = {}
    for c in rows:
        doms[c["dominant"]] = doms.get(c["dominant"], 0) + 1
    us = (time.time() - t0) * 1e6
    derived = "_".join(f"{k}:{v}" for k, v in sorted(doms.items())) + \
        f"_cells={len(rows)}"
    return us, derived


BENCHES = [
    ("table2_quant_episode", bench_table2_quant, True),
    ("table3_binarize_episode", bench_table3_binarize, True),
    ("table4_compare_eval", bench_table4_compare, True),
    ("fig8_hier_vs_flat_episode", bench_fig8_convergence, True),
    ("fig9_roofline_serving", bench_fig9_roofline_serving, True),
    ("kernel_quant_matmul", bench_kernel_quant_matmul, False),
    ("kernel_binary_matmul", bench_kernel_binary_matmul, False),
    ("kernel_fake_quant", bench_kernel_fake_quant, False),
    ("dryrun_roofline_summary", bench_dryrun_roofline, False),
]


def main() -> int:
    """Print one CSV row per bench; a bench that raises still gets its
    ``nan,ERROR`` row, and the run then exits non-zero."""
    print("name,us_per_call,derived")
    ctx = None
    failed = []
    for name, fn, needs_sub in BENCHES:
        if needs_sub and ctx is None:
            ctx = _substrate()
        try:
            us, derived = fn(*(ctx if needs_sub else ()))
            print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:                      # pragma: no cover
            print(f"{name},nan,ERROR:{e!r}", flush=True)
            failed.append(name)
    if failed:
        print(f"failed benches: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
