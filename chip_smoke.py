"""Chip smoke run: serve gemma2-2b at published widths on one TPU.

    python chip_smoke.py [--seed N]

Drives the serving path a user calls -- ``ServeEngine.run`` -> ``serve``
-> ``StepLoop`` -> ``LM.model_step`` -> Pallas paged attention -- once,
with random bf16 weights made from ``--seed``, a kernel-wise policy whose
per-group weight QBNs are drawn from {2, 4, 8, 16} (every packed storage
bucket), 8-bit activations, the packed weight store and int8 paged KV.
It fails (non-zero exit, no result line) unless JAX's backend is TPU, and
unless every check below holds:

* every request returns exactly ``n_new`` in-vocab tokens;
* the compiled ``model_step`` contains ``tpu_custom_call`` -- the Pallas
  kernels were compiled for the chip, not interpreted;
* prompt-chunk logits of the Pallas path match the same engine's
  ``attn_impl="ref"`` jnp path within ``LOGIT_TOL``.  Activations are
  bf16: the two attention backends differ in f32 accumulation order (and
  matmul passes), which flips the bf16 rounding of some attention
  outputs, and that perturbation grows through 26 residual layers (0.7%
  relative RMS at 26 layers of width 256 on CPU).  A mis-indexed head,
  page or mask moves logits by tens of percent.

It prints the device, parameter and peak HBM bytes, compile and serve
wall time as a smoke run -- not a benchmark -- and, as its last line,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

# max |pallas - ref| / max |ref| and ||pallas - ref|| / ||ref|| on logits
LOGIT_TOL = dict(max_rel=2e-1, rms_rel=5e-2)
PACKED_BUCKETS = {"int2", "int4", "int8", "full"}


class SmokeFailure(AssertionError):
    """A smoke check failed: the run must not report a result."""


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def phase(msg):
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def random_policy(graph, seed):
    """Kernel-wise policy: per-group weight QBNs drawn over {2, 4, 8, 16}
    (the int2 / int4 / int8 / bf16 buckets of the packed store), 8-bit
    activations everywhere."""
    from repro.quant.policy import QuantMode, QuantPolicy
    rng = np.random.default_rng(seed)
    return QuantPolicy(
        mode=QuantMode.QUANT,
        weight_bits={l.name: rng.choice([2.0, 4.0, 8.0, 16.0], l.n_groups)
                     for l in graph.layers},
        act_bits={l.name: 8.0 for l in graph.layers})


def build_engine(cfg, seed, max_len):
    """bf16 random weights -> packed kernel-wise store.  The bf16 tree is
    referenced only inside this call, so it is freed once packed."""
    import jax
    import jax.numpy as jnp
    from repro.models import LM
    from repro.serve import ServeEngine
    model = LM(cfg)
    graph = model.graph(seq_len=1, batch=1)
    init = jax.jit(model.init, static_argnames=("dtype",))
    return ServeEngine(
        model, init(jax.random.PRNGKey(seed), dtype=jnp.bfloat16),
        policy=random_policy(graph, seed), graph=graph,
        weight_store="packed", kv_bits=8, attn_impl="pallas",
        max_len=max_len)


def logit_parity(eng, prompts, *, page_size, max_slots, n_chunks):
    """Feed ``n_chunks`` prompt chunks per row through the engine's
    ``model_step`` twice -- Pallas and ref attention, each on its own cache
    -- and compare the logits of every chunk's last token.

    Shapes match ``run()``'s mixed step (``(max_slots, page_size)`` tokens,
    the same pool and block-table widths), so the Pallas variant is the one
    the serving run then uses.  Returns the errors and the compiled Pallas
    step's text."""
    import jax.numpy as jnp
    from repro.serve import paged_kv
    blocks = paged_kv.pages_needed(eng.max_len, page_size)
    num_pages = max_slots * blocks + 1
    tables = np.zeros((max_slots, blocks), np.int32)
    for r in range(max_slots):
        tables[r, :n_chunks] = 1 + r * blocks + np.arange(n_chunks)
    tables = jnp.asarray(tables)
    slot_map = jnp.arange(max_slots, dtype=jnp.int32)
    cols = jnp.full((max_slots,), page_size - 1, jnp.int32)

    def cache():
        return eng.model.init_paged_cache(max_slots, num_pages, page_size,
                                          dtype=eng.cache_dtype,
                                          kv_bits=eng.kv_bits)

    def inputs(c):
        sl = slice(c * page_size, (c + 1) * page_size)
        toks = np.stack([prompts[r % len(prompts)][sl]
                         for r in range(max_slots)])
        pos = np.broadcast_to(np.arange(sl.start, sl.stop, dtype=np.int32),
                              toks.shape)
        return jnp.asarray(toks), jnp.asarray(pos)

    args = lambda c, kv: (eng.params, *inputs(c), slot_map, kv, tables, cols,
                          eng.act_bits)
    t0 = time.perf_counter()
    compiled = eng._model_step.lower(*args(0, cache()),
                                     attn_impl="pallas").compile()
    phase(f"pallas model_step compiled in {time.perf_counter() - t0:.1f} s")
    kv_p, kv_r = cache(), cache()
    max_abs = max_ref = sq_err = sq_ref = 0.0
    for c in range(n_chunks):
        t0 = time.perf_counter()
        lp, kv_p = compiled(*args(c, kv_p))
        lp = np.asarray(lp, np.float32)
        t1 = time.perf_counter()
        lr, kv_r = eng._model_step(*args(c, kv_r), attn_impl="ref")
        lr = np.asarray(lr, np.float32)
        phase(f"chunk {c}: pallas {t1 - t0:.2f} s, "
              f"ref {time.perf_counter() - t1:.2f} s")
        check(np.isfinite(lp).all() and np.isfinite(lr).all(),
              f"non-finite logits at chunk {c}")
        d = lp - lr
        max_abs = max(max_abs, float(np.abs(d).max()))
        max_ref = max(max_ref, float(np.abs(lr).max()))
        sq_err += float((d * d).sum())
        sq_ref += float((lr * lr).sum())
    return {"max_rel": max_abs / max_ref,
            "rms_rel": float(np.sqrt(sq_err / sq_ref)),
            "max_abs": max_abs}, compiled.as_text()


def run_smoke(cfg, *, seed=0, n_requests=8, prompt_lens=(64, 512),
              new_tokens=(16, 32), max_len=1024, page_size=16, max_slots=8,
              parity_chunks=4):
    """Serve ``n_requests`` random requests of ``cfg`` through
    ``ServeEngine.run`` and check them; returns what the smoke run
    reports.  Raises :class:`SmokeFailure` on any failed check."""
    import jax
    from repro.kernels.pack import PackedWeight

    phase("init + pack weights")
    t0 = time.perf_counter()
    eng = build_engine(cfg, seed, max_len)
    jax.block_until_ready(eng.params)
    t_build = time.perf_counter() - t0
    buckets = {name for leaf in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(leaf, PackedWeight) for name, _ in leaf.buckets}
    check(PACKED_BUCKETS <= buckets, f"packed buckets hit: {buckets}")

    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_requests)
    n_new = rng.integers(new_tokens[0], new_tokens[1] + 1, n_requests)
    prompts = [rng.integers(0, cfg.vocab, int(s)).astype(np.int32)
               for s in lens]
    requests = [(p, int(n)) for p, n in zip(prompts, n_new)]
    run = dict(page_size=page_size, max_slots=max_slots)

    # parity first: it times one compiled step chunk by chunk, so a step
    # that is slow on the device shows before the serving run starts
    phase("pallas vs ref logits")
    errs, step_text = logit_parity(eng, prompts, page_size=page_size,
                                   max_slots=max_slots,
                                   n_chunks=parity_chunks)
    for key, tol in LOGIT_TOL.items():
        check(errs[key] <= tol, f"pallas vs ref logits: {key} {errs} > {tol}")

    phase("warm-up run (compiles both step variants)")
    t0 = time.perf_counter()
    eng.run([(prompts[0][:page_size + 1], 2)], **run)
    t_compile = time.perf_counter() - t0
    phase(f"serve {n_requests} requests")
    t0 = time.perf_counter()
    res = eng.run(requests, **run)
    t_serve = time.perf_counter() - t0
    for i, (out, n) in enumerate(zip(res["outputs"], n_new)):
        check(out.shape == (n,), f"request {i}: {out.shape[0]} of {n} tokens")
        check(((out >= 0) & (out < cfg.vocab)).all(),
              f"request {i}: token outside the vocab")
    return {"weight_hbm_bytes": eng.weight_hbm_bytes(),
            "build_s": t_build, "compile_s": t_compile, "serve_s": t_serve,
            "tokens_served": int(sum(len(o) for o in res["outputs"])),
            "steps": res["stats"].steps,
            "prompt_tokens": int(lens.sum()), "logit_errors": errs,
            "tpu_custom_call": "tpu_custom_call" in step_text}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX backend is {jax.default_backend()!r}, "
                 "not 'tpu' -- nothing was served")
    from repro.configs import get
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    dev = jax.devices()[0]
    out = run_smoke(get("gemma2-2b").config, seed=args.seed)
    check(out["tpu_custom_call"],
          "compiled model_step has no tpu_custom_call: attention not compiled")
    stats = dev.memory_stats() or {}
    print("smoke run (not a benchmark): gemma2-2b, published widths, "
          f"random weights, seed {args.seed}")
    print(f"device_kind: {dev.device_kind}  compile cache: {cache_dir}")
    print(f"param HBM bytes: {out['weight_hbm_bytes']}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"build+pack s: {out['build_s']}  warm-up compile s: "
          f"{out['compile_s']}  serve s: {out['serve_s']}")
    print(f"tokens served: {out['tokens_served']} "
          f"(prompt tokens {out['prompt_tokens']}, {out['steps']} steps)")
    print(f"pallas vs ref logits: {out['logit_errors']} tol {LOGIT_TOL}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
