"""Attention subsystem benchmark: Pallas kernels vs the jnp oracle.

Three measurements, each with a hard numerical-parity gate (the assert is
the point -- CI runs ``--smoke`` so kernel parity is checked on every PR):

* **prefill** -- tiled flash kernel vs the chunked-flash jnp reference on a
  causal (optionally windowed) prompt;
* **paged decode** -- the block-table-walking kernel vs the dense-gather
  path on a ragged page pool (mixed in-flight lengths, idle lanes);
* **engine tok/s** -- ``ServeEngine.run`` over 8 interleaved requests on
  ``attn_impl="pallas"`` vs ``attn_impl="ref"``, token streams compared.

Timing caveat: off-TPU the kernels execute in Pallas *interpret* mode --
correct but emulated, so wall-clock comparisons against the jnp oracle are
meaningless and the "paged decode no slower than the dense gather" check
only arms on a real TPU backend, where the kernel's HBM story (stream pages
into VMEM, skip out-of-window pages, no (B, nb*page_size) gather buffer)
is what the measurement reflects.

Usage:  PYTHONPATH=src python benchmarks/attention.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.models import LM
from repro.models.layers import attention, paged_attention
from repro.models.transformer import POS_SENTINEL
from repro.serve import ServeEngine

TOL = dict(rtol=2e-4, atol=2e-5)


def _timeit(fn, *args, reps=3):
    fn(*args)                                   # compile / warm
    t0 = time.time()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return out, (time.time() - t0) / reps


def bench_prefill(S, Hkv, G, D, window):
    rng = np.random.default_rng(0)
    B = 2
    q = jnp.asarray(rng.normal(size=(B, S, Hkv * G, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def run(impl):      # jit over operands (constants would fold at compile)
        return jax.jit(lambda a, b, c, p: attention(
            a, b, c, q_pos=p, kv_pos=p, window=window, impl=impl))

    ref, t_ref = _timeit(run("ref"), q, k, v, pos)
    got, t_pal = _timeit(run("pallas"), q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
    print(f"prefill  S={S:5d} window={window}: ref {t_ref*1e3:8.2f} ms | "
          f"flash kernel {t_pal*1e3:8.2f} ms | parity OK")
    return t_ref, t_pal


def bench_paged_decode(lens, ps, Hkv, G, D, window):
    rng = np.random.default_rng(1)
    B = len(lens)
    nb = -(-max(lens) // ps) + 1
    P = 1 + sum(-(-s // ps) for s in lens)
    k = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    pos = np.full((P, ps), POS_SENTINEL, np.int32)
    bt = np.zeros((B, nb), np.int32)
    nxt = 1
    for i, s in enumerate(lens):
        n = -(-s // ps)
        bt[i, :n] = range(nxt, nxt + n)
        for p in range(s):
            pos[bt[i, p // ps], p % ps] = p
        nxt += n
    pos, bt = jnp.asarray(pos), jnp.asarray(bt)
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, D)), jnp.float32)
    q_pos = jnp.asarray([[s - 1] for s in lens], jnp.int32)

    def run(impl):
        return jax.jit(lambda a, b, c, p, t, qp: paged_attention(
            a, b, c, p, t, q_pos=qp, window=window, impl=impl))

    ref, t_ref = _timeit(run("ref"), q, k, v, pos, bt, q_pos)
    got, t_pal = _timeit(run("pallas"), q, k, v, pos, bt, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), **TOL)
    print(f"paged decode  B={B} pages<= {nb}: dense gather {t_ref*1e3:8.2f} "
          f"ms | page-walk kernel {t_pal*1e3:8.2f} ms | parity OK")
    return t_ref, t_pal


def bench_engine(n_new, max_len):
    cfg = ARCHS["internlm2-20b"].smoke
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    lens = np.linspace(4, max_len - n_new, 8).astype(int)
    reqs = [(rng.integers(0, cfg.vocab, size=int(s)).astype(np.int32), n_new)
            for s in lens]

    def toks_per_s(impl):
        eng = ServeEngine(model, params, max_len=max_len, attn_impl=impl)
        eng.run(reqs[:1], page_size=4, max_slots=8)          # warm jit
        res = eng.run(reqs, page_size=4, max_slots=8)
        return res["outputs"], res["stats"].decode_tok_per_s

    out_r, tps_r = toks_per_s("ref")
    out_p, tps_p = toks_per_s("pallas")
    for i, (a, b) in enumerate(zip(out_p, out_r)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    print(f"engine  8 interleaved x {n_new} new: ref {tps_r:8.1f} tok/s | "
          f"pallas {tps_p:8.1f} tok/s | streams identical")
    return tps_r, tps_p


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes (parity gate, minimal wall-clock)")
    args = ap.parse_args()
    if args.smoke:
        bench_prefill(64, 2, 2, 16, window=None)
        t_ref, t_pal = bench_paged_decode([37, 9, 22, 5], 8, 2, 2, 16,
                                          window=16)
        tps_r, tps_p = bench_engine(n_new=8, max_len=24)
    else:
        bench_prefill(512, 2, 2, 64, window=None)
        bench_prefill(512, 2, 2, 64, window=128)
        t_ref, t_pal = bench_paged_decode(
            [390, 51, 222, 117, 303, 64, 480, 12], 16, 2, 2, 64, window=128)
        tps_r, tps_p = bench_engine(n_new=32, max_len=128)
    if jax.default_backend() != "tpu":
        print("NOTE: off-TPU run -- kernels in interpret mode; timings are "
              "emulation, only the parity gates are meaningful here.")
    else:
        # acceptance: paged decode must not lose to the dense-gather path
        assert t_pal <= t_ref * 1.05, (t_pal, t_ref)
        assert tps_p >= tps_r * 0.95, (tps_p, tps_r)
        print("TPU perf gate: page-walk decode >= dense-gather path OK")


if __name__ == "__main__":
    main()
