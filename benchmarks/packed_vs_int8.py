"""Weight-store benchmark: int8 vs bit-packed sub-byte serving path.

Compares, for an AutoQ-style mixed-QBN policy (~4-bit average, the regime
the paper's searches land in):

* weight-side HBM bytes of the int8 store (kernels/quant_matmul.py path)
  vs the bucketed packed store (kernels/pack.py + quant_pack_sub8);
* wall-clock of the two matmul paths -- interpret mode on CPU (numerics
  validation), compiled on TPU (the real roofline comparison, where the
  packed path's smaller weight reads are the win the reward model prices).

Usage:  PYTHONPATH=src python benchmarks/packed_vs_int8.py [--m 256]
        [--k 2048] [--n 2048] [--iters 5]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.quant import quant_pack_int8, quant_pack_sub8

# a 4-bit-average kernel-wise mixture (most channels 2-4 bits, a tail at
# 6/8 -- the shape AutoQ's searched policies take on CNNs/LMs)
POLICY_MIX = [2, 3, 4, 4, 4, 4, 6, 8, 2, 3]


def _mixed_bits(n: int) -> np.ndarray:
    reps = int(np.ceil(n / len(POLICY_MIX)))
    return np.asarray((POLICY_MIX * reps)[:n], np.float32)


def _time(fn, iters: int) -> float:
    fn()                                     # compile / warm caches
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    M, K, N = args.m, args.k, args.n

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    bits = _mixed_bits(N)
    avg_bits = float(bits.mean())

    qi, si, _ = quant_pack_int8(w, bits, axis=1)
    pw = quant_pack_sub8(w, bits)

    int8_bytes = qi.size * qi.dtype.itemsize + si.size * si.dtype.itemsize
    packed_bytes = pw.hbm_bytes()
    print(f"shape ({M}, {K}) @ ({K}, {N}), avg QBN {avg_bits:.2f}")
    print(f"weight HBM bytes  int8 store   : {int8_bytes:>12,}")
    print(f"weight HBM bytes  packed store : {packed_bytes:>12,}"
          f"   ({100.0 * packed_bytes / int8_bytes:.1f}% of int8)")
    for name, nbytes in pw.bucket_nbytes().items():
        print(f"    bucket {name:<6}: {nbytes:>12,} B")

    mode = ("compiled (TPU)" if jax.default_backend() == "tpu"
            else "interpret (CPU)")
    t_i8 = _time(lambda: ops.quant_matmul(x, qi, si.reshape(-1)), args.iters)
    t_pk = _time(lambda: ops.packed_mixed_matmul(x, pw), args.iters)
    print(f"wall-clock [{mode}]  int8 matmul  : {t_i8 * 1e3:8.2f} ms")
    print(f"wall-clock [{mode}]  packed matmul: {t_pk * 1e3:8.2f} ms")

    y_i8 = ops.quant_matmul(x, qi, si.reshape(-1), use_pallas=False)
    y_pk = ops.packed_mixed_matmul(x, pw, use_pallas=False)
    # both stores quantize b<=8 channels on the same grid -> same result
    err = float(jnp.max(jnp.abs(y_i8 - y_pk)))
    print(f"max |int8 - packed| over outputs: {err:.2e}")
    if K >= 64:
        assert packed_bytes <= 0.60 * int8_bytes, \
            (packed_bytes, int8_bytes, "packed store must be <= 60% of int8")
    else:
        # per-channel f32 scales (4 B, paid by both stores) only amortize
        # once K is large; the <=60% guarantee is about the weight bytes
        print(f"note: K={K} too small for the <=60% bytes check "
              "(scale overhead dominates)")


if __name__ == "__main__":
    main()
