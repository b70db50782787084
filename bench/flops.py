"""Operations and bytes of the served work, by shape.

A step's work is read from the scheduler as entries ``(start, n,
samples)``: ``n`` tokens of one request at positions ``start ..
start+n-1``, and whether the step samples a token from its last one.
What a step needs, at least:

* matmul FLOPs: ``2 * (weight parameters of a layer) * layers`` per token;
* attention FLOPs: QK and PV, ``4 * Hq * head_dim`` per (token, key)
  pair and layer, causal (the token at position ``p`` reads ``p + 1``
  keys);
* unembed FLOPs: ``2 * d * vocab`` per sampled row only;
* HBM bytes: every stored matmul weight once (``weight_bytes``), the
  embedding rows of its tokens, each request's whole KV context once
  per layer (int8 K and V, f32 scales, int32 positions), and the KV of
  its new tokens written once.
"""
from __future__ import annotations

from bench import weights


def matmul_params(cfg: dict) -> int:
    return sum(k * n for k, n in weights.layer_shapes(cfg).values())


def kv_bytes_per_position(cfg: dict) -> int:
    m = weights.dims(cfg)
    return m["layers"] * (2 * m["hkv"] * m["hd"] + 2 * m["hkv"] * 4 + 4)


def attn_flops(cfg: dict, start: int, n: int) -> float:
    m = weights.dims(cfg)
    pairs = n * start + n * (n + 1) / 2
    return 4.0 * m["hq"] * m["hd"] * m["layers"] * pairs


def attn_bytes(cfg: dict, start: int, n: int) -> float:
    """KV read by one request's attention in one step: its context."""
    return float(kv_bytes_per_position(cfg) * (start + n))


def step_work(cfg: dict, entries, weight_bytes: float) -> dict:
    m = weights.dims(cfg)
    tokens = sum(n for _, n, _ in entries)
    rows = sum(1 for _, _, s in entries if s)
    attn_f = sum(attn_flops(cfg, s, n) for s, n, _ in entries)
    attn_b = sum(attn_bytes(cfg, s, n) for s, n, _ in entries)
    kv_write = kv_bytes_per_position(cfg) * tokens
    return {
        "tokens": tokens, "rows": rows,
        "model_flops": (2.0 * matmul_params(cfg) * m["layers"] * tokens
                        + attn_f + 2.0 * m["d"] * m["vocab"] * rows),
        "attn_flops": attn_f, "attn_bytes": attn_b,
        "hbm_bytes": weight_bytes + 2 * m["d"] * tokens + attn_b + kv_write,
    }
