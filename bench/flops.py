"""Operations and bytes of the served work, by shape.

A step's work is read from the scheduler as entries ``(start, n,
samples)``: ``n`` tokens of one request at positions ``start ..
start+n-1``, and whether the step samples a token from its last one.
The architecture module (``arch/<architecture>.py``) describes its
layers: ``layer_shapes`` ((K, N) of each matmul role), ``role_layers``
(how many layers hold each role) and ``layer_windows`` (each layer's
attention window, ``None`` for full).  From those, what a step needs,
at least:

* matmul FLOPs: twice the weight parameters a token passes through, per
  token (:func:`active_params`: each role's ``K * N`` times the layers
  that hold it, or the module's own ``active_params(cfg)`` where not
  every weight is read, as with sparse experts);
* attention FLOPs: QK and PV, ``4 * Hq * head_dim`` per (token, key)
  pair and layer, each layer's token reading the keys :func:`attn_pairs`
  counts under that layer's window;
* unembed FLOPs: ``2 * d * vocab`` per sampled row only;
* HBM bytes: every stored matmul weight once (``weight_bytes``), the
  embedding rows of its tokens, the KV each request's attention reads
  in each layer (:func:`attn_keys`; int8 K and V, f32 scales, int32
  positions), and the KV of its new tokens written once in every layer.
"""
from __future__ import annotations

import collections

from bench import weights


def attn_pairs(start: int, n: int, window=None) -> float:
    """(token, key) pairs of ``n`` tokens from position ``start``, causal:
    the token at position ``p`` reads ``p + 1`` keys, or ``window`` at
    most."""
    if window is None:
        return n * start + n * (n + 1) / 2
    a, b = start + 1, start + n            # p + 1 over the tokens
    c = min(b, window - 1)
    below = (a + c) * (c - a + 1) / 2 if c >= a else 0
    return below + window * max(0, b - max(a, window) + 1)


def attn_keys(start: int, n: int, window=None) -> int:
    """Key positions that ``n`` tokens from ``start`` read together."""
    return start + n if window is None else min(start + n, window - 1 + n)


def kv_bytes_per_layer(cfg: dict) -> int:
    """Paged KV bytes of one position in one layer: int8 K and V, their
    f32 scales per head, an int32 position."""
    m = weights.dims(cfg)
    return 2 * m["hkv"] * m["hd"] + 2 * m["hkv"] * 4 + 4


def active_params(arch, cfg: dict) -> int:
    """Matmul weight parameters one token passes through, all layers."""
    if hasattr(arch, "active_params"):
        return arch.active_params(cfg)
    layers = arch.role_layers(cfg)
    return sum(k * n * layers[r] for r, (k, n) in
               arch.layer_shapes(cfg).items())


def attn_work(arch, cfg: dict, entries) -> tuple:
    """Attention FLOPs and KV bytes read of ``entries``, over every
    layer under its window."""
    m = weights.dims(cfg)
    windows = collections.Counter(arch.layer_windows(cfg)).items()
    pairs = keys = 0
    for s, n, _ in entries:
        pairs += sum(c * attn_pairs(s, n, w) for w, c in windows)
        keys += sum(c * attn_keys(s, n, w) for w, c in windows)
    return (4.0 * m["hq"] * m["hd"] * pairs,
            float(kv_bytes_per_layer(cfg) * keys))


def step_work(arch, cfg: dict, entries, weight_bytes: float) -> dict:
    m = weights.dims(cfg)
    tokens = sum(n for _, n, _ in entries)
    rows = sum(1 for _, _, s in entries if s)
    attn_f, attn_b = attn_work(arch, cfg, entries)
    kv_write = (len(arch.layer_windows(cfg)) * kv_bytes_per_layer(cfg)
                * tokens)
    return {
        "tokens": tokens, "rows": rows,
        "model_flops": (2.0 * active_params(arch, cfg) * tokens
                        + attn_f + 2.0 * m["d"] * m["vocab"] * rows),
        "attn_flops": attn_f, "attn_bytes": attn_b,
        "hbm_bytes": weight_bytes + 2 * m["d"] * tokens + attn_b + kv_write,
    }
