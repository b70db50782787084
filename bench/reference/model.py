"""Plain float32 forward pieces of the decoders the benchmark serves:
RMSNorm, RoPE, grouped-query attention (full or in a sliding window),
SwiGLU, and :class:`Decoder`, the layer loop an architecture's
``Reference`` (``arch/<architecture>.py``) fills in, over the weights the
configuration's policy stores.

It imports nothing of the program and takes nothing the program made.
Weights come from the benchmark's seeded generator (``bench.weights``,
with its exact quantization ties removed) and are quantized here, straight from the definition the configuration
states: each output channel ``c`` of a matmul weight with QBN ``b <= 8``
lies on the grid ``{-L..L} * amax_c / L`` with ``L = 2^(b-1) - 1``, where
``amax_c`` is the channel's largest magnitude over the whole stored
tensor (all layers of the stack); channels with ``b > 8`` keep their bf16
values.  Activations entering the q/k/v and gate/up projections are
quantized per token at the policy's activation QBN (the largest
magnitude of the row sets the scale).  Every matmul runs in float32 at
``Precision.HIGHEST``.  The layers run one at a time, each made from its
keys when it is needed, and attention runs in blocks of queries, so the
whole forward fits beside nothing else on one chip.  Sequences may be
packed several to a row, each attending causally within itself.

Departures from the published models, the same as the configuration
files state: rotary embedding over the whole head (phi4-mini: 96 of 128
dims), no longrope rescaling, an untied unembed (phi4-mini), RMSNorm
gain 1.  The KV cache is not quantized here: int8 KV is the program's
storage choice, and its error is part of what the limit allows.

``mode="fp8"`` is the control: the same forward with both operands of
every weight matmul rounded to float8 e4m3 (per-tensor scale for
weights, per-token for activations), accumulating in float32 -- the
precision step below the bf16 the program computes in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
FP8_MAX = 448.0


def quantize_channels(w, bits, amax):
    """f32 weight on the per-channel grids the policy states."""
    wf = w.astype(jnp.float32)
    b = jnp.asarray(bits, jnp.float32)
    levels = jnp.maximum(2.0 ** (b - 1.0) - 1.0, 1.0)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -levels, levels) * scale
    return jnp.where(b > 8, wf, q)


def quantize_tokens(x, bits):
    """Per-token (last axis) symmetric fake quantization."""
    levels = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    return jnp.clip(jnp.round(x / scale), -levels, levels) * scale


def _fp8(x, axis):
    """``x`` rounded to float8 e4m3 under a scale that maps its largest
    magnitude (over ``axis``) to 448.  The fp8 bits pass through a
    bitcast, so no compiler may keep the float32 values instead."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    f8 = jax.lax.bitcast_convert_type(
        (x / s).astype(jnp.float8_e4m3fn), jnp.uint8)
    return jax.lax.bitcast_convert_type(
        f8, jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x, w, mode):
    if mode == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, None)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, theta, pos):
    """x: (n, L, H, D) at positions ``pos`` (n, L); rotate-half pairing."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, seg, window=None):
    """Causal GQA within each segment of a row (``seg`` (n, L): the
    sequence each slot belongs to, sequences contiguous), q head h
    reading kv head h // (Hq / Hkv), in blocks of queries.  With a
    ``window``, a key ``window`` or more positions behind the query is
    masked.  q: (n, L, Hq, D); k, v: (n, L, Hkv, D)."""
    n, L, Hq, D = q.shape
    G = Hq // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for s in range(0, L, Q_BLOCK):
        qb = q[:, s:s + Q_BLOCK]
        sc = jnp.einsum("nqhd,nkhd->nhqk", qb, k, precision=HI) / np.sqrt(D)
        qi = s + jnp.arange(qb.shape[1])[:, None]
        ok = (jnp.arange(L)[None, :] <= qi)[None] & (
            seg[:, s:s + Q_BLOCK, None] == seg[:, None, :])
        if window is not None:
            ok &= (jnp.arange(L)[None, :] > qi - window)[None]
        sc = jnp.where(ok[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=1)


def self_attention(x, pos, seg, w, c, mode, window=None):
    """``x`` plus the attention sublayer of weights ``w`` (``wq``, ``wk``,
    ``wv``, ``wo``) under the layer items ``c`` (:func:`layer_items`)."""
    n, L, d = x.shape
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    h = quantize_tokens(rmsnorm(x, c["rms_norm_eps"]), c["act_bits"])
    q = matmul(h, w["wq"], mode).reshape(n, L, hq, hd)
    k = matmul(h, w["wk"], mode).reshape(n, L, hkv, hd)
    v = matmul(h, w["wv"], mode).reshape(n, L, hkv, hd)
    q, k = rope(q, c["rope_theta"], pos), rope(k, c["rope_theta"], pos)
    o = attention(q, k, v, seg, window).reshape(n, L, hq * hd)
    return x + matmul(o, w["wo"], mode)


def swiglu(x, w, c, mode):
    """``x`` plus the SwiGLU sublayer of weights ``w`` (``wg``, ``wu``,
    ``wd``)."""
    h = quantize_tokens(rmsnorm(x, c["rms_norm_eps"]), c["act_bits"])
    g = matmul(h, w["wg"], mode)
    u = matmul(h, w["wu"], mode)
    return x + matmul(jax.nn.silu(g) * u, w["wd"], mode)


@functools.partial(jax.jit, static_argnames=("items", "mode", "window"))
def dense_layer(x, pos, seg, w, items, mode, window=None):
    """One dense decoder layer: attention, then SwiGLU."""
    c = dict(items)
    return swiglu(self_attention(x, pos, seg, w, c, mode, window), w, c,
                  mode)


def layer_items(cfg):
    """The sizes a layer function needs, hashable (a jit static)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys) + (
        ("act_bits", float(cfg["policy"]["act_bits"])),)


class Decoder:
    """The reference of one configuration and seed, less what each
    layer is: an architecture's ``Reference`` subclasses it with
    ``layer_weights(l)`` and ``layer(l, x, pos, seg, w, mode)`` for layer
    ``l``.  ``shapes`` ({role: (K, N)}), ``role_ids`` and ``bits`` (per-
    group QBNs, the unembed's too) are the architecture's; ``stack``
    {role: layers} says over how many layers each role's channel maxima
    are taken (its stack in the stored tree)."""

    def __init__(self, cfg: dict, seed: int, shapes: dict, role_ids: dict,
                 bits: dict, stack: dict):
        self.cfg = cfg
        self.key = weights.base_key(seed)
        self.dims = weights.dims(cfg)
        self.shapes = shapes
        self.role_ids = role_ids
        self.bits = {r: jnp.asarray(weights.channel_bits(bits[r], n))
                     for r, (_, n) in list(shapes.items()) +
                     [("unembed", (0, self.dims["vocab_padded"]))]}
        self.amax = {role: stack_amax(self.key, role_ids[role], stack[role],
                                      shape)
                     for role, shape in shapes.items()}

    def quantized(self, r: int, roles) -> dict:
        """Layer ``r`` of each of ``roles``' stacks, on its grid."""
        return _layer_weights(self.key, r, self.amax, self.bits, tuple(
            (role, self.role_ids[role], self.shapes[role]) for role in roles))

    def hidden(self, tokens: np.ndarray, mode: str = "f32"):
        """Final-normed hidden states (n, L, d) of a padded token batch,
        one sequence per row from position 0."""
        n, L = tokens.shape
        pos = np.broadcast_to(np.arange(L, dtype=np.int32), (n, L))
        seg = np.zeros((n, L), np.int32)
        return self.hidden_rows([(tokens, pos, seg)], mode)[0]

    def hidden_rows(self, rows, mode: str = "f32"):
        """:meth:`hidden` of several batches of packed rows, each given as
        ``(tokens, positions, segments)`` (n, L): a row may hold several
        sequences, each with its own positions and segment id.  Each
        layer's weights are made once for all batches."""
        d, vp = self.dims["d"], self.dims["vocab_padded"]
        xs = [_embed(self.key, jnp.asarray(t), d, vp) for t, _, _ in rows]
        aux = [(jnp.asarray(p), jnp.asarray(g)) for _, p, g in rows]
        for r in range(self.dims["layers"]):
            w = self.layer_weights(r)
            xs = [self.layer(r, x, p, g, w, mode)
                  for x, (p, g) in zip(xs, aux)]
        return [rmsnorm(x, self.cfg["rms_norm_eps"]) for x in xs]

    def logits(self, h, mode: str = "f32"):
        """(rows, vocab) logits of final-normed hidden rows (rows, d)."""
        return _logits(self.key, h, self.bits["unembed"], self.dims["d"],
                       self.dims["vocab_padded"], self.dims["vocab"], mode)


def stack_amax(key, role_id: int, layers: int, shape):
    """Channel maxima of one role's weights over its ``layers``."""
    a = _channel_amax(key, 0, role_id, tuple(shape))
    for r in range(1, layers):
        a = jnp.maximum(a, _channel_amax(key, r, role_id, tuple(shape)))
    return a


@functools.partial(jax.jit, static_argnames=("role_id", "shape"))
def _channel_amax(key, r, role_id, shape):
    return weights.channel_amax(weights.layer_weight(key, role_id, r, shape))


@functools.partial(jax.jit, static_argnames=("d", "vp"))
def _embed(key, tokens, d, vp):
    return jnp.take(weights.embed(key, vp, d), tokens,
                    axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shapes",))
def _layer_weights(key, r, amax, bits, shapes):
    return {role: quantize_channels(weights.untie(
        weights.layer_weight(key, rid, r, shape), bits[role], amax[role]),
        bits[role], amax[role]) for role, rid, shape in shapes}


@functools.partial(jax.jit, static_argnames=("d", "vp", "vocab", "mode"))
def _logits(key, h, bits, d, vp, vocab, mode):
    u = weights.unembed(key, d, vp)
    amax = weights.channel_amax(u)
    w = quantize_channels(weights.untie(u, bits, amax), bits, amax)
    return matmul(h, w, mode)[:, :vocab]
