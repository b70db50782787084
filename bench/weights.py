"""Weights and quantization policy from the seed, on the benchmark's side.

Every weight is drawn from its own key, ``fold_in(fold_in(seed key,
role id), layer)``, so the plain reference can make any one layer again
without the rest and without anything the program made.  The embedding
and the unembed have the ids 1 and 2; each architecture numbers its own
roles after them (``role_ids``).  The program's tree is built by
stacking those layers, in bf16, on the device, in one jitted call that
also packs it with the program's ``apply_policy_packed``; the compile
cache serves that call from the second run on.  What the tree holds is
the architecture's (``arch/<architecture>.py`` ``program_tree``); this
module keeps what every architecture shares.

The kernel-wise policy is drawn from the configuration's fixed
``policy_seed``: it sets the packed shapes and so the compiled programs
and the bytes each step reads, which must not change with ``--seed``.
"""
from __future__ import annotations

import numpy as np

EMBED_ID, UNEMBED_ID = 1, 2


def role_ids(roles) -> dict:
    """Key ids of an architecture's matmul roles, after the embedding's
    and the unembed's, in the order given."""
    return {r: 3 + i for i, r in enumerate(roles)}


def dims(cfg: dict) -> dict:
    """The sizes every decoder configuration states."""
    return {"d": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "vocab": cfg["vocab_size"],
            "vocab_padded": -(-cfg["vocab_size"] // 128) * 128,
            "layers": cfg["num_hidden_layers"]}


def base_key(seed: int):
    import jax
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, fan_in):
    """bf16 normals of std ``1/sqrt(fan_in)``.

    The rounding to bf16 is made explicit (``reduce_precision``): inside
    a jitted program XLA may otherwise keep the float32 values where a
    bf16 result feeds float32 arithmetic (its excess-precision rule, which
    the TPU compiler applies), so ``channel_amax``, ``untie`` and the
    reference's quantization would see other values than the weights
    stored."""
    import jax
    import jax.numpy as jnp
    w = jax.random.normal(key, shape, jnp.float32) * np.float32(
        1.0 / np.sqrt(fan_in))
    return jax.lax.reduce_precision(w, exponent_bits=8,
                                    mantissa_bits=7).astype(jnp.bfloat16)


def channel_amax(w):
    """Largest magnitude of each output channel (last axis) of ``w`` over
    every other axis, as float32."""
    import jax.numpy as jnp
    return jnp.max(jnp.abs(w.astype(jnp.float32)),
                   axis=tuple(range(w.ndim - 1)))


def untie(w, bits, amax):
    """Move each bf16 weight that lies exactly half-way between two levels
    of its channel's grid one ulp toward zero.

    A channel with QBN ``b <= 8`` has ``L = max(2^(b-1) - 1, 1)`` levels of
    ``amax / L``; ``w * L / amax`` exactly ``k + 1/2`` is a tie, and which
    way it rounds depends on how a compiler forms ``w / (amax / L)`` (the
    program's jitted and eager packings differ there).  bf16 weights hit
    such ties often (``amax / 2`` always is one), so the generator removes
    them and every correct packing stores the same codes.  The test is
    exact in float32: ``2 |w| L`` and ``(2k + 1) amax`` carry at most 16
    significant bits.  ``amax`` is never a tie, so it does not move."""
    import jax
    import jax.numpy as jnp
    b = jnp.asarray(bits, jnp.float32)
    lv = jnp.where(b <= 8, jnp.maximum(2.0 ** (b - 1.0) - 1.0, 1.0), 0.0)
    a = jnp.abs(w.astype(jnp.float32))
    t = 2.0 * a * lv
    k = jnp.floor(a * lv / jnp.where(amax > 0, amax, 1.0))
    tie = (lv > 0) & ((t == (2 * k + 1) * amax) | (t == (2 * k - 1) * amax)
                      | (t == (2 * k + 3) * amax))
    down = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(w, jnp.uint16) - jnp.uint16(1),
        jnp.bfloat16)
    return jnp.where(tie, down, w)


def layer_weight(key, role_id: int, layer, shape):
    """One layer's bf16 weight of the role with key id ``role_id``
    (``layer`` may be traced)."""
    import jax
    k = jax.random.fold_in(jax.random.fold_in(key, role_id), layer)
    return _normal(k, shape, shape[0])


def stack(key, role_id: int, layers: int, shape, group_bits):
    """``layers`` weights of one role stacked (layer, K, N), ties removed
    under its per-group QBNs and the stack's channel maxima."""
    import jax
    import jax.numpy as jnp
    w = jax.vmap(lambda r: layer_weight(key, role_id, r, shape))(
        jnp.arange(layers))
    return untie(w, channel_bits(group_bits, shape[1]), channel_amax(w))


def embed(key, vocab_padded: int, d: int):
    """(vocab_padded, d) bf16 token embedding."""
    import jax
    return _normal(jax.random.fold_in(key, EMBED_ID),
                   (vocab_padded, d), d)


def unembed(key, d: int, vocab_padded: int):
    """(d, vocab_padded) bf16 output projection."""
    import jax
    return _normal(jax.random.fold_in(key, UNEMBED_ID),
                   (d, vocab_padded), d)


def tree(key, cfg: dict, blocks: tuple, unembed_bits) -> dict:
    """The program's parameter tree (``LM.init`` layout) around an
    architecture's ``blocks``, one stacked block per pattern position:
    final norm 0 (gain 1 under the program's ``(1 + w)`` RMSNorm), the
    unembed with ties removed under its QBNs, the embedding."""
    import jax.numpy as jnp
    m = dims(cfg)
    u = unembed(key, m["d"], m["vocab_padded"])
    return {"blocks": blocks,
            "final_norm": jnp.zeros((m["d"],), jnp.bfloat16),
            "unembed": untie(u, channel_bits(unembed_bits, u.shape[1]),
                             channel_amax(u)),
            "embed": embed(key, m["vocab_padded"], m["d"])}


def policy_bits(cfg: dict, layer_shapes: dict) -> dict:
    """Per-group weight QBNs of each matmul role of ``layer_shapes``
    ({role: (K, N)}, in the architecture's order) and of the unembed,
    drawn in that order from the configuration's ``policy_seed``; groups
    are ``ceil(N / n_groups)`` contiguous output channels."""
    pol = cfg["policy"]
    rng = np.random.default_rng(pol["policy_seed"])
    shapes = dict(layer_shapes)
    shapes["unembed"] = (dims(cfg)["d"], dims(cfg)["vocab_padded"])
    return {role: rng.choice(np.asarray(pol["weight_bits"], np.float64),
                             min(pol["max_groups"], n))
            for role, (_, n) in shapes.items()}


def channel_bits(group_bits: np.ndarray, n: int) -> np.ndarray:
    """Per-group QBNs -> per-channel QBNs of ``n`` channels."""
    reps = -(-n // len(group_bits))
    return np.repeat(np.asarray(group_bits, np.float32), reps)[:n]
