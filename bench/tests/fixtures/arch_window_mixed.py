"""Test architecture: the dense block with sliding-window and full
attention alternating, ``(local_attn, attn)`` in each period, as
Mellum2-12B-A2.5B and Trinity-Mini mix them.  It stands for an
architecture a later change brings as new files: nothing in the harness
names it, and it reaches the harness only through the interface
``bench/arch/dense.py`` sets out.  Each pattern position has weights of
its own, so its roles are the program's graph sites (``p0.wq``, ...)."""
from __future__ import annotations

from bench import weights
from bench.reference import model

PATTERN = ("local_attn", "attn")
SITES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
ROLES = tuple(f"p{p}.{s}" for p in range(len(PATTERN)) for s in SITES)
ROLE_IDS = weights.role_ids(ROLES)


def _window(cfg: dict, p: int):
    return cfg["sliding_window"] if PATTERN[p] == "local_attn" else None


def _repeats(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // len(PATTERN)


def lm_config(cfg: dict):
    from repro.configs.base import dense
    from repro.models.api import LMConfig
    return LMConfig(
        name=cfg["name"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        n_layers=cfg["num_hidden_layers"],
        pattern=tuple(dense(kind) for kind in PATTERN),
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        window=cfg["sliding_window"], norm_eps=cfg["rms_norm_eps"])


def site_role(site: str) -> str:
    return site


def layer_shapes(cfg: dict) -> dict:
    m = weights.dims(cfg)
    d, q, kv = m["d"], m["hq"] * m["hd"], m["hkv"] * m["hd"]
    ff = cfg["intermediate_size"]
    one = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
           "wg": (d, ff), "wu": (d, ff), "wd": (ff, d)}
    return {f"p{p}.{s}": one[s] for p in range(len(PATTERN)) for s in SITES}


def role_layers(cfg: dict) -> dict:
    return dict.fromkeys(ROLES, _repeats(cfg))


def layer_windows(cfg: dict) -> list:
    return [_window(cfg, l % len(PATTERN))
            for l in range(cfg["num_hidden_layers"])]


def policy_bits(cfg: dict) -> dict:
    return weights.policy_bits(cfg, layer_shapes(cfg))


def program_tree(key, cfg: dict) -> dict:
    import jax.numpy as jnp
    R, d = _repeats(cfg), cfg["hidden_size"]
    bits, shapes = policy_bits(cfg), layer_shapes(cfg)
    blocks = []
    for p in range(len(PATTERN)):
        block = {"norm": jnp.zeros((R, d), jnp.bfloat16),
                 "ffn_norm": jnp.zeros((R, d), jnp.bfloat16)}
        for s in SITES:
            role = f"p{p}.{s}"
            block[s] = weights.stack(key, ROLE_IDS[role], R, shapes[role],
                                     bits[role])
        blocks.append(block)
    return weights.tree(key, cfg, tuple(blocks), bits["unembed"])


class Reference(model.Decoder):
    """Layer ``l`` is pattern position ``l % 2`` of repeat ``l // 2``; each
    role's channel maxima are over its own position's stack."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed, layer_shapes(cfg), ROLE_IDS,
                         policy_bits(cfg), role_layers(cfg))
        self.items = model.layer_items(cfg)

    def layer_weights(self, l: int) -> dict:
        p = l % len(PATTERN)
        w = self.quantized(l // len(PATTERN), [f"p{p}.{s}" for s in SITES])
        return {role.split(".")[1]: v for role, v in w.items()}

    def layer(self, l, x, pos, seg, w, mode):
        return model.dense_layer(x, pos, seg, w, self.items, mode,
                                 _window(self.cfg, l % len(PATTERN)))
