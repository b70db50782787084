"""Dense decoder: RMSNorm, RoPE, grouped-query attention, SwiGLU, every
layer alike -- the architecture of phi4-mini-3.8b and internlm2-20b as
published.

An architecture module is everything the harness does that depends on
the model's layers.  A configuration names it (``"architecture":
"dense"``) and ``bench.spec`` loads ``arch/<architecture>.py`` by path,
so a new architecture is a new file.  Each module provides:

* ``lm_config(cfg)``: the program's ``LMConfig``;
* ``site_role(site)``: the role of each site of the program's
  quantizable graph (``unembed`` included), the keys of ``policy_bits``;
* ``layer_shapes(cfg)``, ``policy_bits(cfg)``, ``program_tree(key,
  cfg)``: its roles' (K, N), their per-group QBNs, and the program's
  parameter tree from the seed's key (``bench.weights``);
* ``role_layers(cfg)``: how many layers hold each role (its stack);
* ``layer_windows(cfg)``: each layer's attention window, ``None`` for
  full -- with the two above, what ``bench.flops`` counts the work from;
  a module whose tokens pass through only some of its weights (sparse
  experts) also gives ``active_params(cfg)``;
* ``Reference(cfg, seed)``: the plain float32 forward, with ``hidden``,
  ``hidden_rows`` and ``logits`` (``bench.reference.model.Decoder``).
"""
from __future__ import annotations

from bench import weights
from bench.reference import model

ROLES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
ROLE_IDS = weights.role_ids(ROLES)


def lm_config(cfg: dict):
    from repro.configs.base import dense
    from repro.models.api import LMConfig
    return LMConfig(
        name=cfg["name"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        n_layers=cfg["num_hidden_layers"], pattern=(dense(),),
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"])


def site_role(site: str) -> str:
    """``p0.wq`` -> ``wq``: one block per period, so the site's weight
    names its role."""
    return site.split(".")[-1]


def layer_shapes(cfg: dict) -> dict:
    """(K, N) of each matmul weight of one layer: inputs on K."""
    m = weights.dims(cfg)
    d, q, kv = m["d"], m["hq"] * m["hd"], m["hkv"] * m["hd"]
    ff = cfg["intermediate_size"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "wg": (d, ff), "wu": (d, ff), "wd": (ff, d)}


def role_layers(cfg: dict) -> dict:
    return dict.fromkeys(ROLES, weights.dims(cfg)["layers"])


def layer_windows(cfg: dict) -> list:
    return [None] * weights.dims(cfg)["layers"]


def policy_bits(cfg: dict) -> dict:
    return weights.policy_bits(cfg, layer_shapes(cfg))


def program_tree(key, cfg: dict) -> dict:
    """The program's parameter tree in bf16, one ``attn`` block per
    pattern period stacked over every layer, ties removed under the
    policy; norm weights 0, i.e. gain 1 under the program's ``(1 + w)``
    RMSNorm."""
    import jax.numpy as jnp
    m = weights.dims(cfg)
    R = m["layers"]
    bits = policy_bits(cfg)
    block = {"norm": jnp.zeros((R, m["d"]), jnp.bfloat16),
             "ffn_norm": jnp.zeros((R, m["d"]), jnp.bfloat16)}
    for role, shape in layer_shapes(cfg).items():
        block[role] = weights.stack(key, ROLE_IDS[role], R, shape,
                                    bits[role])
    return weights.tree(key, cfg, (block,), bits["unembed"])


class Reference(model.Decoder):
    """The dense reference of one configuration and seed: every layer
    attention then SwiGLU, each role's channel maxima over all layers."""

    def __init__(self, cfg: dict, seed: int):
        super().__init__(cfg, seed, layer_shapes(cfg), ROLE_IDS,
                         policy_bits(cfg), role_layers(cfg))
        self.items = model.layer_items(cfg)

    def layer_weights(self, r: int) -> dict:
        return self.quantized(r, ROLES)

    def layer(self, r, x, pos, seg, w, mode):
        return model.dense_layer(x, pos, seg, w, self.items, mode)
