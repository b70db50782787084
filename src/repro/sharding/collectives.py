"""Custom collectives: int8-compressed gradient all-reduce over the pod axis.

Cross-pod links (DCN) are the scarcest bandwidth in a multi-pod job; the
paper's own linear quantizer compresses the pod-level gradient exchange:
each pod quantizes its local gradient int8 (absmax scale per last-axis row),
all-gathers the (q, scale) pairs over "pod" (1 byte + amortized scale instead
of 2), and dequantize-sums locally.  Exact for pod=2 up to int8 rounding;
4x fewer DCN bytes than an fp32 ring all-reduce, 2x fewer than bf16.

Used by steps.make_train_step(compress_pod=True): the loss/grad is computed
under shard_map manual over "pod" (auto over data/model), so each pod holds
its local-batch gradient and this function performs the only cross-pod
communication in the step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _q8(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def compressed_allreduce(tree, axis_name: str = "pod"):
    """Mean over `axis_name` via int8 all-gather + local dequant-sum.

    Call inside shard_map (manual over axis_name).  Scalars and tiny leaves
    (< 1KiB) go through a plain psum -- compression overhead isn't worth it.
    """
    n = jax.lax.axis_size(axis_name)

    def one(g):
        if g.ndim == 0 or g.size < 256:
            return jax.lax.pmean(g, axis_name)
        gf = g.astype(jnp.float32)
        q, s = _q8(gf)
        qg = jax.lax.all_gather(q, axis_name)        # (n, ...)
        sg = jax.lax.all_gather(s, axis_name)
        total = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
        return (total / n).astype(g.dtype)

    return jax.tree.map(one, tree)
