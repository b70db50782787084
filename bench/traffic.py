"""The one traffic generator: a mix file's parameters plus a seed.

Every seed gets the same multiset of sizes and gaps in another order, so
runs with different seeds do the same work.  Lengths are the quantiles
of a clipped lognormal at the midpoints of ``block`` equal-probability
bins; each block of ``block`` requests holds every quantile once, in an
order drawn from the seed (prompt and output lengths are shuffled
independently).  Poisson gaps are exponential quantiles at ``rate_rps``,
shuffled per block the same way.  A mix with ``"fixed_order": true``
draws that order once for every seed, so a window that sees only part
of a block sees the same part whatever the seed; the seed then draws
the token ids alone.  Prompt token ids are uniform over the
vocabulary.  The program receives only the generated requests.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lognormal_sizes(dist: dict, block: int) -> np.ndarray:
    """``block`` lengths: clipped lognormal quantiles, ascending."""
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(block)])
    v = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(v, dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _blocked(values: np.ndarray, rng):
    """Endless stream of ``values``, each block of them in a new order."""
    while True:
        yield from rng.permutation(values)


def requests(traffic: dict, seed: int, vocab: int):
    """The seed's endless stream of requests: (prompt, n_new)."""
    block = traffic["block"]
    order = 0 if traffic.get("fixed_order") else seed
    plens = _blocked(lognormal_sizes(traffic["prompt"], block),
                     _rng(order, 1))
    olens = _blocked(lognormal_sizes(traffic["output"], block),
                     _rng(order, 2))
    tok = _rng(seed, 3)
    for p, o in zip(plens, olens):
        yield tok.integers(0, vocab, int(p), dtype=np.int32), int(o)


def arrival_offsets(traffic: dict, seed: int, seconds: float):
    """Seconds from the window's start at which an open loop's requests
    are due, up to ``seconds``: cumulative shuffled exponential
    quantiles."""
    block = traffic["block"]
    gaps = np.array([-math.log(1.0 - p) for p in _quantiles(block)])
    t, out = 0.0, []
    for g in _blocked(gaps / traffic["rate_rps"], _rng(seed, 4)):
        t += float(g)
        if t > seconds:
            return out
        out.append(t)
