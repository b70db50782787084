"""The dense architecture behind ``bench/arch/dense.py`` computes what the
harness computed before the architecture had a module of its own: the
packed tree, the reference logits and the work counts, pinned in
``fixtures/dense_pin.json``; and the shared work counts of a window."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import flops, serving, weights
from bench.tests.conftest import ROOT, fixture_json

PIN = fixture_json("dense_pin.json")


def test_packed_tree_is_unchanged(smoke_cfg, dense):
    from repro.models import LM
    from repro.quant.apply import apply_policy_packed
    model = LM(dense.lm_config(smoke_cfg))
    policy, graph = serving.program_policy(dense, model, smoke_cfg)
    build = jax.jit(lambda k: apply_policy_packed(
        dense.program_tree(k, smoke_cfg), graph, policy))
    leaves, tdef = jax.tree.flatten(build(weights.base_key(PIN["seed"])))
    h = hashlib.sha256(str(tdef).encode())
    for x in leaves:
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PIN["tree_sha256"]


def test_reference_logits_are_unchanged(smoke_cfg, dense):
    pin = PIN["logits"]
    ref = dense.Reference(smoke_cfg, pin["seed"])
    toks = np.random.default_rng(pin["tokens_rng"]).integers(
        0, 256, pin["shape"], np.int32)
    h = ref.hidden(toks)
    for i, want in enumerate(pin["values"]):
        got = np.asarray(ref.logits(h[i]))[pin["positions"]]
        assert np.array_equal(got, np.asarray(want, np.float32))


def test_work_counts_are_unchanged(dense):
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "phi4-mini-3.8b.json").read_text())
    pin = PIN["step_work"]
    for steps, want in zip(pin["steps"], pin["work"]):
        got = flops.step_work(dense, cfg, [tuple(e) for e in steps],
                              pin["weight_bytes"])
        assert got == want


@pytest.mark.parametrize("window", [None, 1, 5, 16, 40])
@pytest.mark.parametrize("start,n", [(0, 1), (0, 30), (3, 7), (20, 1),
                                     (15, 12), (60, 9)])
def test_attention_pairs_and_keys_count_each_key_once(start, n, window):
    """The closed forms equal a count over each token's keys: position
    ``p`` reads the keys at ``p`` and before, and with a window none
    ``window`` or more positions behind."""
    w = window or 10 ** 9
    keys = [set(range(max(0, p - w + 1), p + 1))
            for p in range(start, start + n)]
    assert flops.attn_pairs(start, n, window) == sum(map(len, keys))
    assert flops.attn_keys(start, n, window) == len(set().union(*keys))


def test_an_architecture_may_state_the_parameters_a_token_reads(dense):
    """Where not every weight is read per token (sparse experts), the
    module's ``active_params`` replaces the count over its roles."""
    import types
    cfg = fixture_json("smoke-dense.json")
    sparse = types.SimpleNamespace(
        **{k: getattr(dense, k) for k in ("layer_shapes", "role_layers",
                                          "layer_windows")},
        active_params=lambda c: 1000)
    step = [(0, 8, True), (40, 1, True)]
    full = flops.step_work(dense, cfg, step, 0.0)
    part = flops.step_work(sparse, cfg, step, 0.0)
    assert part["model_flops"] - part["attn_flops"] == \
        2.0 * 1000 * 9 + 2.0 * 64 * 256 * 2
    assert flops.active_params(dense, cfg) == 2 * sum(
        k * n for k, n in dense.layer_shapes(cfg).values())
    assert part["attn_flops"] == full["attn_flops"]
