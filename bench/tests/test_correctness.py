"""The comparison that decides ``correct``: the plain reference agrees
with the program's own float32 forward, a sound run passes, the fp8
control fails, and each fault a serving cell can have fails."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correctness, serving, weights
from bench.tests.conftest import ARCH_DIRS, ROOT, arch_of, fixture_json
from bench.tests.smoke import smoke_run

sys.path.insert(0, str(ROOT / "bench"))

LIMIT = fixture_json("smoke.limits.json")["served_gap"]
# the dense architecture, and a test one that mixes window and full
# attention and reaches the harness only as new files
CONFIGS = list(ARCH_DIRS)


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_matches_the_programs_f32_forward(config):
    """Same weights, same QBNs, both in float32 at highest precision: the
    program's ``LM.apply`` and the reference differ by rounding alone."""
    from repro.kernels.pack import PackedWeight
    from repro.models import LM
    from repro.quant.apply import apply_policy_packed
    smoke_cfg = fixture_json(config)
    arch = arch_of(config)
    model = LM(arch.lm_config(smoke_cfg))
    policy, graph = serving.program_policy(arch, model, smoke_cfg)
    params = apply_policy_packed(
        arch.program_tree(weights.base_key(7), smoke_cfg), graph, policy)
    is_pw = lambda x: isinstance(x, PackedWeight)
    params = jax.tree.map(
        lambda x: PackedWeight(x.parts, x.k, x.n, x.buckets, "float32")
        if is_pw(x) else x.astype(jnp.float32), params, is_leaf=is_pw)
    act = model.block_act_bits(graph, [8.0] * len(graph.layers))
    toks = np.random.default_rng(0).integers(0, 256, (2, 40), np.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = model.apply(params, {"tokens": jnp.asarray(toks)},
                            act_bits=act)
    ref = arch.Reference(smoke_cfg, 7)
    h = ref.hidden(toks)
    rl = np.stack([np.asarray(ref.logits(h[i])) for i in range(2)])
    assert np.abs(np.asarray(lg)[..., :256] - rl).max() < 1e-3


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    out = smoke_run(2 ** 35 + 3, config=config)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("seed", [1, 5])
def test_fp8_control_fails_the_limit(seed):
    """The fp8 control in the program's place comes out not correct
    through the harness's own comparison; the program on the same
    sample passes."""
    out = smoke_run(seed, control=True)
    assert not out["correct"], out["checks"]
    assert out["program_gap"] <= LIMIT < out["checks"]["served_gap"][
        "value"], out
    assert list(out)[-1] == "checks"


def _state_unchanged(monkeypatch):
    from repro.models.transformer import LM
    orig = LM.model_step

    def step(self, params, tokens, positions, slot_map, cache, *a, **k):
        logits, _ = orig(self, params, tokens, positions, slot_map, cache,
                         *a, **k)
        return logits, cache
    monkeypatch.setattr(LM, "model_step", step)


def _rows_mixed(monkeypatch):
    """Each lane of the batch gets its neighbour's logits."""
    from repro.models.transformer import LM
    orig = LM.model_step

    def step(self, *a, **k):
        logits, cache = orig(self, *a, **k)
        return jnp.roll(logits, 1, axis=0), cache
    monkeypatch.setattr(LM, "model_step", step)


def _token_altered(monkeypatch):
    orig = serving.build_engine

    def build(arch, cfg, traffic, seed):
        eng = orig(arch, cfg, traffic, seed)
        sample = eng._sample_span
        eng._sample_span = lambda *a: (
            lambda t, k: ((t + 1) % cfg["vocab_size"], k))(*sample(*a))
        return eng
    monkeypatch.setattr(serving, "build_engine", build)


@pytest.mark.parametrize("fault", [_state_unchanged, _rows_mixed,
                                   _token_altered])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = smoke_run(2 ** 35 + 3)
    assert not out["correct"], out["checks"]


def test_packed_rows_serve_each_token_once_and_change_nothing(smoke_cfg,
                                                             dense):
    """Sequences packed into fixed rows, longest first, each attending
    within itself only: every served token is read once, and each one's
    reference gap is what the sequence alone gives."""
    rng = np.random.default_rng(3)
    sample = [{"prompt": rng.integers(0, 256, p).astype(np.int32),
               "prompt_len": p, "n_new": o,
               "tokens": [int(t) for t in rng.integers(0, 256, o)]}
              for p, o in [(30, 5), (60, 4), (12, 9), (40, 3)]]
    rows = correctness.served_rows(sample, 128)
    # sequences of 63, 42, 34, 20 tokens in rows of 128: 63 + 42 + 20, 34
    assert [len(set(r[0][2][0]) - {-1}) for r in rows] == [3, 1]
    for (tok, pos, seg), slots, toks in rows:
        # every served token but a sequence's last is fed right after the
        # slot whose logits chose it
        nxt = slots + 1 < tok.shape[1]
        same = seg[0, np.minimum(slots + 1, tok.shape[1] - 1)] == \
            seg[0, slots]
        fed = nxt & same
        assert (tok[0, slots[fed] + 1] == toks[fed]).all()
    assert sorted(int(t) for r in rows for t in r[2]) == sorted(
        t for r in sample for t in r["tokens"])
    ref = dense.Reference(smoke_cfg, 7)
    h_packed, t_packed = correctness._served_hidden(ref, rows)
    alone = [correctness.served_rows([sample[i]], 128) for i in (1, 3, 2, 0)]
    h_alone = np.concatenate([np.asarray(correctness._served_hidden(
        ref, a)[0]) for a in alone])
    assert np.abs(np.asarray(h_packed) - h_alone).max() < 1e-4
