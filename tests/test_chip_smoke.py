"""chip_smoke.py at CPU size: its serve-and-check path on the smoke config
(Pallas interpreted), and its refusal to run without a TPU backend."""
import importlib.util
import os
import pathlib
import subprocess
import sys

from repro.configs import get

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_path_serves_and_checks_at_cpu_size():
    """The same run_smoke the chip runs, at smoke widths and lengths: every
    request gets its n_new tokens, all packed buckets are hit, and the
    Pallas logits agree with the ref path within the script's tolerance."""
    out = _chip_smoke().run_smoke(
        get("gemma2-2b").smoke, seed=1, n_requests=3, prompt_lens=(8, 14),
        new_tokens=(2, 3), max_len=24, page_size=4, max_slots=2,
        parity_chunks=2)
    assert out["tokens_served"] >= 3 * 2
    assert out["weight_hbm_bytes"]["packed"] > 0
    assert not out["tpu_custom_call"]                # interpreted on CPU


def test_refuses_a_cpu_backend():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    assert r.stdout == ""                            # no result line
