"""Integration test of the launch path: jit(step) with production-style
shardings lowers AND compiles on a small multi-device mesh (subprocess with
8 placeholder host devices; the real 256/512-chip runs live in results/).
Covers steps.py + specs.py + sharding/specs.py + the HLO analyzer end-to-end.
"""
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get
from repro.models import LM, shape_by_name
from repro.models.api import ShapeCfg
from repro.optim import AdamW
from repro.launch.steps import (hidden_rules, make_decode_step,
                                make_prefill_step, make_train_step,
                                shardings_for)
from repro.launch.specs import step_structs
from repro.launch.hlo import analyze
from repro.sharding.ctx import sharding_rules
from repro.sharding.specs import to_named
import dataclasses as dc

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
spec = get("gemma2-2b")
# shrink the production config so the 8-device compile is fast but the
# sharding logic is exercised on the same code path
cfg = dc.replace(spec.config, n_layers=2, d_model=256, n_heads=8,
                 n_kv_heads=4, d_ff=512, vocab=1024, head_dim=32, window=64)
spec = dc.replace(spec, config=cfg)
model = LM(cfg)
opt = AdamW(state_bits=8)

for shape, mode, mk in [
    (ShapeCfg("train_4k", 128, 16, "train"), "train",
     lambda: make_train_step(model, opt)),
    (ShapeCfg("decode_32k", 128, 16, "decode"), "decode",
     lambda: make_decode_step(model)),
]:
    structs = step_structs(spec, shape, opt, cfg_override=cfg)
    in_s, out_s = shardings_for(structs, mode, cfg, shape, mesh)
    with mesh, sharding_rules(mesh, hidden_rules(mesh)):
        compiled = jax.jit(mk(), in_shardings=to_named(in_s, mesh),
                           out_shardings=to_named(out_s, mesh)
                           ).lower(*structs).compile()
    stats = analyze(compiled.as_text(), default_group=8)
    assert stats.flops > 0, mode
    print("OK", mode, int(stats.flops))
"""


def test_launch_path_lowers_and_compiles():
    # JAX_PLATFORMS=cpu keeps jax's TPU plugin from polling GCP metadata
    # (30 HTTP retries per variable) inside the stripped subprocess env
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert r.stdout.count("OK") == 2
