"""Compressed gradient all-reduce: exactness vs psum on a multi-device mesh.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps the single real CPU device.
"""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.sharding.collectives import compressed_allreduce

mesh = jax.make_mesh((8,), ("pod",))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 64, 32)).astype(np.float32))
tiny = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))

def f(g, tiny):
    out = compressed_allreduce({"g": g[0], "t": tiny[0]}, "pod")
    return out["g"], out["t"]

smap = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                     out_specs=(P(), P()), axis_names={"pod"},
                     check_vma=False)
cg, ct = jax.jit(smap)(g, tiny)

exact_g = np.mean(np.asarray(g), axis=0)
exact_t = np.mean(np.asarray(tiny), axis=0)
err = np.abs(np.asarray(cg) - exact_g).max()
scale = np.abs(np.asarray(g)).max(axis=(0, 2), keepdims=True)
# int8 absmax rounding: per-element error <= amax/127/2 per shard, summed
assert err < np.abs(np.asarray(g)).max() / 127.0, err
np.testing.assert_allclose(np.asarray(ct), exact_t, rtol=1e-6, atol=1e-6)
print("OK", err)
"""


def test_compressed_allreduce_subprocess():
    # JAX_PLATFORMS=cpu: without it jax's TPU plugin polls GCP instance
    # metadata (30 HTTP retries per variable) and the subprocess burns the
    # whole timeout before running a single op
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "OK" in r.stdout
