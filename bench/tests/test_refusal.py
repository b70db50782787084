"""A run refuses a non-TPU backend, naming the device, and a checkout
without the program; neither prints a result line."""
import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4-mini.prefill-long",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_backend_is_refused_and_named():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cpu" in p.stderr and "not a TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    import pytest
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    with pytest.raises(KeyError):
        run.peaks("TPU v99")
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_compile_while_serving_refuses_the_run(monkeypatch):
    """With the warm-up left out, the window compiles: the run is refused
    with the counts beside their limit 0."""
    import pytest
    sys.path.insert(0, str(ROOT / "bench"))
    import run
    from bench import serving
    from bench.tests.smoke import smoke_run
    monkeypatch.setattr(serving, "warm_up", lambda *a: None)
    with pytest.raises(run.WindowCompiled) as e:
        smoke_run(2 ** 34 + 1, seconds=1.0)
    assert e.value.checks["compiles_in_window"]["value"] > 0
    assert e.value.checks["compiles_in_window"]["limit"] == 0
