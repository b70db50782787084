"""Benchmark tests: ``pytest bench/tests`` from the repository root, on
the CPU at smoke size."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def fixture_json(name):
    return json.loads((FIXTURES / name).read_text())


# each test configuration with the directory of the architecture it
# names: the benchmark's own, where ``spec.cell`` finds it, or the
# fixtures for a test architecture that reaches the harness as new files
ARCH_DIRS = {"smoke-dense.json": ROOT / "bench" / "arch",
             "smoke-window-mixed.json": FIXTURES}


def arch_of(config):
    """The architecture module test configuration ``config`` names."""
    from bench import spec
    return spec.architecture(fixture_json(config)["architecture"],
                             ARCH_DIRS[config])


@pytest.fixture
def smoke_cfg():
    return fixture_json("smoke-dense.json")


@pytest.fixture
def dense():
    return arch_of("smoke-dense.json")
