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


@pytest.fixture
def smoke_cfg():
    return fixture_json("smoke-dense.json")
