"""A whole run of the harness at smoke size on the CPU, past its look for
a chip (the Pallas kernels run in interpret mode)."""
from bench.tests.conftest import arch_of, fixture_json


def smoke_cell(traffic="smoke-poisson.json", config="smoke-dense.json"):
    cfg = fixture_json(config)
    return {"workload": {"name": "smoke", "chips": 1},
            "config": cfg, "arch": arch_of(config),
            "traffic": fixture_json(traffic),
            "limits": fixture_json("smoke.limits.json"),
            "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"},
                           {"name": "ttft_p95_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def smoke_run(seed, seconds=3.0, traffic="smoke-poisson.json",
              control=False, config="smoke-dense.json"):
    import run
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return run.run_cell(smoke_cell(traffic, config), seed, seconds, False,
                        device, control=control)
