"""The fp8 control in the program's place, on many seeds in one process.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed this is a whole run of the cell (``run.run_cell``) whose
comparison judges, instead of the program's served tokens, the tokens
the fp8 control puts first at the same positions, against the same
limits: its result line must read ``correct`` false.  The line also
carries ``program_gap`` and ``program_mean_gap``, the program's own
widest and mean gap over the same sample, so one call reads both ends of
each limit.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, enable_compile_cache, log, print_checks, \
    require_chips, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.cell(spec.load_benchmark(ROOT), args.workload, ROOT)
    device = require_chips(cell["workload"]["chips"])
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, seed, args.seconds, False, device,
                       control=True)
        log(f"seed {seed}: control correct {out['correct']}")
        print_checks(out["checks"])
        print(json.dumps({"workload": args.workload, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
