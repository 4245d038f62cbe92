"""Readings of a training cell's control and planted faults at the cell's
own size on the chip, through the cell's own driver and comparison:

    python chipbench/tools/control_train.py --workload mamba2-370m.train-steady \
        --fault control --seeds 1,2,3 [--seconds 1]

For each seed it plants the fault (``faults.py``: ``control``,
``state_unchanged``, ``half_batch``) under the timed path, runs the cell
as ``run.py`` would with a short window, and prints the result line, whose
``correct`` has to read false, with the numbers compared and their limits.
The driver's ``readings:`` line before it holds every number the
comparison can take.  All seeds run in one process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"control_train: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    from chipbench import faults, harness
    from chipbench.run import run_driver
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(bench, args.workload, seed, args.seconds,
                              False, devices[:1], time.perf_counter())
        with faults.planted(args.fault, ctx):
            out = harness.result(ctx, run_driver(ctx))
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
