"""Run one cell of the benchmark on the chip and print its result.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root; everything that belongs to it is found from there by name (see
``harness.py``).  The run makes its weights and inputs from ``--seed``,
warms up what the window uses, measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics with ``--trace 1``.  The numbers that
decide ``correct`` are printed with their limits as the last lines of
standard error and under ``checks`` at the end of that object.

It exits with 2 and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for; it never falls back to the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"run.py: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    found = (f"{len(devices)} x {devices[0].device_kind} "
             f"({devices[0].platform})")
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {found}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips; JAX "
              f"found {found}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    print(f"device: {found}", flush=True)

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    from chipbench import harness
    ctx = harness.Context(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), devices, T_START)
    return emit(ctx, run_driver(ctx))


def run_driver(ctx) -> dict:
    from chipbench import harness
    driver = harness.load_module(
        harness.HERE / "drivers" / f"{ctx.traffic['kind']}.py")
    return driver.run(ctx)


def emit(ctx, rec: dict) -> int:
    from chipbench import harness
    out = harness.result(ctx, rec)
    c = ctx.clock.snapshot()
    print(f"compile: {c['compiles']} compiles ({c['compile_s']:.2f} s), "
          f"{c['cache_hits']} persistent-cache reads "
          f"({c['retrieve_s']:.2f} s) in the whole run", flush=True)
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
