"""The restart cell with the program's own spans read out.

    python chipbench/tools/restart_spans.py --seed <n> [--seconds 50] \
        [--out chiprun_out/restart_spans.json]

Runs ``mamba2-370m.train-restart`` through its own driver as
``run.py --trace 1`` would, and prints its result line.  Besides, for
every restart (the set-up one, each of the window, the traced one) it
takes the table of the program's spans and counters
(``repro.core.profiler.SPANS``: name, parent, count, total and self
seconds) and the programs XLA compiled anew, prints one summary line each
and writes the tables to ``--out``.  In the traced restart the device's
idle gaps are named by the innermost program span (``repro.*``) or
benchmark span (``bench.*``) around them.

The summary holds what the program's spans give per restart:
``restore_params_s`` (``train.restore``), ``restore_opt_wait_s``
(``train.opt_wait``), ``warmup_s`` (the ``train.warmup.*`` spans) and
``compiles_uncached`` (``compile.*`` less ``compile_cached.*`` counts).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

WORKLOAD = "mamba2-370m.train-restart"

from chipbench import harness  # noqa: E402
from chipbench import trace as tr  # noqa: E402


class SpanClock(harness.CompileClock):
    """The driver's compile clock that also takes the program's span table
    of each restart: the driver snapshots its clock as a restart starts
    and reads it back as the restart's timed part ends."""

    def __init__(self):
        super().__init__()
        self.restarts: list = []
        self._open = None

    def snapshot(self) -> dict:
        from repro.core.profiler import SPANS
        self._open = (SPANS.snapshot(), time.perf_counter())
        return super().snapshot()

    def since(self, snap: dict) -> dict:
        from repro.core.profiler import SPANS
        spans, t0 = self._open
        self.restarts.append({"seconds": time.perf_counter() - t0,
                              "spans": SPANS.since(spans)})
        return super().since(snap)


def summary(rows: dict) -> dict:
    from repro.core.profiler import uncached_compiles

    def total(name):
        return rows[name].total_s if name in rows else 0.0

    uncached = uncached_compiles(rows)
    return {"restore_params_s": total("train.restore"),
            "restore_opt_wait_s": total("train.opt_wait"),
            "warmup_s": sum(r.total_s for n, r in rows.items()
                            if n.startswith("train.warmup.")),
            "compiles_uncached": sum(uncached.values()),
            "uncached": dict(sorted(uncached.items(),
                                    key=lambda kv: (-kv[1], kv[0])))}


def table(rows: dict) -> list:
    """[name, parent, count, total_s, self_s], longest total first."""
    return [[n, r.parent, r.count, r.total_s, r.self_s]
            for n, r in sorted(rows.items(), key=lambda kv: -kv[1].total_s)]


def name_program_spans(set_attr=setattr) -> None:
    """Let the trace reduction keep the program's spans beside the
    benchmark's, so that idle gaps take the innermost of either."""
    import jax
    bench = tr.SPAN_PREFIX
    set_attr(tr, "SPAN_PREFIX", (bench, "repro."))
    set_attr(tr, "span", lambda name: jax.profiler.TraceAnnotation(
        bench + name))


def run(ctx) -> dict:
    """Drive the cell with ``ctx`` (its clock replaced); the result line
    and one entry per restart."""
    from chipbench.run import run_driver
    clock = ctx.clock = SpanClock()
    out = harness.result(ctx, run_driver(ctx))
    labels = (["setup"] + [f"window {i + 1}" for i in
                           range(out["attempted"])] + ["traced"] * ctx.trace)
    restarts = [dict(label=lab, seconds=r["seconds"], **summary(r["spans"]),
                     spans=table(r["spans"]))
                for lab, r in zip(labels, clock.restarts)]
    return {"result": out, "restarts": restarts}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "restart_spans.json"))
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"restart_spans: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    name_program_spans()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    ctx = harness.Context(bench, WORKLOAD, args.seed, args.seconds, True,
                          devices[:1], time.perf_counter())
    got = run(ctx)
    for r in got["restarts"]:
        print("spans {label}: {seconds:.3f} s; restore_params_s "
              "{restore_params_s:.3f}, restore_opt_wait_s "
              "{restore_opt_wait_s:.3f}, warmup_s {warmup_s:.3f}, "
              "compiles_uncached {compiles_uncached}: {uncached}".format(**r),
              flush=True)
    window = [r for r in got["restarts"] if r["label"].startswith("window")]
    if window:
        print("window means: " + json.dumps({
            k: statistics.fmean(r[k] for r in window)
            for k in ("seconds", "restore_params_s", "restore_opt_wait_s",
                      "warmup_s", "compiles_uncached")}), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(dict(got, seed=args.seed)))
    print(json.dumps(got["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
