"""Smoke test: the BootSeer train and serve path on a TPU at published widths.

Drives mamba2-370m (all 48 layers, registry widths, random weights from a
fixed seed) through the entry points a user calls, in this one process:

  (a) a cold ``repro.launch.train``: startup, a few steps at batch 4 x 1024
      and one checkpoint save;
  (b) a warm ``repro.launch.train`` in the same workdir, which resumes from
      that checkpoint;
  (c) ``repro.launch.serve``, answering a few requests; the decode-step
      logits of a served sequence must agree with a full forward over it.

``--four-chips`` runs only the sharded path, on four chips: the same seeded
steps on a 1x1 and then on a 2x2 (data x model) mesh, whose losses must
agree and whose parameters must be spread a quarter to each device.

Each phase prints one line; the last line of stdout is one JSON object
naming the device.  Exits non-zero, with no JSON line, when JAX finds no
TPU or any check fails.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "mamba2-370m"
WORKDIR = ROOT / ".chip_smoke"
# bf16 tolerances.  Activations are bf16 through all 48 layers, and on the
# TPU an f32 matmul at default precision runs in bf16 passes, so the
# chunked prefill scan and the one-token recurrence round differently.
LOSS_RTOL = 1e-2          # losses of two mesh layouts
LOGIT_RTOL = 5e-2         # ||full - decode|| / ||full|| over the vocabulary


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_hbm_gb() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def compiles(rows: dict) -> tuple:
    """(compile seconds, persistent-cache hits) of a ``SPANS`` table."""
    return (sum(r.total_s for n, r in rows.items()
                if n.startswith("compile.")),
            sum(r.count for n, r in rows.items()
                if n.startswith("compile_cached.")))


def run_phase(name: str, fn, *args) -> str:
    """Run one phase; print its line (seconds, compile seconds, cache hits,
    peak HBM so far, what it checked)."""
    from repro.core.profiler import SPANS
    snap, t0 = SPANS.snapshot(), time.perf_counter()
    detail = fn(*args)
    compile_s, hits = compiles(SPANS.since(snap))
    print(f"phase {name}: {time.perf_counter() - t0:.1f} s, compile "
          f"{compile_s:.1f} s, {hits} cache hits, peak HBM "
          f"{peak_hbm_gb():.2f} GB; {detail}", flush=True)
    return detail


def size_args(tiny: bool) -> list:
    """Training driver arguments: batch 4 x 1024 at full width; the
    ``tiny`` size rehearses the phases on the CPU."""
    if tiny:
        return ["--arch", ARCH, "--tiny", "--batch", "2", "--seq-len", "32"]
    return ["--arch", ARCH, "--batch", "4", "--seq-len", "1024"]


def finite_losses(summary: dict) -> list:
    losses = [loss for _, loss in summary["losses"]]
    check(bool(losses) and all(math.isfinite(v) for v in losses),
          f"losses not finite: {summary['losses']}")
    return losses


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def train_cold(workdir: Path, tiny: bool) -> str:
    from repro.launch import train
    s = train.main(size_args(tiny) + ["--steps", "3", "--ckpt-every", "3",
                                      "--workdir", str(workdir)])
    check(s["resume_step"] is None, f"cold run resumed from "
          f"{s['resume_step']}")
    losses = finite_losses(s)
    check([v["step"] for v in s["saved"]] == [3],
          f"expected one save at step 3, got {s['saved']}")
    return (f"startup {s['startup_s']['total']:.2f} s, losses "
            f"{', '.join(f'{v:.4f}' for v in losses)}, checkpoint step 3 "
            f"saved in {s['saved'][0]['s']:.1f} s")


def train_warm(workdir: Path, tiny: bool) -> str:
    from repro.launch import train
    s = train.main(size_args(tiny) + ["--steps", "2", "--ckpt-every", "3",
                                      "--workdir", str(workdir)])
    check(s["resume_step"] == 3, f"warm run resumed from "
          f"{s['resume_step']}, not the saved step 3")
    check(s["losses"][0][0] == 3, f"first resumed step is "
          f"{s['losses'][0][0]}, not 3")
    losses = finite_losses(s)
    return (f"startup {s['startup_s']['total']:.2f} s (model_init "
            f"{s['startup_s']['model_init']:.2f} s), resumed at step 3, "
            f"losses {', '.join(f'{v:.4f}' for v in losses)}")


def serve(workdir: Path, tiny: bool) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_tiny
    from repro.launch import serve as serve_driver
    from repro.models.model import Model
    from repro.sharding.rules import single_device_rules

    args = ["--arch", ARCH, "--requests", "6", "--new-tokens", "8",
            "--batch", "4", "--cache-len", "128", "--workdir", str(workdir)]
    s = serve_driver.main(args + (["--tiny"] if tiny else []))
    reqs = s["requests"]
    check(len(reqs) == 6, f"{len(reqs)} of 6 requests answered")
    for r in reqs:
        check(len(r["generated"]) == r["max_new_tokens"],
              f"a request got {len(r['generated'])} of "
              f"{r['max_new_tokens']} tokens")

    # decode-step logits vs a full forward over a served greedy sequence,
    # with the parameters the driver seeded (key 0)
    greedy = next(r for r in reqs if r["temperature"] == 0.0)
    seq = jnp.asarray([greedy["prompt"] + greedy["generated"]], jnp.int32)
    model = Model(get_tiny(ARCH) if tiny else get_config(ARCH),
                  single_device_rules())
    params = model.init(jax.random.key(0))
    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=128))
    full, _ = prefill(params, {"tokens": seq})
    _, cache = prefill(params, {"tokens": seq[:, :-1]})
    dec, _ = jax.jit(model.decode_step)(params, seq[:, -1:], cache,
                                        jnp.int32(seq.shape[1] - 1))
    full = np.asarray(full, np.float32)
    dec = np.asarray(dec, np.float32)
    check(bool(np.isfinite(dec).all()), "decode logits not finite")
    rel = float(np.linalg.norm(full - dec) / np.linalg.norm(full))
    agree = (f"decode vs full forward over {seq.shape[1]} tokens: relative "
             f"error {rel:.4f}, max |diff| {np.abs(full - dec).max():.4f}, "
             f"max |logit| {np.abs(full).max():.4f}, same argmax "
             f"{bool(full.argmax() == dec.argmax())}")
    check(rel <= LOGIT_RTOL, agree)
    tokens = sum(len(r["generated"]) for r in reqs)
    return (f"startup {s['startup_s']['total']:.2f} s, {len(reqs)} requests, "
            f"{tokens} tokens in {s['serve_s']:.1f} s; {agree}")


def one_chip(workdir: Path, tiny: bool = False) -> None:
    run_phase("a (cold train)", train_cold, workdir / "train", tiny)
    run_phase("b (warm train)", train_warm, workdir / "train", tiny)
    run_phase("c (serve)", serve, workdir / "serve", tiny)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def sharded_train(workdir: Path, tiny: bool) -> str:
    from repro.launch import train

    runs = {}
    for mesh in ("1x1", "2x2"):
        runs[mesh] = train.main(
            size_args(tiny) + ["--steps", "3", "--ckpt-every", "0",
                               "--mesh", mesh,
                               "--workdir", str(workdir / mesh)])
    one, four = (finite_losses(runs[m]) for m in ("1x1", "2x2"))
    check(len(one) == len(four) and all(
        abs(a - b) <= LOSS_RTOL * abs(a) for a, b in zip(one, four)),
        f"1x1 losses {one} vs 2x2 losses {four}")

    total = sum(runs["1x1"]["param_bytes_per_device"].values())
    per_dev = runs["2x2"]["param_bytes_per_device"]
    check(len(per_dev) == 4, f"2x2 params on {len(per_dev)} devices")
    shares = {d: b / total for d, b in sorted(per_dev.items())}
    check(all(0.24 <= v <= 0.30 for v in shares.values()),
          f"per-device parameter shares {shares}")
    return (f"losses 1x1 {', '.join(f'{v:.4f}' for v in one)} vs 2x2 "
            f"{', '.join(f'{v:.4f}' for v in four)}; parameter bytes "
            f"{total} on 1x1, per device on 2x2 "
            f"{', '.join(f'{d}: {b} ({shares[d]:.4f})' for d, b in sorted(per_dev.items()))}")


def four_chips(workdir: Path, tiny: bool = False) -> None:
    run_phase("4-chip (1x1 vs 2x2 train)", sharded_train, workdir / "mesh",
              tiny)


# ---------------------------------------------------------------------------

def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x1-vs-2x2 sharded training check")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {device['count']} x "
              f"{device['kind']} ({device['platform']})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        print(f"chip_smoke: needs {need} TPU chips, found "
              f"{device['count']}", file=sys.stderr)
        return 2
    print(f"device: {device['count']} x {device['kind']} "
          f"({device['platform']})", flush=True)

    from repro.core.profiler import SPANS
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    print(f"compile cache: {cache} ({cache_entries(cache)} entries before)",
          flush=True)
    snap = SPANS.snapshot()

    shutil.rmtree(WORKDIR, ignore_errors=True)
    try:
        (four_chips if args.four_chips else one_chip)(WORKDIR)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    compile_s, hits = compiles(SPANS.since(snap))
    print(f"compile cache: {cache} ({cache_entries(cache)} entries after); "
          f"compile {compile_s:.1f} s in all, {hits} cache hits", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
