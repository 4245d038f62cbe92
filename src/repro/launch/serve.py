"""Serving driver: BootSeer-managed startup, then a batched serving session
with the ServeEngine (prefill + decode over a shared cache), at the
architecture's published widths (``--tiny``: the reduced variant, for CPU
runs and tests).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m \
        --requests 6 --new-tokens 16 --workdir /tmp/bootseer_serve

Like the training driver, restarts are warm: the image hot-block record and
env cache survive in the workdir, so a second invocation starts faster —
the paper's many-short-jobs workload (§4, "feature testing" jobs).
"""

from __future__ import annotations

import argparse
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from repro.blockstore.registry import Registry
from repro.ckpt.checkpoint import Checkpointer
from repro.configs import ARCHS, get_config, get_tiny
from repro.core.bootseer import BootseerRuntime, JobSpec
from repro.core.stages import Stage
from repro.dfs.hdfs import HdfsCluster, ThrottleModel
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import device_summary, ensure_image, stage_seconds
from repro.models.model import Model
from repro.serve.engine import Request, ServeEngine
from repro.sharding.rules import single_device_rules


def main(argv=None) -> dict:
    """Run startup then a serving session; returns a summary (device,
    startup stage seconds, requests served with their prompts and
    generated tokens)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m", choices=list(ARCHS))
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU runs and tests)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--workdir",
                    default=str(Path(tempfile.gettempdir()) / "bootseer_serve"))
    ap.add_argument("--no-bootseer", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    root = Path(args.workdir)
    root.mkdir(parents=True, exist_ok=True)
    reg = Registry(root / "registry", throttle=ThrottleModel(
        bandwidth=3e7, per_stream=2e6, timescale=1.0))
    ensure_image(root, reg)
    hdfs = HdfsCluster(root / "hdfs", num_groups=8, block_size=1 << 20)

    spec = JobSpec(
        job_id=f"serve-{args.arch}", image="train-image",
        num_nodes=args.nodes,
        job_params={"arch": args.arch, "mode": "serve"},
        startup_reads=[("bin/python", 0, -1), ("libframework.so", 0, -1)],
        env_setup=lambda t, r: (time.sleep(0.08),
                                (t / "serving_deps.py").write_text("x")))
    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=root / "rt",
                         optimize=not args.no_bootseer)
    res = rt.run_startup(spec)
    startup = stage_seconds(res, (Stage.IMAGE_LOAD, Stage.ENV_SETUP))
    for name, sec in startup.items():
        print(f"startup {name:<12} {sec:6.2f}s")
    print(f"startup was {'warm' if res.notes.get('prefetch_used') else 'cold'}")

    cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
    model = Model(cfg, single_device_rules())
    # serving params live in a checkpoint: the first invocation seeds it,
    # warm restarts restore through the planned path under the runtime's
    # IOScheduler at CRITICAL (params gate time-to-first-token) — the
    # same discipline the training startup DAG uses, instead of the old
    # fresh init on every boot.
    ckpt = Checkpointer(hdfs, f"/serve_ckpt/{args.arch}")
    if ckpt.latest_step() is None:
        params = model.init(jax.random.key(0))
        ckpt.save(0, params)
        print("serve params: seeded checkpoint step 0")
    engine = ServeEngine.from_checkpoint(
        model, ckpt, batch=args.batch, cache_len=args.cache_len,
        sched=rt.io_sched)
    if rt.io_sched is not None:
        dfs = rt.io_sched.snapshot().get("dfs", {})
        print(f"serve params: planned restore read "
              f"{dfs.get('bytes', {}).get('critical', 0)} bytes at "
              "CRITICAL")

    rng = np.random.default_rng(0)
    todo = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 12)).astype(np.int32),
                    max_new_tokens=args.new_tokens,
                    temperature=0.7 if i % 2 else 0.0)
            for i in range(args.requests)]
    served = []
    t0 = time.perf_counter()
    while todo:
        n = min(args.batch, len(todo))
        served += engine.generate(todo[:n])[:n]  # generate() pads the list
        todo = todo[n:]
    dt = time.perf_counter() - t0
    rt.drain_deferred()   # surface deferred stream failures
    done = sum(len(r.generated) for r in served)
    device = device_summary()
    print(f"served {len(served)} requests, {done} tokens in {dt:.2f}s "
          f"({done / dt:.1f} tok/s on {device['count']} x "
          f"{device['kind']})")
    return {"arch": cfg.name, "device": device, "startup_s": startup,
            "serve_s": dt,
            "requests": [{"prompt": r.prompt.tolist(),
                          "temperature": r.temperature,
                          "max_new_tokens": r.max_new_tokens,
                          "generated": list(r.generated)} for r in served]}


if __name__ == "__main__":
    main()
