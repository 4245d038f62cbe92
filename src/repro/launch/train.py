"""End-to-end training driver with BootSeer-managed startup.

Runs the full worker-phase startup (image load -> env setup -> model init)
through the BootSeer runtime with real I/O, then trains an assigned
architecture at its published widths (``--tiny``: the reduced variant, for
CPU runs and tests) on a ``--mesh DxM`` data x model mesh, with periodic
checkpoints into the striped DFS.  Restartable: a second invocation with
the same --workdir resumes from the latest checkpoint via the warm path
(hot-block prefetch + env cache + striped resume).

    PYTHONPATH=src python -m repro.launch.train \
        --arch mamba2-370m --steps 40 --workdir /tmp/bootseer_job
"""

from __future__ import annotations

import argparse
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from repro import compat
from repro.blockstore.image import build_image
from repro.blockstore.registry import Registry
from repro.ckpt.checkpoint import Checkpointer
from repro.configs import ARCHS, get_config, get_tiny
from repro.core.bootseer import BootseerRuntime, JobSpec
from repro.core.stages import Stage
from repro.dfs.hdfs import HdfsCluster, ThrottleModel
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import Model
from repro.sharding.rules import make_rules
from repro.train.loop import train_loop

BS = 64 * 1024


def ensure_image(root: Path, reg: Registry) -> None:
    try:
        reg.get_manifest("train-image")
        return
    except FileNotFoundError:
        pass
    src = root / "image_src"
    (src / "bin").mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(0)
    (src / "bin" / "python").write_bytes(
        rng.integers(0, 256, 8 * BS, dtype=np.uint8).tobytes())
    (src / "libframework.so").write_bytes(
        rng.integers(0, 256, 12 * BS, dtype=np.uint8).tobytes())
    (src / "assets.tar").write_bytes(
        rng.integers(0, 256, 32 * BS, dtype=np.uint8).tobytes())
    build_image(src, reg, "train-image", block_size=BS)


def device_summary() -> dict:
    """The device the run used, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def stage_seconds(res, stages) -> dict:
    """Slowest node's seconds per startup stage, plus the DAG total."""
    out = {st.value: max(d.get(st.value, 0) for d in res.node_stage_s.values())
           for st in stages}
    out["total"] = res.total_s
    return out


def bytes_per_device(tree) -> dict:
    """{device id: bytes of ``tree``'s shards that device holds}."""
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def parse_mesh(text: str) -> tuple[int, int]:
    try:
        d, m = (int(v) for v in text.lower().split("x"))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--mesh wants DxM (data x model), got {text!r}") from None
    return d, m


def main(argv=None) -> dict:
    """Run startup then training; returns a summary of the run (device,
    startup stage seconds, resume step, logged losses, saved steps and
    the parameter bytes each device holds)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m", choices=list(ARCHS))
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU runs and tests)")
    ap.add_argument("--mesh", type=parse_mesh, default=(1, 1),
                    help="DxM device mesh over (data, model)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--regions", type=int, default=1,
                    help="region tier for the swarm: node ranks stripe "
                         "over this many regions (cross-region fetches "
                         "ride the WAN tier exactly once per block)")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--workdir",
                    default=str(Path(tempfile.gettempdir()) / "bootseer_job"))
    ap.add_argument("--no-bootseer", action="store_true",
                    help="baseline startup (no prefetch/env-cache/striping)")
    args = ap.parse_args(argv)
    use_compile_cache()

    root = Path(args.workdir)
    root.mkdir(parents=True, exist_ok=True)
    reg = Registry(root / "registry", throttle=ThrottleModel(
        bandwidth=3e7, per_stream=2e6, timescale=1.0))
    ensure_image(root, reg)
    hdfs = HdfsCluster(root / "hdfs", num_groups=8, block_size=1 << 20,
                       throttle=ThrottleModel(bandwidth=1e9, per_stream=2e7,
                                              timescale=1.0))
    ck = Checkpointer(hdfs, striped=not args.no_bootseer, width=8)
    resume = ck.latest_step()

    def env_setup(target, rank):
        time.sleep(0.1)
        for i in range(8):
            (target / f"dep{i}.py").write_text(f"v={i}")

    spec = JobSpec(
        job_id=f"train-{args.arch}", image="train-image",
        num_nodes=args.nodes,
        job_params={"arch": args.arch, "deps": ["framework==2.1"]},
        startup_reads=[("bin/python", 0, -1), ("libframework.so", 0, -1)],
        env_setup=env_setup, resume_step=resume,
        resume_plan="rows")

    topology = None
    if args.regions > 1:
        from repro.blockstore.swarm import Topology

        def region_fn(node_id, _n=args.regions):
            digits = "".join(ch for ch in node_id if ch.isdigit())
            return f"region{int(digits or 0) % _n}"

        topology = Topology(region_fn=region_fn)

    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=root / "rt",
                         optimize=not args.no_bootseer, topology=topology)
    print(f"== startup ({'baseline' if args.no_bootseer else 'BootSeer'}"
          f"{', resume@' + str(resume) if resume else ', cold'}) ==")
    res = rt.run_startup(spec, checkpointer=ck)
    startup = stage_seconds(
        res, (Stage.IMAGE_LOAD, Stage.ENV_SETUP, Stage.MODEL_INIT))
    for name, sec in startup.items():
        print(f"  {name:<12} {sec:6.2f}s")

    device = device_summary()
    print(f"== training on {device['count']} x {device['kind']} "
          f"({device['platform']}), mesh {args.mesh[0]}x{args.mesh[1]} ==")
    rules = make_rules(compat.make_mesh(args.mesh, ("data", "model")))
    cfg = get_tiny(args.arch) if args.tiny else get_config(args.arch)
    model = Model(cfg, rules)
    if resume is not None:
        print(f"resuming params/opt from step {resume} "
              "(planned two-wave restore)")

    saved: list = []

    class Saver:
        """Logs saves; delegates restore_planned etc. to the real ckpt."""

        def save(self, step, p, o):
            t0 = time.perf_counter()
            ck.save(step, p, o)
            dt = time.perf_counter() - t0
            saved.append({"step": step, "s": dt})
            print(f"  checkpoint @ step {step} "
                  f"({ck.load_index(step).total_bytes / 2**20:.1f} MiB, "
                  f"{'striped' if ck.striped else 'plain'}, {dt:.2f}s)")

        def __getattr__(self, name):
            return getattr(ck, name)

    params, _, hist = train_loop(
        model, batch=args.batch, seq_len=args.seq_len, steps=args.steps,
        resume_from=resume, checkpointer=Saver(),
        ckpt_every=args.ckpt_every)
    rt.drain_deferred()   # surface deferred restore/stream failures
    if hist:
        print(f"done: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    return {"arch": cfg.name, "device": device, "mesh": list(args.mesh),
            "startup_s": startup, "resume_step": resume,
            "losses": [(h["step"], h["loss"]) for h in hist],
            "saved": saved,
            "param_bytes_per_device": bytes_per_device(params)}


if __name__ == "__main__":
    main()
