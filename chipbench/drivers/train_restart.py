"""Crash-restart of a training job: relaunch -> BootSeer startup ->
planned restore -> first training step, repeated for the window.

Traffic parameters: ``batch``, ``seq_len``, ``resume_step``, ``nodes``,
``dfs`` and ``registry`` (ThrottleModel rates of the modelled storage).

Set-up builds the job's working directory (the image with the training
driver's layout, the registry and the DFS), writes a step-``resume_step``
checkpoint of parameters and AdamW state made from the seed through an
unthrottled DFS over the same root, and runs one whole restart: that boot
records the hot blocks and the environment snapshot, and compiles what a
restart runs into the persistent cache, as a job's first start would.

Each restart in the window does what ``launch/train.py:main`` does after
process start: build the registry, DFS, checkpointer and a fresh
``BootseerRuntime(optimize=True)``, ``run_startup`` with the resume plan
"rows", then ``train_loop(resume_from=..., steps=1)``, and ends on
``block_until_ready`` of the step's outputs.  Between restarts, outside
the timed span: ``drain_deferred()``, ``close()``, every device array
dropped and ``jax.clear_caches()``, so that each restart compiles from the
persistent cache as a new process would.  ``restart_s`` leaves out process
start and TPU client creation, which ``setup_s`` pays once.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import statistics
import time

import jax
import jax.numpy as jnp

from chipbench import train_check
from chipbench import trace as tr
from chipbench.harness import log, reduce_trace
from chipbench.reference.precision import F32
from chipbench.streams import TokenStream, jax_seed


class TimedRestore:
    """Proxy of the checkpointer that times ``restore_planned`` and the
    wait on its async optimizer tail (as the training driver's ``Saver``
    wraps ``save``), and keeps what the restore returned."""

    def __init__(self, ck):
        self._ck = ck
        self.seconds = 0.0
        self.restored = None

    def restore_planned(self, *args, **kw):
        t0 = time.perf_counter()
        with tr.span("restore.params"):
            params, tail = self._ck.restore_planned(*args, **kw)
        self.seconds += time.perf_counter() - t0
        self.restored = [params]
        return params, _TimedTail(tail, self)

    def __getattr__(self, name):
        return getattr(self._ck, name)


class _TimedTail:
    def __init__(self, fut, owner):
        self._fut = fut
        self._owner = owner

    def result(self, timeout=None):
        t0 = time.perf_counter()
        with tr.span("restore.opt_tail"):
            out = self._fut.result(timeout)
        self._owner.seconds += time.perf_counter() - t0
        self._owner.restored += list(out)
        return out


def make_state(ref, m: dict, step: int):
    """Seeded parameters and AdamW state as they might stand at ``step``:
    gradients of a clipped step's size (``g`` per element, the global norm
    1 spread over every parameter); the first moment an average of such
    gradients (spread 0.25 g), the second moment of their squares averaged
    over the ~40 steps b2 = 0.95 remembers (Gamma(20) / 20 times g^2), so
    that |mu| / sqrt(nu) stays of order one, as in a real AdamW state."""

    def make(key):
        kp, km, kn = jax.random.split(key, 3)
        params = ref.init(m, kp)
        leaves, tree = jax.tree.flatten(params)
        g = 1.0 / math.sqrt(sum(x.size for x in leaves))
        mu = [0.25 * g * jax.random.normal(k, x.shape, jnp.float32)
              for k, x in zip(jax.random.split(km, len(leaves)), leaves)]
        nu = [g * g * jax.random.gamma(k, 20.0, x.shape, jnp.float32) / 20
              for k, x in zip(jax.random.split(kn, len(leaves)), leaves)]
        return params, {"mu": jax.tree.unflatten(tree, mu),
                        "nu": jax.tree.unflatten(tree, nu),
                        "step": jnp.int32(step)}

    return jax.jit(make)


def env_setup(target, rank):
    """The job's install commands (those of the training driver)."""
    time.sleep(0.1)
    for i in range(8):
        (target / f"dep{i}.py").write_text(f"v={i}")


def run(ctx) -> dict:
    from repro.blockstore.registry import Registry
    from repro.ckpt.checkpoint import Checkpointer
    from repro.core.bootseer import BootseerRuntime, JobSpec
    from repro.dfs.hdfs import HdfsCluster, ThrottleModel
    from repro.launch.train import ensure_image
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.sharding.rules import single_device_rules
    from repro.train.loop import train_loop

    m, hp, t = ctx.config["model"], ctx.config["optimizer"], ctx.traffic
    bsz, seq, resume = t["batch"], t["seq_len"], t["resume_step"]
    ref = ctx.reference
    key = jax.random.key(jax_seed(ctx.seed))
    cfg = ctx.program_config()
    root = ctx.workdir
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)

    def registry():
        return Registry(root / "registry", throttle=ThrottleModel(
            timescale=1.0, **t["registry"]))

    def dfs(throttled: bool = True):
        return HdfsCluster(root / "hdfs", num_groups=t["dfs"]["stripes"],
                           block_size=1 << 20, throttle=ThrottleModel(
                               bandwidth=t["dfs"]["bandwidth"],
                               per_stream=t["dfs"]["per_stream"],
                               timescale=1.0) if throttled else None)

    ensure_image(root, registry())
    state = make_state(ref, m, resume)(key)
    t0 = time.perf_counter()
    Checkpointer(dfs(throttled=False), striped=True,
                 width=t["dfs"]["stripes"]).save(resume, *state)
    del state
    os.sync()   # its dirty pages would otherwise flush inside the window
    log(f"set-up: step-{resume} checkpoint written in "
        f"{time.perf_counter() - t0:.1f} s")

    spec = JobSpec(
        job_id=f"train-{cfg.name}", image="train-image",
        num_nodes=t["nodes"],
        job_params={"arch": cfg.name, "deps": ["framework==2.1"]},
        startup_reads=[("bin/python", 0, -1), ("libframework.so", 0, -1)],
        env_setup=env_setup, resume_step=resume, resume_plan="rows")

    def restart() -> dict:
        snap = ctx.clock.snapshot()
        t0 = time.perf_counter()
        with tr.span("startup"):
            hdfs = dfs()
            ck = Checkpointer(hdfs, striped=True, width=t["dfs"]["stripes"])
            rt = BootseerRuntime(registry=registry(), hdfs=hdfs,
                                 workdir=root / "rt", optimize=True)
            res = rt.run_startup(spec, checkpointer=ck)
        proxy = TimedRestore(ck)
        with tr.span("train_loop"):
            model = Model(cfg, single_device_rules())
            params, opt, hist = train_loop(
                model, batch=bsz, seq_len=seq, steps=1, resume_from=resume,
                checkpointer=proxy, seed=ctx.seed,
                opt_cfg=AdamWConfig(**hp), log_fn=lambda *_: None)
            jax.block_until_ready((params, opt))
        seconds = time.perf_counter() - t0
        comp = ctx.clock.since(snap)
        rt.drain_deferred()
        rt.close()
        return {"restart_s": seconds, "dag_s": res.total_s,
                "restore_s": proxy.seconds, "read_bytes": hdfs.read_bytes,
                "compile_s": comp["compile_s"] + comp["retrieve_s"],
                "compiles": comp["compiles"], "cache_hits": comp["cache_hits"],
                "loss": hist[0]["loss"], "outputs": (params, opt),
                "restored": proxy.restored}

    def fresh():
        gc.collect()
        jax.clear_caches()

    first = restart()
    log(f"set-up restart: {first['restart_s']:.2f} s, "
        f"{first['compiles']} compiles ({first['compile_s']:.2f} s)")
    del first
    fresh()
    ctx.setup_done()

    done: list = []
    last = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        last = None
        fresh()
        last = restart()
        done.append({k: v for k, v in last.items()
                     if k not in ("outputs", "restored")})
        log("restart {}: {restart_s:.3f} s (startup DAG {dag_s:.3f} s, "
            "restore {restore_s:.3f} s, {read_bytes} B read, compile "
            "{compile_s:.3f} s: {compiles} compiles, {cache_hits} cache "
            "reads)".format(len(done), **done[-1]))
    window_s = time.perf_counter() - t0
    busy = sum(r["restart_s"] for r in done)
    log(f"window: {len(done)} restarts in {window_s:.3f} s, "
        f"{100 * busy / window_s:.1f}% of it inside timed restarts")

    memory = ctx.memory_peak()

    # what the last restart restored and produced, against the saved state
    # (its outputs wait on the host while the device remakes that state)
    params, opt = jax.device_get(last.pop("outputs"))
    restored = last.pop("restored")
    saved_p, saved_o = make_state(ref, m, resume)(key)
    mismatch = 0
    for got, want in zip(
            jax.tree.leaves(restored),
            jax.tree.leaves((saved_p, saved_o["mu"], saved_o["nu"],
                             saved_o["step"]))):
        mismatch += int(jnp.sum(jnp.asarray(got) != want))
    prog = {"losses": [r["loss"] for r in done],
            "grad": train_check.moment_grad_norms(opt["mu"], saved_o["mu"],
                                                  hp["b1"]),
            "change": train_check.diff_norms(params, saved_p)}
    del params, opt, saved_p, saved_o, restored, last

    traced = None
    if ctx.trace:
        fresh()
        with tr.capture(ctx.workdir / "trace"):
            restart()
        traced = reduce_trace(ctx.workdir / "trace")
    fresh()

    def start():
        p, o = make_state(ref, m, resume)(key)
        return p, o["mu"], o["nu"]

    stream = TokenStream(m["vocab_size"], ctx.seed)
    t_ref = time.perf_counter()
    refd = train_check.reference_steps(
        ref, m, hp, start, [stream.inputs(resume, bsz, seq)], F32,
        count0=resume)
    refd["losses"] = refd["losses"] * len(done)
    r = train_check.readings(prog, refd)
    log(f"reference: 1 step in {time.perf_counter() - t_ref:.1f} s; losses "
        f"{prog['losses']} vs {refd['losses'][0]}")
    log("readings: " + json.dumps(dict(r, restored_mismatch=mismatch)))
    shutil.rmtree(root, ignore_errors=True)
    mean = lambda k: statistics.fmean(x[k] for x in done)
    return {
        "attempted": len(done), "failed": 0,
        "e2e": {"restart_s": mean("restart_s")},
        "layer": {"startup_dag_s": mean("dag_s"),
                  "ckpt_restore_s": mean("restore_s"),
                  "ckpt_read_gb": mean("read_bytes") / 1e9,
                  "compile_s": mean("compile_s"),
                  "restarts": done},
        "trace": traced, "memory_peak_bytes": memory,
        "checks": ctx.checks(dict(r, restored_mismatch=mismatch)),
    }
