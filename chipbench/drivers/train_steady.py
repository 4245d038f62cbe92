"""Steady-state training: the jitted train step and its sharded loader,
driven for a fixed window after the first three steps.

Traffic parameters: ``batch``, ``seq_len``, ``trace_steps``.

Set-up makes the weights from the seed on the device in one jitted call,
builds the program's step (``train.step.jit_train_step``) and loader
(``data.loader.ShardedLoader`` over the benchmark's seeded stream), and
runs steps 0-2 through them: the first compiles, and all three are the
ones the reference follows.  The window then keeps calling the same step
on fresh batches until ``--seconds`` have passed, one step in flight ahead
of the host, and ends on ``block_until_ready`` of the last step.
"""

from __future__ import annotations

import json
import time

import jax

from chipbench import train_check
from chipbench import trace as tr
from chipbench.harness import log, reduce_trace
from chipbench.reference.precision import F32
from chipbench.streams import TokenStream, jax_seed

CHECKED_STEPS = 3


def run(ctx) -> dict:
    from repro.data.loader import ShardedLoader
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.sharding.rules import single_device_rules
    from repro.train.step import jit_train_step

    m, hp, t = ctx.config["model"], ctx.config["optimizer"], ctx.traffic
    bsz, seq = t["batch"], t["seq_len"]
    ref = ctx.reference
    key = jax.random.key(jax_seed(ctx.seed))
    make_params = jax.jit(lambda k: ref.init(m, k))

    model = Model(ctx.program_config(), single_device_rules())
    params = make_params(key)
    opt = jax.jit(adamw_init)(params)
    step_fn = jit_train_step(model, AdamWConfig(**hp), bsz)
    stream = TokenStream(m["vocab_size"], ctx.seed)
    loader = ShardedLoader(stream, model.rules, bsz, seq)

    prog = {"losses": []}
    for i in range(CHECKED_STEPS):
        data = loader(i)
        if i == 0:
            ma = step_fn.lower(params, opt, data).compile().memory_analysis()
            log(f"train step: arguments {ma.argument_size_in_bytes} B, "
                f"temporaries {ma.temp_size_in_bytes} B, outputs "
                f"{ma.output_size_in_bytes} B (compiler)")
        params, opt, met = step_fn(params, opt, data)
        prog["losses"].append(float(met["loss"]))
        if i == 0:   # the first moment after one step is (1 - b1) g
            prog["grad"] = {k: v / (1 - hp["b1"]) for k, v in
                            train_check.norms(opt["mu"]).items()}
    prog["change"] = train_check.diff_norms(params, make_params(key))
    jax.block_until_ready((params, opt))
    ctx.setup_done()

    snap = ctx.clock.snapshot()
    step, done, prev = CHECKED_STEPS, 0, None
    t0 = time.perf_counter()
    while True:
        params, opt, met = step_fn(params, opt, loader(step))
        step += 1
        done += 1
        if prev is not None:
            prev.block_until_ready()
        prev = met["loss"]
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready((params, opt, met))
    window_s = time.perf_counter() - t0
    inside = ctx.clock.since(snap)
    tokens_per_s = done * bsz * seq / window_s
    log(f"window: {done} steps of {bsz} x {seq} tokens in {window_s:.3f} s; "
        f"{inside['compiles']} compiles, {inside['cache_hits']} cache reads "
        f"inside the window")

    traced = None
    if ctx.trace:
        with tr.capture(ctx.workdir / "trace"):
            for _ in range(t["trace_steps"]):
                with tr.span("loader"):
                    data = loader(step)
                with tr.span("dispatch"):
                    params, opt, met = step_fn(params, opt, data)
                with tr.span("sync"):
                    met["loss"].block_until_ready()
                step += 1
        traced = reduce_trace(ctx.workdir / "trace")

    memory = ctx.memory_peak()
    log(f"peak_bytes_in_use: {memory} B (device)")
    del params, opt, met, prev
    batches = [stream.inputs(i, bsz, seq) for i in range(CHECKED_STEPS)]
    zeros = jax.jit(lambda p: jax.tree.map(lambda x: 0 * x, p))

    def start():
        p = make_params(key)
        return p, zeros(p), zeros(p)

    t_ref = time.perf_counter()
    refd = train_check.reference_steps(ref, m, hp, start, batches, F32)
    r = train_check.readings(prog, refd)
    log(f"reference: {CHECKED_STEPS} steps in "
        f"{time.perf_counter() - t_ref:.1f} s; losses {prog['losses']} vs "
        f"{refd['losses']}")
    log("readings: " + json.dumps(r))
    return {
        "attempted": done + CHECKED_STEPS, "failed": 0,
        "e2e": {"train_tokens_per_s": tokens_per_s},
        "layer": {"train_tokens_per_s": tokens_per_s,
                  "train_flops_per_token":
                      ctx.cost.train_flops_per_token(m, seq),
                  "inside_window": inside},
        "trace": traced, "memory_peak_bytes": memory,
        "checks": ctx.checks(r),
    }
