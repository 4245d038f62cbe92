"""Training loop with BootSeer-profiled startup stages and periodic
checkpointing through the striped DFS (repro.ckpt)."""

from __future__ import annotations

from typing import Callable, Optional

import jax

from repro.core.profiler import span
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.step import jit_train_step, state_shardings


def train_loop(model: Model, *, tune_profile=None, **kw):
    """Train on the synthetic stream.  Returns (params, opt_state, history).

    See :func:`_train_loop` for the full keyword set.  ``tune_profile``:
    a :class:`repro.tune.profile.TuningProfile` installed as the ambient
    profile for the loop's duration, so the kernel ops resolve their
    tuned launch configs (block shapes, SSD chunk) instead of hardcoded
    defaults — the training-side consumer of the boot-time profile
    restore."""
    if tune_profile is None:
        return _train_loop(model, **kw)
    from repro.tune.profile import use_profile
    with use_profile(tune_profile):
        return _train_loop(model, **kw)


def _train_loop(model: Model, *, batch: int, seq_len: int, steps: int,
                opt_cfg: Optional[AdamWConfig] = None, seed: int = 0,
                log_every: int = 10, log_fn: Callable = print,
                checkpointer=None, ckpt_every: int = 0, full_every: int = 0,
                params=None, opt_state=None, start_step: int = 0,
                resume_from: Optional[int] = None, restore_specs=None,
                restore_coords: Optional[dict] = None, restore_sched=None):
    """Train on the synthetic stream.  Returns (params, opt_state, history).

    ``resume_from``: checkpoint step to restore through the planner
    (``checkpointer.restore_planned``) before training.  Params restore
    first (wave 0); the optimizer state streams as an async second wave
    that overlaps loader setup and the step's ahead-of-time compile.
    ``restore_specs``
    optionally carries PartitionSpec trees congruent to (params, opt) for
    sharding-aware partial restore against ``model.rules``;
    ``restore_coords`` gives this host's mesh coordinates (default: mesh
    position of rank 0 — on a trivial mesh that is the full extent).
    ``restore_sched`` attaches an ``IOScheduler`` to the restore's preads
    (params wave CRITICAL, async optimizer tail DEFERRED).

    ``full_every``: with ``ckpt_every``, write every ``full_every``-th
    checkpoint as a full snapshot and the ones between as incremental
    deltas chained against the previous save (``save_delta``) — the
    continuous-recovery cadence.  0 (default) keeps every save full.
    """
    from repro.data.loader import ShardedLoader
    from repro.data.synthetic import SyntheticStream

    opt_cfg = opt_cfg or AdamWConfig()
    if resume_from is not None and checkpointer is None:
        raise ValueError(
            f"resume_from={resume_from} requires a checkpointer — without "
            "one the run would silently train from scratch")
    # every tree is placed with the step's own shardings, so a restored
    # or freshly initialized state is never held whole on one device
    pshard, oshard = state_shardings(model)
    if resume_from is None:
        if params is None:
            params = model.init(jax.random.key(seed))
        if opt_state is None:
            opt_state = adamw_init(params)
        params, opt_state = jax.device_put((params, opt_state),
                                           (pshard, oshard))

    opt_tail = None
    if resume_from is not None:
        # restore targets: shapes only, so no device memory is spent on a
        # state the checkpoint overwrites
        if params is None:
            params = jax.eval_shape(model.init, jax.random.key(seed))
        if opt_state is None:
            opt_state = jax.eval_shape(adamw_init, params)
        if restore_coords is None and restore_specs is not None:
            restore_coords = model.rules.coords_of_rank(0)
        with span("train.restore"):
            params, opt_tail = checkpointer.restore_planned(
                resume_from, params, opt_state, specs=restore_specs,
                rules=model.rules, coords=restore_coords, async_tail=True,
                sched=restore_sched)
        with span("train.place.params"):
            params = jax.device_put(params, pshard)
        start_step = resume_from

    with span("train.build"):
        step_fn = jit_train_step(model, opt_cfg, batch)
        loader = ShardedLoader(SyntheticStream(model.cfg.vocab_size, seed),
                               model.rules, batch, seq_len)
    with span("train.compile"):
        # compile from shapes and shardings alone, so on a resume it runs
        # while the optimizer wave streams in; every step, the first
        # included, then calls this one executable
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            (params, opt_state), (pshard, oshard))
        step_fn = step_fn.lower(*state, loader(start_step)).compile()
    if opt_tail is not None:
        with span("train.opt_wait"):
            (opt_state,) = opt_tail.result()
        with span("train.place.opt"):
            opt_state = jax.device_put(opt_state, oshard)

    history = []
    saves = 0       # saves this run: every full_every-th one is full
    last_saved: Optional[int] = None
    for step in range(start_step, start_step + steps):
        with span("train.step"):
            data = loader(step)
            params, opt_state, metrics = step_fn(params, opt_state, data)
            if (step - start_step) % log_every == 0 \
                    or step == start_step + steps - 1:
                loss = float(metrics["loss"])
                grad_norm = float(metrics["grad_norm"])
                history.append({"step": step, "loss": loss,
                                "grad_norm": grad_norm})
                log_fn(f"step {step:5d}  loss {loss:.4f}  "
                       f"gnorm {grad_norm:.3f}")
        if checkpointer is not None and ckpt_every and \
                (step + 1) % ckpt_every == 0:
            if full_every and saves % full_every != 0 \
                    and last_saved is not None:
                checkpointer.save_delta(step + 1, params, opt_state,
                                        base=last_saved)
            else:
                checkpointer.save(step + 1, params, opt_state)
            saves += 1
            last_saved = step + 1
    return params, opt_state, history
