"""Batched serving engine: continuous-batch prefill + greedy/temperature
decode over a shared KV cache."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.serve.step import jit_decode_step, jit_prefill


@dataclass
class Request:
    prompt: np.ndarray              # [prompt_len] int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    generated: list = field(default_factory=list)


class ServeEngine:
    """Minimal batched engine: pads a request batch to a fixed shape,
    prefills once, then decodes step-by-step for all sequences together."""

    def __init__(self, model: Model, params, *, batch: int, cache_len: int,
                 tune_profile=None):
        self.model = model
        # placed once with the steps' shardings: host arrays (a restored
        # checkpoint) would otherwise be copied to the device on every call
        self.params = jax.device_put(
            params, model.rules.named_tree(model.param_specs()))
        self.batch = batch
        self.cache_len = cache_len
        # kernel launch configs for this replica: installed as the
        # ambient profile around generate(), so the prefill/decode
        # traces resolve tuned block shapes instead of defaults
        self.tune_profile = tune_profile
        self._prefill = jit_prefill(model, batch, cache_len)
        self._decode = jit_decode_step(model, batch, cache_len)

    @classmethod
    def from_checkpoint(cls, model: Model, checkpointer, step=None, *,
                        batch: int, cache_len: int, sched=None,
                        priority=None, tune_store=None) -> "ServeEngine":
        """Build an engine whose params come from a checkpoint via the
        planned restore path — ``restore_planned(sched=, priority=
        CRITICAL)`` — instead of a raw reader: serving cold-starts are
        exactly the startup I/O the IOScheduler exists to arbitrate, so
        a replica booting under load competes for DFS tokens at CRITICAL
        (params gate time-to-first-token) rather than bypassing the
        scheduler.  Params-only: no optimizer wave is planned or read.

        ``tune_store``: a ``repro.tune.store.ProfileStore`` — the
        replica fetches the cluster's TuningProfile (tiny, metered,
        DEFERRED by the store's own default priority: it never gates
        time-to-first-token) so a serving cold-start inherits tuned
        kernel configs with zero re-tuning; a missing or corrupt
        profile silently keeps the defaults.
        """
        from repro.core.pipeline import CRITICAL
        if step is None:
            step = checkpointer.latest_step()
            if step is None:
                raise FileNotFoundError(
                    "from_checkpoint: no checkpoint steps found under "
                    f"{checkpointer.base!r}")
        like = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        (params,) = checkpointer.restore_planned(
            step, like, sched=sched,
            priority=CRITICAL if priority is None else priority)
        tune_profile = tune_store.fetch() if tune_store is not None \
            else None
        return cls(model, params, batch=batch, cache_len=cache_len,
                   tune_profile=tune_profile)

    def generate(self, requests: list[Request], seed: int = 0) -> list[Request]:
        if self.tune_profile is None:
            return self._generate(requests, seed)
        from repro.tune.profile import use_profile
        with use_profile(self.tune_profile):
            return self._generate(requests, seed)

    def _generate(self, requests: list[Request], seed: int = 0) -> list[Request]:
        assert len(requests) <= self.batch
        # pad the request list to the engine batch
        while len(requests) < self.batch:
            requests.append(Request(prompt=np.zeros(1, np.int32),
                                    max_new_tokens=0))
        plen = max(len(r.prompt) for r in requests)
        toks = np.zeros((self.batch, plen), np.int32)
        for i, r in enumerate(requests):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)})

        key = jax.random.key(seed)
        max_new = max(r.max_new_tokens for r in requests)
        pos = plen
        last = None
        for step in range(max_new):
            if last is None:
                nxt = self._sample(logits, requests, key, step)
            else:
                nxt = last
            logits, cache = self._decode(
                self.params, jnp.asarray(nxt)[:, None], cache,
                jnp.int32(pos))
            pos += 1
            out = self._sample(logits, requests, key, step)
            for i, r in enumerate(requests):
                if step < r.max_new_tokens:
                    r.generated.append(int(out[i]))
            last = out
        return requests

    def _sample(self, logits, requests, key, step):
        logits = np.asarray(logits, np.float32)
        out = np.argmax(logits, axis=-1).astype(np.int32)
        for i, r in enumerate(requests):
            if r.temperature > 0:
                k = jax.random.fold_in(jax.random.fold_in(key, step), i)
                p = jax.nn.softmax(jnp.asarray(logits[i]) / r.temperature)
                out[i] = int(jax.random.choice(k, logits.shape[-1], p=p))
        return out
