"""Compile a cell's programs for a described (not attached) TPU v5e and
print what each needs of the chip's memory.  Runs on the CPU:

    JAX_PLATFORMS=cpu python chipbench/tools/compile_v5e.py [program ...]

Programs: ``train_step`` (the program's jitted step at the training cells'
batch) and ``reference_step`` (the plain reference's loss, gradient and
AdamW step at the same batch).  Nothing runs; a compile that passes is not
a chip run.
"""

from __future__ import annotations

import os
import sys
from functools import partial
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def shaped(tree, sharding):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    from chipbench.reference import adamw
    from chipbench.reference.precision import F32

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = harness.load_json(harness.HERE / "configs" / "mamba2-370m.json")
    traffic = harness.load_json(harness.HERE / "traffic" / "train-steady.json")
    m, hp = cfg["model"], cfg["optimizer"]
    bsz, seq = traffic["batch"], traffic["seq_len"]
    ref = harness.load_module(harness.HERE / "reference" / "mamba2.py")
    params = shaped(jax.eval_shape(lambda: ref.init(m, jax.random.key(0))),
                    one)
    tok = jax.ShapeDtypeStruct((bsz, seq), jnp.int32, sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    want = set(argv) or {"train_step", "reference_step"}

    def report(name, compiled):
        ma = compiled.memory_analysis()
        print(f"{name}: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{ma.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.2f} GB", flush=True)

    if "reference_step" in want:
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def ref_step(p, mu, nu, count, t, l):
            loss, g = jax.value_and_grad(
                lambda q: ref.loss(m, q, t, l, F32))(p)
            return (loss,) + adamw.update(p, g, mu, nu, count, hp)[:3]
        report("reference_step", ref_step.lower(
            params, params, params, step, tok, tok).compile())

    if "train_step" in want:
        from repro.models.model import Model
        from repro.optim.adamw import AdamWConfig
        from repro.sharding.rules import make_rules
        from repro import compat
        from repro.train.step import make_train_step
        mesh = compat.make_mesh((1, 1), ("data", "model"),
                                devices=topo.devices[:1])
        model = Model(harness.program_config(cfg), make_rules(mesh))
        fn = jax.jit(make_train_step(model, AdamWConfig(**hp)),
                     donate_argnums=(0, 1))
        opt = {"mu": params, "nu": params, "step": step}
        report("train_step", fn.lower(params, opt,
                                      {"tokens": tok, "labels": tok}).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
