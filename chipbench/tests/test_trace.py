"""The trace reduction, on hand-made intervals and on a small trace
recorded here (the CPU client's threads stand in for the device)."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as tr


def test_union_merges_overlaps_and_sorts():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == \
        [(0, 3), (5, 8)]


def test_reduce_busy_idle_ops_and_named_gaps():
    dev = {"/device:TPU:0": [("fusion.1", 100, 300), ("dot.2", 250, 400),
                             ("fusion.1", 600, 700)]}
    host = [("bench.window", 0, 1000), ("bench.loader", 400, 600),
            ("bench.sync", 700, 1000), ("bench.step", 0, 1000)]
    out = tr.reduce(dev, host)
    assert out["busy_s"] == pytest.approx(400e-9)
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["device_ops"] == [["fusion.1", 300e-9], ["dot.2", 150e-9]]
    assert out["idle_gaps"] == [["bench.sync", 300e-9],
                                ["bench.loader", 200e-9],
                                ["bench.step", 100e-9]]


def test_busy_is_averaged_over_devices_and_clipped_to_the_window():
    dev = {"/device:TPU:0": [("a", 0, 600)], "/device:TPU:1": [("a", 200, 400)]}
    out = tr.reduce(dev, [("bench.window", 100, 500)])
    assert out["busy_s"] == pytest.approx((400 + 200) / 2 * 1e-9)


def test_hlo_text_names_shorten_to_op_and_opcode():
    assert tr.op_name("%copy.715 = f32[4,8]{1,0:T(8,128)} copy(f32[4,8]{0,1} "
                      "%fusion.802)") == "copy.715 (copy)"
    assert tr.op_name("%fusion.3 = (bf16[4]{0}, bf16[4]{0}) fusion(bf16[4]"
                      "{0} %p), kind=kLoop") == "fusion.3 (fusion)"
    assert tr.op_name("jit_step") == "jit_step"


def test_device_planes_are_tpus_only():
    assert tr.DEVICE_PLANE.match("/device:TPU:3")
    assert not tr.DEVICE_PLANE.match("/host:CPU")
    assert tr.OPS_LINE.match("XLA Ops") and not tr.OPS_LINE.match("Steps")


def test_a_recorded_trace(tmp_path, cpu_trace):
    from chipbench import harness
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    with tr.capture(tmp_path / "t"):
        for _ in range(3):
            with tr.span("dispatch"):
                y = f(x)
            with tr.span("sync"):
                y.block_until_ready()
    out = harness.reduce_trace(tmp_path / "t")
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and out["idle_gaps"]
    assert not (tmp_path / "t").exists()
