"""End-to-end behaviour of the BootSeer runtime with REAL I/O (deliverable
c integration tests): baseline vs optimized startups reproduce the paper's
qualitative claims at laptop scale."""

import time

import numpy as np
import pytest

from repro.blockstore.image import build_image
from repro.blockstore.registry import Registry
from repro.ckpt.checkpoint import Checkpointer
from repro.core.bootseer import BootseerRuntime, JobSpec
from repro.core.profiler import SPANS
from repro.core.stages import Stage
from repro.dfs.hdfs import HdfsCluster, ThrottleModel
from repro.dfs.striped import StripeMissingError

BS = 64 * 1024


@pytest.fixture()
def env(tmp_path, rng):
    src = tmp_path / "src"
    (src / "bin").mkdir(parents=True)
    (src / "bin" / "start").write_bytes(
        rng.integers(0, 256, 6 * BS, dtype=np.uint8).tobytes())
    (src / "weights.ref").write_bytes(
        rng.integers(0, 256, 20 * BS, dtype=np.uint8).tobytes())
    # throttled registry: lazy faulting is slow, prefetch+p2p isn't
    reg = Registry(tmp_path / "reg",
                   throttle=ThrottleModel(bandwidth=5e8, throttle_after=2,
                                          timescale=2e-3))
    build_image(src, reg, "img", block_size=BS)
    hdfs = HdfsCluster(tmp_path / "hdfs", num_groups=8, block_size=1 << 20)
    ck = Checkpointer(hdfs, striped=True, width=8)
    params = {"w": np.arange(64 * 4096, dtype=np.float32).reshape(64, -1)}
    ck.save(100, params)
    return tmp_path, reg, hdfs, ck


def _spec(n=3):
    def env_setup(target, rank):
        time.sleep(0.15)  # the "pip install" work the cache skips
        for i in range(6):
            (target / f"dep{i}.py").write_text(f"x={i}")
    return JobSpec(
        job_id="trainjob", image="img", num_nodes=n,
        job_params={"deps": ["a==1"], "gpu": "H800"},
        startup_reads=[("bin/start", 0, -1)],
        env_setup=env_setup, resume_step=100, resume_plan="rows")


def test_baseline_vs_bootseer_startup(env, tmp_path):
    """Warm-restart wins asserted on SCHEDULER-COUNTED work and recorded
    orderings, not wall-clock ratios: on 2-CPU CI runners the GIL convoy
    makes elapsed-time comparisons flaky (see the slow-marked
    test_warm_restart_beats_baseline_walltime for the wall-clock form)."""
    _, reg, hdfs, ck = env
    installs = []

    def startup(rt):
        snap = SPANS.snapshot()
        res = rt.run_startup(_spec(), checkpointer=ck)
        ran = SPANS.since(snap).get("startup.env.install.ran")
        installs.append(ran.count if ran else 0)
        return res

    base_rt = BootseerRuntime(registry=reg, hdfs=hdfs,
                              workdir=tmp_path / "wb", optimize=False)
    rb = startup(base_rt)

    opt_rt = BootseerRuntime(registry=reg, hdfs=hdfs,
                             workdir=tmp_path / "wo", optimize=True)
    r1 = startup(opt_rt)   # record run
    r2 = startup(opt_rt)   # warm restart

    # the record run must NOT claim it prefetched (it created the record;
    # regression test for the note once re-querying has_record after the
    # record-phase upload) — the warm restart must
    assert rb.notes["prefetch_used"] is False
    assert r1.notes["prefetch_used"] is False
    assert r2.notes["prefetch_used"] is True

    # warm restart replaced the install sleep with a counted cache
    # restore: one DFS archive fetch (singleflight), the other two nodes
    # hit the node-local archive cache
    assert opt_rt.env_cache.stats["dfs_archive_fetches"] == 1
    assert opt_rt.env_cache.stats["local_cache_hits"] == 2

    # install ran on every baseline/record node, on NO warm node: the
    # env.install task degenerates to the restored-cache check (counted
    # where the install commands run, not timed)
    assert installs == [3, 3, 0]

    # scheduler-counted I/O: critical-path DFS bytes flowed (env archive
    # windows + params-wave preads), and the warm restart added ZERO
    # critical registry bytes over the record run (the per-job block
    # cache survived the restart; snapshots are cumulative per runtime)
    sched = r2.notes["io_sched"]
    assert sched["dfs"]["bytes"]["critical"] > 0
    assert sched["registry"]["bytes"]["critical"] == \
        r1.notes["io_sched"]["registry"]["bytes"]["critical"]

    # stage ordering on every node: startup stages all precede TRAINING,
    # and within the record run install follows the image (its DAG edge)
    for res in (rb, r1, r2):
        assert len(res.node_stage_s) == 3
        for node_stages in res.node_stage_s.values():
            for st in (Stage.IMAGE_LOAD, Stage.ENV_SETUP, Stage.MODEL_INIT):
                assert st.value in node_stages
    for attr in r1.notes["critical_path"].values():
        assert attr["tasks"]["env.install"]["start"] >= \
            attr["tasks"]["image.startup_reads"]["end"] - 1e-6

    # per-node TRAINING readiness is the max over recorded chains; the
    # single pre-TRAINING event is the max over nodes
    slowest = max(a["train_ready_s"]
                  for a in r2.notes["critical_path"].values())
    assert r2.total_s >= slowest - 1e-6


@pytest.mark.slow
def test_warm_restart_beats_baseline_walltime(env, tmp_path):
    """The wall-clock form of the claim above — meaningful on unloaded
    boxes, flaky under CI GIL convoys, hence slow-marked."""
    _, reg, hdfs, ck = env
    base_rt = BootseerRuntime(registry=reg, hdfs=hdfs,
                              workdir=tmp_path / "wb", optimize=False)
    rb = base_rt.run_startup(_spec(), checkpointer=ck)
    opt_rt = BootseerRuntime(registry=reg, hdfs=hdfs,
                             workdir=tmp_path / "wo", optimize=True)
    opt_rt.run_startup(_spec(), checkpointer=ck)        # record run
    r2 = opt_rt.run_startup(_spec(), checkpointer=ck)   # warm restart

    def stage_max(res, stage):
        return max(d.get(stage.value, 0.0)
                   for d in res.node_stage_s.values())

    # env WORK (restore + degenerate install), not the stage span: under
    # the pipelined schedule the ENV_SETUP span absorbs the wait for the
    # image edge, so spans aren't comparable across schedules
    warm_env_work = max(
        a["tasks"]["env.restore"]["s"] + a["tasks"]["env.install"]["s"]
        for a in r2.notes["critical_path"].values())
    assert warm_env_work < stage_max(rb, Stage.ENV_SETUP)
    assert r2.total_s < rb.total_s


def test_deferred_opt_wave_failure_surfaces(env, tmp_path):
    """A stripe file lost between the params wave and the deferred
    optimizer-state wave must fail loudly via drain_deferred(), not
    vanish into the background pool."""
    _, reg, hdfs, ck = env
    params = {"w": np.arange(256 * 1024, dtype=np.float32).reshape(256, -1)}
    opt = {"mu": {"w": np.ones((1024, 1024), np.float32)},
           "nu": {"w": np.ones((1024, 1024), np.float32)}}
    ck.save(200, params, opt)         # 9 MiB: wave 1 reaches stripe file 2
    files = hdfs.attrs(ck.data_path(200))["striped"]["files"]
    group, name = files[2]            # holds optimizer-state bytes only
    (hdfs.root / f"group{group:02d}" / name).unlink()

    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=tmp_path / "wd",
                         optimize=True)
    spec = JobSpec(**{**_spec().__dict__, "resume_step": 200})
    rt.run_startup(spec, checkpointer=ck)    # params wave reads fine
    with pytest.raises(StripeMissingError):
        rt.drain_deferred()


def test_warm_restart_hands_dag_bytes_to_the_loop(env, tmp_path, rules):
    """run_startup then train_loop(resume_from=...): the loop's planned
    restore takes every byte from the startup DAG's staged waves (the
    optimizer wave possibly still in flight) and reads nothing from the
    DFS but its manifest."""
    import jax
    from repro.configs import get_tiny
    from repro.core.pipeline import IOScheduler
    from repro.models.model import Model
    from repro.optim.adamw import adamw_init
    from repro.train.loop import train_loop
    _, reg, hdfs, ck = env
    model = Model(get_tiny("mamba2-370m"), rules)
    params = model.init(jax.random.key(0))
    ck.save(300, params, adamw_init(params))
    total = ck.load_index(300).total_bytes
    manifest = len(hdfs.read(ck.index_path(300)))

    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=tmp_path / "w",
                         optimize=True)
    spec = JobSpec(**{**_spec().__dict__, "resume_step": 300})
    rt.run_startup(spec, checkpointer=ck)
    snap = SPANS.snapshot()
    sched = IOScheduler()
    warm, _, hist = train_loop(model, batch=2, seq_len=16, steps=1,
                               log_fn=lambda *_: None, checkpointer=ck,
                               resume_from=300, restore_sched=sched)
    rows = SPANS.since(snap)
    rt.drain_deferred()
    rt.close()
    assert rows["ckpt.handoff.hit_bytes"].count == total
    assert "ckpt.handoff.miss_bytes" not in rows
    assert sum(sched.snapshot()["dfs"]["bytes"].values()) == manifest
    # the same step from a cold restore (nothing staged) trains the same
    cold, _, cold_hist = train_loop(
        model, batch=2, seq_len=16, steps=1, log_fn=lambda *_: None,
        checkpointer=Checkpointer(hdfs, striped=True, width=8),
        resume_from=300)
    assert hist == cold_hist
    for a, b in zip(jax.tree.leaves(warm), jax.tree.leaves(cold)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unoptimized_startup_stages_nothing(env, tmp_path):
    _, reg, hdfs, ck = env
    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=tmp_path / "w",
                         optimize=False)
    rt.run_startup(_spec(), checkpointer=ck)
    rt.close()
    assert ck.handoff.lookup(100, ck.load_index(100)) is None
    snap = SPANS.snapshot()
    (got,) = ck.restore_planned(
        100, {"w": np.zeros((64, 4096), np.float32)})
    rows = SPANS.since(snap)
    assert "ckpt.handoff.hit_bytes" not in rows
    assert rows["ckpt.handoff.miss_bytes"].count == 64 * 4096 * 4
    np.testing.assert_array_equal(
        got["w"], np.arange(64 * 4096, dtype=np.float32).reshape(64, -1))


def test_hot_record_created_once(env, tmp_path):
    _, reg, hdfs, ck = env
    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=tmp_path / "w",
                         optimize=True)
    man = reg.get_manifest("img")
    assert not rt.hot_service.has_record(man.digest)
    rt.run_startup(_spec(), checkpointer=ck)
    assert rt.hot_service.has_record(man.digest)
    hot = rt.hot_service.hot_blocks(man.digest)
    assert 0 < len(hot) <= len(man.unique_blocks)


def test_analysis_service_accumulates_runs(env, tmp_path):
    _, reg, hdfs, ck = env
    rt = BootseerRuntime(registry=reg, hdfs=hdfs, workdir=tmp_path / "w",
                         optimize=True)
    rt.run_startup(_spec(), checkpointer=ck)
    rt.run_startup(_spec(), checkpointer=ck)
    assert len(rt.analysis.jobs()) == 2  # one job tag per startup
