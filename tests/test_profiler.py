"""Profiling system (§4.1): log emission, parsing, stage analytics, and
the in-process spans and counters on the profiler's clock."""

import math
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro.core import profiler
from repro.core.profiler import (SPANS, SpanRow, SpanTotals,
                                 StageAnalysisService, StageLogger, count,
                                 current, parse_log, span,
                                 uncached_compiles, watch_compiles)
from repro.core.stages import GPU_CONSUMING, STAGE_ORDER, Stage
from repro.core.straggler import barrier_cost, max_median_ratio, tail_summary


def _fake_clock(times):
    it = iter(times)
    return lambda: next(it)


class TestLoggerAndParser:
    def test_roundtrip(self):
        log = StageLogger("jobA", "node0",
                          clock=_fake_clock([1.0, 3.5, 4.0, 9.0]))
        with log.stage(Stage.IMAGE_LOAD):
            pass
        with log.stage(Stage.ENV_SETUP):
            pass
        events = parse_log(log.lines())
        assert len(events) == 4
        assert events[0].stage == "image_load" and events[0].ev == "BEGIN"
        assert events[1].ts == 3.5

    def test_parser_ignores_noise(self):
        text = ("random print output\n"
                "BOOTSEER_STAGE ts=2.0 job=j node=n stage=env_setup ev=BEGIN\n"
                "pip install torch... done\n"
                "BOOTSEER_STAGE ts=5.0 job=j node=n stage=env_setup ev=END\n")
        events = parse_log(text)
        assert len(events) == 2


def _service_with_job(durs):
    """durs: {node: {stage: (begin, end)}}"""
    svc = StageAnalysisService()
    for node, stages in durs.items():
        log = StageLogger("job1", node, clock=lambda: 0.0)
        for stage, (b, e) in stages.items():
            log.begin(stage, ts=b)
            log.end(stage, ts=e)
        svc.ingest_log(log.lines())
    return svc


class TestAnalysis:
    def test_node_stage_durations(self):
        svc = _service_with_job({
            "n0": {Stage.IMAGE_LOAD: (0, 10), Stage.ENV_SETUP: (10, 110)},
            "n1": {Stage.IMAGE_LOAD: (0, 30), Stage.ENV_SETUP: (30, 90)},
        })
        d = svc.node_stage_durations("job1")
        assert d["n0"]["image_load"] == 10
        assert d["n1"]["env_setup"] == 60

    def test_node_vs_job_level(self):
        """Job-level includes the straggler wait; node-level does not."""
        svc = _service_with_job({
            "n0": {Stage.IMAGE_LOAD: (0, 10), Stage.TRAINING: (40, 41)},
            "n1": {Stage.IMAGE_LOAD: (0, 40), Stage.TRAINING: (40, 41)},
        })
        node = svc.node_level_overhead("job1")
        assert node["n0"] < node["n1"]
        job = svc.job_level_overhead("job1")
        assert job == 40.0  # first submit -> last training begin

    def test_max_median_ratio(self):
        svc = _service_with_job({
            f"n{i}": {Stage.ENV_SETUP: (0, 60)} for i in range(9)
        } | {"slow": {Stage.ENV_SETUP: (0, 92)}})
        r = svc.max_median_ratio("job1", Stage.ENV_SETUP)
        assert math.isclose(r, 92 / 60)

    def test_stage_stats(self):
        svc = _service_with_job({
            "n0": {Stage.MODEL_INIT: (0, 100)},
            "n1": {Stage.MODEL_INIT: (0, 200)},
        })
        st = svc.stage_stats("job1")["model_init"]
        assert st["min"] == 100 and st["max"] == 200 and st["mean"] == 150

    def test_save_load(self, tmp_path):
        svc = _service_with_job({"n0": {Stage.IMAGE_LOAD: (0, 5)}})
        svc.save(tmp_path / "r.json")
        svc2 = StageAnalysisService.load(tmp_path / "r.json")
        assert svc2.node_stage_durations("job1")["n0"]["image_load"] == 5


class TestStages:
    def test_order_and_sets(self):
        assert STAGE_ORDER[0] is Stage.RESOURCE_QUEUE
        assert STAGE_ORDER[-1] is Stage.TRAINING
        assert Stage.ENV_SETUP in GPU_CONSUMING
        assert Stage.RESOURCE_QUEUE not in GPU_CONSUMING


class TestStragglerMetrics:
    def test_tail_summary(self):
        xs = [60.0] * 99 + [92.0]
        t = tail_summary(xs)
        assert t["p50"] == 60 and t["max"] == 92
        assert 0 < t["tail_fraction_over_1p5x_median"] <= 0.01

    def test_barrier_cost(self):
        assert barrier_cost([10, 10, 40]) == 60.0

    def test_max_median(self):
        assert max_median_ratio([1, 1, 4]) == 4.0


class TestSpans:
    def test_nested_self_time_is_total_less_children(self):
        snap = SPANS.snapshot()
        with span("t.outer"):
            time.sleep(0.01)
            for _ in range(2):
                with span("t.inner"):
                    time.sleep(0.005)
        rows = SPANS.since(snap)
        outer, inner = rows["t.outer"], rows["t.inner"]
        assert (outer.count, inner.count) == (1, 2)
        assert inner.parent == "t.outer" and outer.parent is None
        assert outer.self_s == pytest.approx(outer.total_s - inner.total_s)
        assert inner.self_s == pytest.approx(inner.total_s)
        assert outer.total_s >= inner.total_s + 0.01
        assert current() is None

    def test_pool_thread_span_takes_the_submitters_parent(self):
        snap = SPANS.snapshot()
        with ThreadPoolExecutor(1) as pool:
            with span("t.submit"):
                parent = current()

                def work():
                    with span("t.pooled", parent=parent):
                        with span("t.pooled_inner"):
                            time.sleep(0.01)
                fut = pool.submit(work)
                fut.result()
        rows = SPANS.since(snap)
        assert rows["t.pooled"].parent == "t.submit"
        assert rows["t.pooled_inner"].parent == "t.pooled"
        # work on another thread is not taken from the submitter's self
        assert rows["t.submit"].self_s == pytest.approx(
            rows["t.submit"].total_s)
        assert rows["t.pooled"].self_s == pytest.approx(
            rows["t.pooled"].total_s - rows["t.pooled_inner"].total_s)

    def test_concurrent_spans_lose_no_update(self):
        import sys
        threads, each = 16, 200
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            snap = SPANS.snapshot()

            def work():
                for _ in range(each):
                    with span("t.stress"):
                        count("t.stress_hits")
            with ThreadPoolExecutor(threads) as pool:
                futs = [pool.submit(work) for _ in range(threads)]
                for f in futs:
                    f.result(timeout=60)
        finally:
            sys.setswitchinterval(old)
        rows = SPANS.since(snap)
        assert rows["t.stress"].count == threads * each
        assert rows["t.stress_hits"].count == threads * each
        assert rows["t.stress_hits"].parent == "t.stress"

    def test_since_subtracts_and_drops_unchanged_names(self):
        store = SpanTotals()
        store.add("a", 1, 2.0, 1.5, None)
        store.add("b", 3)
        snap = store.snapshot()
        store.add("a", 2, 1.0, 0.5, "p")
        assert store.since(snap) == {"a": SpanRow(2, 1.0, 0.5, "p")}
        assert store.since({}) == {"a": SpanRow(3, 3.0, 2.0, "p"),
                                   "b": SpanRow(3, 0.0, 0.0, None)}

    def test_counters_and_uncached_compiles(self):
        snap = SPANS.snapshot()
        with span("t.counting"):
            count("t.hits", 2)
        count("t.hits")
        rows = SPANS.since(snap)
        assert rows["t.hits"].count == 3 and rows["t.hits"].total_s == 0
        assert uncached_compiles({
            "compile.jit_a": SpanRow(3, 1.0, 1.0, None),
            "compile_cached.jit_a": SpanRow(1, 0.0, 0.0, None),
            "compile.jit_b": SpanRow(1, 1.0, 1.0, None),
            "compile_cached.jit_b": SpanRow(1, 0.0, 0.0, None),
        }) == {"jit_a": 2}

    def test_stage_logger_opens_a_span_and_keeps_its_lines(self):
        snap = SPANS.snapshot()
        log = StageLogger("jobA", "node0", clock=_fake_clock([1.0, 2.5]))
        with log.stage(Stage.ENV_SETUP):
            pass
        assert [e.ts for e in parse_log(log.lines())] == [1.0, 2.5]
        assert SPANS.since(snap)["stage.env_setup"].count == 1

    def test_compiles_are_counted_by_program(self):
        import jax
        import jax.numpy as jnp
        watch_compiles()
        watch_compiles()        # idempotent: one listener
        snap = SPANS.snapshot()
        with span("t.compiling"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        rows = SPANS.since(snap)
        named = [n for n in rows if n.startswith("compile.")]
        assert any("lambda" in n for n in named), named
        assert all(rows[n].parent == "t.compiling" for n in named)
        assert rows["t.compiling"].self_s == pytest.approx(
            rows["t.compiling"].total_s
            - sum(rows[n].total_s for n in named))

    def test_span_lands_in_a_profiler_trace(self, tmp_path):
        import jax
        from jax.profiler import ProfileData
        with jax.profiler.trace(str(tmp_path)):
            with span("t.traced"):
                time.sleep(0.01)
        files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        assert files
        names = {e.name for plane in ProfileData.from_file(
                     str(files[-1])).planes
                 for line in plane.lines for e in line.events}
        assert profiler.SPAN_PREFIX + "t.traced" in names


def test_train_loop_resume_records_its_spans(tmp_path, rules):
    """A resumed train_loop through a real Checkpointer records each
    ckpt.* and train.* span the expected number of times, compiles the
    train step once inside ``train.compile`` (no warm-up, no compile or
    cache read inside a ``train.step``), and compiles by program."""
    import jax
    from repro.ckpt.checkpoint import Checkpointer
    from repro.configs import get_tiny
    from repro.dfs.hdfs import HdfsCluster
    from repro.models.model import Model
    from repro.optim.adamw import adamw_init
    from repro.train.loop import train_loop
    watch_compiles()
    model = Model(get_tiny("mamba2-370m"), rules)
    params = model.init(jax.random.key(0))
    ck = Checkpointer(HdfsCluster(tmp_path / "hdfs", num_groups=4,
                                  block_size=1 << 16), width=4)
    ck.save(7, params, adamw_init(params))
    for _ in range(2):
        snap = SPANS.snapshot()
        train_loop(model, batch=2, seq_len=16, steps=3,
                   log_fn=lambda *_: None, checkpointer=ck, resume_from=7)
        rows = SPANS.since(snap)
        once = ["train.restore", "train.place.params", "train.build",
                "train.compile", "train.opt_wait", "train.place.opt",
                "ckpt.plan", "ckpt.wave.params", "ckpt.assemble",
                "ckpt.wave.opt"]
        assert {n: rows[n].count for n in once} == dict.fromkeys(once, 1)
        assert rows["train.step"].count == 3
        assert not [n for n in rows if n.startswith("train.warmup.")]
        assert rows["ckpt.wave.params"].parent == "train.restore"
        assert rows["ckpt.wave.opt"].parent == "train.restore"
        compiles = {n: r for n, r in rows.items()
                    if n.startswith(("compile.", "compile_cached."))}
        assert compiles and all(r.count > 0 for r in compiles.values())
        assert all(r.parent != "train.step" for r in compiles.values())
        step = {n: r for n, r in compiles.items() if "train_step" in n}
        assert step["compile.jit(train_step)"].count == 1
        assert {r.parent for r in step.values()} == {"train.compile"}
        assert rows["train.compile"].self_s < rows["train.compile"].total_s
