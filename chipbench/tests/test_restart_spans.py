"""``tools/restart_spans.py`` at the tiny size on the CPU: every restart
gets its table of program spans, and the traced restart's idle gaps are
named by program spans where one covers them."""

import json
import time

import jax

from chipbench import harness
from chipbench import trace as tr
from chipbench.tests.conftest import ROOT, TINY_TRAFFIC, tiny_config
from chipbench.tools import restart_spans

WORKLOAD = restart_spans.WORKLOAD


def test_name_gap_prefers_an_inner_program_span(monkeypatch):
    restart_spans.name_program_spans(monkeypatch.setattr)
    host = [("bench.window", 0, 1000), ("bench.train_loop", 100, 900),
            ("repro.train.warmup.step", 300, 600),
            ("other.span", 350, 450)]
    assert tr.name_gap((400, 500), [h for h in host
                                    if h[0].startswith(tr.SPAN_PREFIX)]) \
        == "repro.train.warmup.step"
    assert tr.name_gap((700, 800), host) == "bench.train_loop"


def test_every_restart_gets_its_span_table(monkeypatch, cpu_trace):
    from repro.core.profiler import watch_compiles
    watch_compiles()
    restart_spans.name_program_spans(monkeypatch.setattr)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = harness.load_json(harness.HERE / "traffic"
                                / "train-restart.json")
    traffic.update(TINY_TRAFFIC["train-restart"])
    limits = harness.load_json(ROOT / "chipbench" / "tests"
                               / "limits_tiny.json")[WORKLOAD]
    jax.clear_caches()
    ctx = harness.Context(bench, WORKLOAD, 2**31 + 77, 1.0, True,
                          jax.devices()[:1], time.perf_counter(),
                          config=tiny_config("mamba2-370m"),
                          traffic=traffic, limits=limits)
    got = restart_spans.run(ctx)
    out, restarts = got["result"], got["restarts"]
    assert out["correct"], out["checks"]
    labels = [r["label"] for r in restarts]
    assert labels[0] == "setup" and labels[-1] == "traced"
    assert len(labels) == out["attempted"] + 2
    for r in restarts:
        names = {row[0]: row for row in r["spans"]}
        assert names["train.restore"][2] == 1
        assert names["train.step"][2] == 1
        assert names["ckpt.wave.opt"][1] == "train.restore"
        assert 0 < r["restore_params_s"] < r["seconds"]
        assert r["warmup_s"] > 0
    assert restarts[0]["compiles_uncached"] > 0
    gaps = [n for n, _ in out["breakdown"]["idle_gaps"]]
    assert any(n.startswith("repro.") for n in gaps), gaps
