"""Helpers of the benchmark's own CPU tests (run by explicit path:
``python -m pytest chipbench/tests``).  They drive the harness at the
registry's tiny sizes, with the harness's look for a chip skipped."""

from __future__ import annotations

import json
import re
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY_SSM = {"state_dim": 16, "head_dim": 32, "expand": 2, "conv_width": 4,
            "chunk_size": 32, "ngroups": 1}
TINY_MODELS = {
    "mamba2-370m": {"num_layers": 2, "d_model": 256, "vocab_size": 512,
                    "ssm": TINY_SSM},
}
TINY_TRAFFIC = {
    "train-steady": {"batch": 2, "seq_len": 64, "trace_steps": 2},
    "train-restart": {"batch": 2, "seq_len": 64},
}


def tiny_config(name: str) -> dict:
    from chipbench import harness
    doc = harness.load_json(harness.HERE / "configs" / f"{name}.json")
    doc["model"].update(TINY_MODELS[name])
    return doc


@pytest.fixture
def cpu_trace(monkeypatch):
    """Read the CPU client's threads as the 'device' of a trace, and give
    the CPU peaks, so that a traced run completes here."""
    from chipbench import harness
    from chipbench import trace as tr
    monkeypatch.setattr(tr, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setattr(tr, "OPS_LINE", re.compile(r"^tf_XLAPjRtCpuClient"))
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def run_tiny(workload: str, *, seed: int = 2**31 + 12345,
             seconds: float = 2.0, trace: bool = False,
             fault: str | None = None) -> dict:
    """Drive one cell end to end at the tiny size on the CPU, against the
    tiny size's limits (``limits_tiny.json``), with ``fault`` (see
    ``faults.py``) planted under its timed path if given; returns its
    result line."""
    import contextlib

    import jax
    from chipbench import faults, harness
    from chipbench.run import run_driver
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    traffic = harness.load_json(harness.HERE / "traffic"
                                / f"{cell['traffic']}.json")
    traffic.update(TINY_TRAFFIC[cell["traffic"]])
    limits = harness.load_json(Path(__file__).parent / "limits_tiny.json")
    ctx = harness.Context(bench, workload, seed, seconds, trace,
                          jax.devices()[:1], time.perf_counter(),
                          config=tiny_config(cell["config"]),
                          traffic=traffic, limits=limits[workload])
    with (faults.planted(fault, ctx) if fault
          else contextlib.nullcontext()):
        return harness.result(ctx, run_driver(ctx))
