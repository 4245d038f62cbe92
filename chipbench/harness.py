"""What every cell shares: the cell's files found by name, the compile
clock, the device, the peaks, the result line and the correctness line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files:

    configs/<config>.json        sizes as run, source, departures, assumed
    traffic/<traffic>.json       the mix's parameters; "kind" names a driver
    drivers/<kind>.py            the general generator and window of a kind
    limits/<workload>.json       the limit of each number ``correct`` compares
    reference/<family>.py        the plain float32 model
    cost/<config>.py             FLOPs and bytes from the published shapes
    metrics/<metric>.py          one reader per per-layer metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a file of the benchmark by its path (its name may hold
    dots and dashes)."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(HERE)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Seconds XLA spent compiling and reading the persistent cache, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.retrieve_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += duration
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.compiles,
                "retrieve_s": self.retrieve_s, "cache_hits": self.cache_hits}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


def peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def program_config(config: dict):
    """The program's ModelConfig, built from a configuration file's sizes
    (the file is the configuration as run)."""
    from repro.configs import get_config
    from repro.configs.base import SSMConfig
    m = dict(config["model"])
    if "ssm" in m:
        m["ssm"] = SSMConfig(**m["ssm"])
    return dataclasses.replace(get_config(config["arch"]), **m)


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: |prog - ref| / max(ref, median ref leaf)} over the leaves
    named in ``keep`` (all when None)."""
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - r) / max(r, med) for k, r in ref.items()
            if keep is None or k in keep}


class Context:
    """One run of one cell: what its driver is given."""

    def __init__(self, bench: dict, workload: str, seed: int, seconds: float,
                 trace: bool, devices, t_start: float, *, config=None,
                 traffic=None, limits=None):
        cell = next((w for w in bench["workloads"] if w["name"] == workload),
                    None)
        if cell is None:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.bench = bench
        self.workload = workload
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == cell["config"])
        self.config = config or load_json(ROOT / cfg_entry["file"])
        self.traffic = traffic or load_json(
            HERE / "traffic" / f"{cell['traffic']}.json")
        self.limits = limits if limits is not None else load_json(
            HERE / "limits" / f"{workload}.json")
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.setup_s = None
        self.clock = CompileClock()
        self.workdir = ROOT / ".chipbench" / workload
        self.reference = load_module(
            HERE / "reference" / f"{self.config['family']}.py")
        self.cost = load_module(HERE / "cost" / f"{self.config['name']}.py")

    # ----- what drivers/<kind>.py calls -----

    def program_config(self):
        return program_config(self.config)

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        log(f"setup: {self.setup_s:.3f} s")

    def peak(self) -> dict:
        return peaks(self.devices[0].device_kind)

    def checks(self, readings: dict) -> list:
        """The numbers ``limits/<workload>.json`` names, each against its
        limit."""
        return [check(k, readings[k], v["limit"])
                for k, v in self.limits.items()]

    def memory_peak(self) -> int:
        return memory_peak_bytes(self.devices)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, value: float, limit: float) -> dict:
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def reduce_trace(trace_dir: Path) -> dict:
    """Reduce the trace under ``trace_dir``, print its layout, and remove
    it (traces are large and the run keeps only the reduction)."""
    import shutil
    from chipbench import trace as tr
    dev, host, layout = tr.events(trace_dir)
    print("trace planes: " + "; ".join(
        f"{p}: {', '.join(ls[:8])}" for p, ls in layout.items()
        if tr.DEVICE_PLANE.match(p)), file=sys.stderr)
    out = tr.reduce(dev, host)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not out or out["busy_s"] <= 0:
        raise RuntimeError(f"the trace under {trace_dir} holds no device "
                           f"operation (planes: {sorted(layout)})")
    return out


def result(ctx: Context, rec: dict) -> dict:
    """The run's last line (see BENCHMARK.json for the metrics)."""
    bench, wl = ctx.bench, ctx.workload
    metrics = {}
    if not ctx.trace:
        values = dict(rec["e2e"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if applies(m, wl):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        rec = dict(rec, peak=ctx.peak())
        for m in bench["per_layer"]:
            if not applies(m, wl):
                continue
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": all(c["ok"] for c in rec["checks"])
           and rec["failed"] == 0,
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if ctx.trace and rec.get("trace"):
        t = rec["trace"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in rec["checks"]}
    return out
