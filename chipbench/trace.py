"""Reduction of a profiler trace to device busy time, idle share, the
device operations that took most time, and the longest idle gaps named by
the benchmark's host span around them.

``capture(dir)`` wraps a stretch of the run in ``jax.profiler.trace``;
``events(dir)`` reads the ``.xplane.pb`` it wrote with JAX alone;
``reduce(...)`` is plain arithmetic on (name, start_ns, end_ns) tuples.
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation`` names,
which start with ``bench.``; the window is the ``bench.window`` span.
"""

from __future__ import annotations

import contextlib
import re
import shutil
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = re.compile(r"^XLA Ops$")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
HLO_TEXT = re.compile(r"^%?([^\s=]+) = .*?\s([a-z][\w\-]*)\(")


def op_name(event_name: str) -> str:
    """A device event's short name: an XLA op given as HLO text
    ('%copy.7 = f32[..] copy(...)') becomes 'copy.7 (copy)'."""
    m = HLO_TEXT.match(event_name)
    return f"{m.group(1)} ({m.group(2)})" if m else event_name


@contextlib.contextmanager
def capture(trace_dir: Path):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # host spans are the benchmark's own
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield


def span(name: str):
    """A host span of the benchmark, visible in the trace."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def events(trace_dir: Path, device_plane=None, ops_line=None):
    """({device plane: [(op, start_ns, end_ns)]}, [(span, start, end)],
    {plane: [line names]}) from the newest trace under ``trace_dir``."""
    device_plane = device_plane or DEVICE_PLANE
    ops_line = ops_line or OPS_LINE
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    dev: dict = {}
    host: list = []
    layout: dict = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        on_device = bool(device_plane.match(plane.name))
        for ln in lines:
            ops = on_device and bool(ops_line.match(ln.name))
            for e in ln.events:
                iv = (op_name(e.name) if ops else e.name, e.start_ns,
                      e.start_ns + e.duration_ns)
                if ops:
                    dev.setdefault(plane.name, []).append(iv)
                elif e.name.startswith(SPAN_PREFIX):
                    host.append(iv)
    return dev, host, layout


def union(intervals) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def name_gap(gap, spans) -> str:
    """The innermost benchmark span that covers the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    around = [(e - s, n) for n, s, e in spans
              if s <= mid <= e and n != WINDOW_SPAN]
    return min(around)[1] if around else "unattributed"


def reduce(dev: dict, host: list, top: int = 10) -> dict:
    """busy_s (mean over devices), window_s, device_ops and idle_gaps."""
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        every = [t for evs in dev.values() for _, s, e in evs for t in (s, e)]
        if not every:
            return {}
        lo, hi = min(every), max(every)
    busy_each, per_op = [], {}
    gaps: list = []
    for i, (plane, evs) in enumerate(sorted(dev.items())):
        busy = clip(union((s, e) for _, s, e in evs), lo, hi)
        busy_each.append(sum(e - s for s, e in busy))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0) + d
        if i == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    if not busy_each:
        return {}
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy_each) / len(busy_each) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, d / 1e9] for n, d in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_gap(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }
