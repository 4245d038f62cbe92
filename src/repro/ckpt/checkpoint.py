"""JAX pytree checkpointing over the (striped) DFS — BootSeer §4.4.

Save: flatten the pytree with key paths, serialize leaves into one logical
stream, write via ``StripedWriter`` (parallel across stripe files), store the
``TensorIndex`` manifest alongside.

Restore: read the manifest, derive a sharding-aware *restore plan*
(repro.ckpt.plan) — per-host byte ranges for any sharded dim, coalesced into
batched reads — and execute it with ``pread_many`` (each physical stripe
file opened at most once per wave, bytes landing zero-copy in preallocated
per-tensor buffers).  This keeps resume cost proportional to
``bytes_per_host`` rather than total checkpoint size.  Restores run in two
waves: wave 0 is the first tree (params), wave 1 the remaining trees
(optimizer state), which ``async_tail=True`` streams on a background thread
so the caller can overlap it with model init.

Incremental delta checkpoints (repro.ckpt.delta): ``save_delta`` writes
only the byte ranges that changed since a base snapshot (chunked CRC diff
against the base manifest's hashes — the base data is never re-read), and
restore composes the base + delta chain into one layered reader so a
resume reads each logical range exactly once from the newest layer that
holds it.  The planner, waves and ``pread_many`` batching are identical
for full and delta steps.

Read-once hand-off (repro.ckpt.handoff): the startup DAG's checkpoint
waves stage the bytes they read in ``Checkpointer.handoff``, and
``restore_planned`` serves the step from there, reading from the DFS
only what no staged piece holds.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np

from repro.ckpt.delta import (DEFAULT_DIFF_CHUNK, LayeredReader,
                              build_layer_map, changed_ranges, chunk_crcs)
from repro.ckpt.handoff import HandoffReader, HandoffStore
from repro.ckpt.index import TensorIndex
from repro.ckpt.plan import (RestorePlan, build_restore_plan,
                             dim_slices_for_spec, execute_plan)
from repro.core.pipeline import CRITICAL, DEFERRED
from repro.core.profiler import current, span
from repro.dfs.hdfs import HdfsCluster
from repro.dfs.striped import StripedReader, StripedWriter


# shared async-tail executor: restore_planned used to spawn a fresh
# single-thread ThreadPoolExecutor per call, putting thread creation on
# every resume.  One lazily-created process-wide pool serves all tails;
# it is never shut down (daemon-like, lives for the process).
_TAIL_LOCK = threading.Lock()
_TAIL_POOL: Optional[ThreadPoolExecutor] = None


def _tail_pool() -> ThreadPoolExecutor:
    global _TAIL_POOL
    with _TAIL_LOCK:
        if _TAIL_POOL is None:
            _TAIL_POOL = ThreadPoolExecutor(
                max(2, min(8, os.cpu_count() or 2)),
                thread_name_prefix="ckpt-tail")
        return _TAIL_POOL


def _flat_with_names(tree: Any) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _is_spec_leaf(x: Any) -> bool:
    from jax.sharding import PartitionSpec
    return x is None or isinstance(x, PartitionSpec)


def _flat_specs(spec_tree: Any) -> list[tuple[str, Any]]:
    """Flatten a PartitionSpec tree (None leaves = replicated)."""
    flat = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=_is_spec_leaf)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


class _PlainReader:
    """Range reads over a non-striped checkpoint file, with the same
    ``pread``/``pread_many`` contract as ``StripedReader`` — including
    scheduler metering: with ``sched`` attached, a batch holds one "dfs"
    token at its (per-call overridable) priority for the duration."""

    def __init__(self, hdfs: HdfsCluster, path: str, *, sched=None,
                 priority: int = 0):
        self._hdfs = hdfs
        self._path = path
        self.sched = sched
        self.priority = priority
        # signature parity with StripedReader: no placement, no degraded
        # reads — counters stay zero
        self.stats = {"degraded_reads": 0, "reconstructed_bytes": 0,
                      "reconstruction_read_bytes": 0, "corrupt_chunks": 0}

    def pread(self, off: int, ln: int) -> bytes:
        return self._hdfs.pread(self._path, off, ln)

    def pread_many(self, ranges, into=None, priority=None):
        from repro.dfs.striped import pread_many_fallback
        prio = self.priority if priority is None else priority
        if self.sched is not None:
            nbytes = sum(ln for _, ln in ranges)
            with self.sched.slot("dfs", priority=prio, nbytes=nbytes):
                return pread_many_fallback(self.pread, ranges, into=into)
        return pread_many_fallback(self.pread, ranges, into=into)


class Checkpointer:
    """``placement`` selects the storage-fabric durability strategy for
    saved checkpoints (see repro.fabric.placement): ``"striped"``
    (default, the pre-fabric layout), ``"replicated"``, or
    ``Placement.erasure(m)`` — with erasure, a restore that hits a
    missing/truncated stripe file reconstructs it from parity
    transparently instead of raising ``StripeMissingError``."""

    def __init__(self, hdfs: HdfsCluster, base: str = "/ckpt", *,
                 striped: bool = True, width: int = 8, threads: int = 8,
                 placement=None, chunk: Optional[int] = None,
                 stripe: Optional[int] = None,
                 diff_chunk: int = DEFAULT_DIFF_CHUNK):
        from repro.dfs.striped import CHUNK, STRIPE
        self.hdfs = hdfs
        self.base = base.rstrip("/")
        self.striped = striped
        self.width = width
        self.threads = threads
        self.placement = placement
        # chunk/stripe granularity of the striped layout — smaller values
        # spread small checkpoints across all ``width`` files (readers
        # pick the geometry up from the file attrs, no knob needed there)
        self.chunk = chunk or CHUNK
        self.stripe = stripe or STRIPE
        # granularity of the save_delta CRC diff; every full save records
        # per-tensor chunk hashes at this size so it can serve as a base
        self.diff_chunk = diff_chunk
        # bytes of one resume step that the startup DAG read, handed to
        # the restore that follows it so they cross the DFS once
        self.handoff = HandoffStore()

    # ----- paths -----

    def data_path(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}.data"

    def delta_data_path(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}.delta"

    def index_path(self, step: int) -> str:
        return f"{self.base}/step_{step:08d}.index.json"

    def steps(self) -> list[int]:
        """Restorable steps, ascending.  A manifest only counts when its
        ``step_NNN`` stem parses AND its data file (``.data``, or
        ``.delta`` for delta steps) exists — foreign ``*.index.json``
        files no longer crash the listing, and a torn save (index written,
        data missing / garbage-collected) is not advertised as a resume
        candidate."""
        out = []
        for p in self.hdfs.listdir(self.base):
            name = p.rsplit("/", 1)[-1]
            if not (name.startswith("step_")
                    and name.endswith(".index.json")):
                continue
            stem = name[len("step_"):-len(".index.json")]
            if not stem.isdigit():
                continue
            step = int(stem)
            if (self.hdfs.exists(self.data_path(step))
                    or self.hdfs.exists(self.delta_data_path(step))):
                out.append(step)
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    # ----- save -----

    def _index_trees(self, step: int, trees: tuple,
                     meta: Optional[dict]) -> tuple[TensorIndex,
                                                    list[bytes]]:
        """Build the manifest for ``trees`` (chunk hashes included) and
        return it with the per-tensor payloads in stream order."""
        index = TensorIndex(meta=dict(meta or {}, step=step,
                                      n_trees=len(trees)))
        index.hash_chunk = self.diff_chunk
        payloads: list[bytes] = []
        for ti, tree in enumerate(trees):
            for name, leaf in _flat_with_names(tree):
                arr = np.asarray(leaf)
                if arr.dtype == jax.numpy.bfloat16:
                    arr = arr.view(np.uint16)  # store bf16 bit pattern
                    e = index.add(f"t{ti}{name}#bf16", arr.dtype, arr.shape)
                else:
                    e = index.add(f"t{ti}{name}", arr.dtype, arr.shape)
                data = arr.tobytes()
                index.chunk_hashes[e.name] = chunk_crcs(data, self.diff_chunk)
                payloads.append(data)
        return index, payloads

    def _write_stream(self, path: str, blobs: list[bytes]):
        if not any(len(b) for b in blobs):
            # empty stream (e.g. a no-op delta): plain zero-byte file —
            # the striped layout has no zero-size geometry
            self.hdfs.write(path, b"")
            return
        if self.striped:
            with StripedWriter(self.hdfs, path, width=self.width,
                               threads=self.threads,
                               placement=self.placement, chunk=self.chunk,
                               stripe=self.stripe) as w:
                for blob in blobs:
                    w.write(blob)
        else:
            self.hdfs.write(path, b"".join(blobs))

    def save(self, step: int, *trees: Any, meta: Optional[dict] = None) -> TensorIndex:
        self.handoff.drop(step=step)
        index, payloads = self._index_trees(step, trees, meta)
        self._write_stream(self.data_path(step), payloads)
        self.hdfs.write(self.index_path(step), index.to_json().encode())
        return index

    def save_delta(self, step: int, *trees: Any, base: Optional[int] = None,
                   meta: Optional[dict] = None) -> TensorIndex:
        """Incremental save: write only the byte ranges of ``trees`` that
        changed since step ``base`` (default: the latest restorable step),
        found by diffing chunk CRCs against the base manifest — the base
        data itself is never read.  ``trees`` must be congruent to the
        base's (same names, dtypes, shapes ⇒ same logical layout); the
        delta manifest carries this step's own chunk hashes, so deltas
        chain: each save diffs against its immediate predecessor.
        """
        if base is None:
            base = self.latest_step()
            if base is None:
                raise ValueError(
                    "save_delta: no base snapshot to diff against — write "
                    "a full save() first")
        self.handoff.drop(step=step)
        base_index = self.load_index(base)
        if base_index.hash_chunk is None:
            raise ValueError(
                f"save_delta: base step {base} has no chunk hashes "
                "(pre-delta checkpoint) — re-save it full first")
        index, payloads = self._index_trees(step, trees, meta)
        index.hash_chunk = base_index.hash_chunk
        mine = [(e.name, e.dtype, e.shape, e.offset)
                for e in index.entries_by_offset()]
        theirs = [(e.name, e.dtype, e.shape, e.offset)
                  for e in base_index.entries_by_offset()]
        if mine != theirs:
            raise ValueError(
                f"save_delta: trees are not congruent to base step {base} "
                "(names/dtypes/shapes must match) — write a full save() "
                "instead")
        if base_index.hash_chunk != self.diff_chunk:
            # re-hash at the base's granularity so the diff is meaningful
            index.chunk_hashes = {
                e.name: chunk_crcs(data, base_index.hash_chunk)
                for e, data in zip(index.entries_by_offset(), payloads)}
        ranges: list[tuple[int, int, int]] = []   # (logical, len, delta_off)
        blobs: list[bytes] = []
        delta_off = 0
        for e, data in zip(index.entries_by_offset(), payloads):
            old = base_index.chunk_hashes.get(e.name, [])
            for off, ln in changed_ranges(data, old, index.hash_chunk,
                                          e.offset):
                rel = off - e.offset
                ranges.append((off, ln, delta_off))
                blobs.append(data[rel:rel + ln])
                delta_off += ln
        index.delta = {"base_step": int(base), "ranges": ranges,
                       "data_bytes": delta_off}
        self._write_stream(self.delta_data_path(step), blobs)
        self.hdfs.write(self.index_path(step), index.to_json().encode())
        return index

    # ----- restore -----

    def load_index(self, step: int, *, sched=None,
                   priority: int = CRITICAL) -> TensorIndex:
        """Read the manifest for ``step``.  With ``sched`` the read runs
        under a "dfs" slot token (it gates every restore, so it competes
        for DFS capacity like any other startup read) and its bytes land
        in the scheduler's per-priority counters."""
        if sched is None:
            raw = self.hdfs.read(self.index_path(step))
        else:
            with sched.slot("dfs", priority=priority):
                raw = self.hdfs.read(self.index_path(step))
            sched.account("dfs", priority, len(raw))
        return TensorIndex.from_json(raw.decode())

    def _file_reader(self, path: str, *, sched=None, priority: int = 0):
        attrs = self.hdfs.attrs(path)
        if "striped" in attrs:
            return StripedReader(self.hdfs, path, threads=self.threads,
                                 sched=sched, priority=priority)
        return _PlainReader(self.hdfs, path, sched=sched, priority=priority)

    def _delta_chain(self, step: int,
                     index: Optional[TensorIndex] = None,
                     sched=None) -> list:
        """``[(step, index), ...]`` along ``step``'s delta chain, base
        (full snapshot) first.  Raises on a cycle in the chain metadata."""
        chain = []
        seen: set[int] = set()
        cur, idx = step, (index if index is not None
                          else self.load_index(step, sched=sched))
        while True:
            if cur in seen:
                raise ValueError(f"delta chain cycle at step {cur}")
            seen.add(cur)
            chain.append((cur, idx))
            if not idx.is_delta:
                break
            cur = idx.base_step
            idx = self.load_index(cur, sched=sched)
        chain.reverse()
        return chain

    def _reader(self, step: int, *, sched=None, priority: int = 0,
                index: Optional[TensorIndex] = None):
        """Range reader for ``step``'s data stream.  ``sched``/``priority``
        attach a ``repro.core.pipeline.IOScheduler``: preads then hold
        "dfs" tokens so restore waves of different priority classes share
        the DFS without convoying each other.

        A full step gets its file's reader directly (no extra metadata
        reads); a delta step gets a :class:`LayeredReader` over its base +
        delta chain, so any logical range is read exactly once, from the
        newest layer holding it."""
        if self.hdfs.exists(self.data_path(step)):
            return self._file_reader(self.data_path(step), sched=sched,
                                     priority=priority)
        chain = self._delta_chain(step, index=index, sched=sched)
        base_step, base_index = chain[0]
        if not self.hdfs.exists(self.data_path(base_step)):
            raise FileNotFoundError(
                f"checkpoint step {step}: base snapshot {base_step} data "
                "file is missing (torn or garbage-collected save)")
        readers = [self._file_reader(self.data_path(base_step),
                                     sched=sched, priority=priority)]
        layer_ranges = []
        for s, idx in chain[1:]:
            readers.append(self._file_reader(self.delta_data_path(s),
                                             sched=sched,
                                             priority=priority))
            layer_ranges.append(idx.delta["ranges"])
        total = base_index.total_bytes
        return LayeredReader(readers, build_layer_map(total, layer_ranges),
                             total)

    def _dim_slices(self, index: TensorIndex, likes: tuple, *,
                    specs=None, rules=None, axis_sizes=None, coords=None,
                    shard_slices: Optional[dict] = None) -> dict:
        """{index entry name: per-dim (start, size)} for this host."""
        out: dict = {}
        if shard_slices:  # legacy {name: (start_row, n_rows)} rows form
            for name, rows in shard_slices.items():
                try:
                    e = index.resolve(name)
                except KeyError:
                    continue
                if len(e.shape) >= 1:
                    out[e.name] = (tuple(rows),)
        if specs is None:
            return out
        sizes = dict(axis_sizes or {})
        if rules is not None and not sizes:
            sizes = dict(rules.mesh.shape)
        coords = dict(coords or {})
        for ti, spec_tree in enumerate(specs):
            if spec_tree is None or ti >= len(likes):
                continue
            for name, spec in _flat_specs(spec_tree):
                if spec is None:
                    continue
                try:
                    e = index.resolve(f"t{ti}{name}")
                except KeyError:
                    continue
                out[e.name] = dim_slices_for_spec(spec, e.shape, sizes,
                                                  coords)
        return out

    def _wave_names(self, index: TensorIndex,
                    n_likes: int) -> list[list[str]]:
        """Entry names per restore wave, each in stream order: wave 0 is
        tree 0 (params), wave 1 the remaining trees (optimizer state).
        A single-tree restore keeps everything in one wave."""
        waves = index.wave_names()
        if n_likes <= 1 and len(waves) > 1:
            return [[n for w in waves for n in w]]
        return waves

    def plan_restore(self, step: int, *likes: Any, specs=None, rules=None,
                     axis_sizes=None, coords=None,
                     shard_slices: Optional[dict] = None, sched=None,
                     **plan_kw) -> tuple[TensorIndex, list[RestorePlan]]:
        """Build this host's restore plan for ``step``: one ``RestorePlan``
        per wave (params, then optimizer state).

        Sharding is described either by ``specs`` — a tuple of
        PartitionSpec trees congruent to ``likes`` (``None`` entries =
        fully replicated) evaluated against ``rules``/``axis_sizes`` +
        ``coords`` (axis name -> this host's coordinate) — or by the
        legacy ``shard_slices`` ``{tensor_name: (start_row, n_rows)}``
        leading-dim form.  With neither, the full checkpoint is planned.
        """
        index = self.load_index(step, sched=sched)
        slices = self._dim_slices(index, likes, specs=specs, rules=rules,
                                  axis_sizes=axis_sizes, coords=coords,
                                  shard_slices=shard_slices)
        plans = [build_restore_plan(index, names, slices, **plan_kw)
                 for names in self._wave_names(index, len(likes))]
        return index, plans

    def _execute_wave(self, reader, plan: RestorePlan,
                      priority: Optional[int] = None) -> dict:
        """Run one wave; {entry name: array} with bf16 views restored."""
        arrays = execute_plan(reader, plan, priority=priority)
        out = {}
        for t, arr in zip(plan.tensors, arrays):
            if t.name.endswith("#bf16"):
                arr = arr.view(jax.numpy.bfloat16)
            out[t.name] = arr
        return out

    def _assemble(self, likes: tuple, first_ti: int, results: dict) -> list:
        out = []
        for k, like in enumerate(likes):
            leaves = []
            for name, _ in _flat_with_names(like):
                key = f"t{first_ti + k}{name}"
                arr = results.get(key, results.get(key + "#bf16"))
                assert arr is not None, f"missing tensor {key}"
                leaves.append(arr)
            out.append(jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(like), leaves))
        return out

    def restore_planned(self, step: int, *likes: Any, specs=None,
                        rules=None, axis_sizes=None, coords=None,
                        shard_slices: Optional[dict] = None,
                        async_tail: bool = False, sched=None,
                        priority: int = CRITICAL,
                        tail_priority: int = DEFERRED, **plan_kw):
        """Planner-backed restore of trees congruent to ``likes``.

        Returns ``tuple(trees)`` — or, with ``async_tail=True``, the pair
        ``(first_tree, Future)`` where the Future resolves to the tuple of
        remaining trees: the optimizer-state wave streams on a background
        thread so the caller can overlap it with model initialization.

        ``sched`` attaches an ``IOScheduler`` to every pread the restore
        issues: the params wave runs at ``priority`` (CRITICAL — it gates
        model init) and the async optimizer tail at ``tail_priority``
        (DEFERRED — it only has to land before the first optimizer
        update), so a resume never convoys foreground startup I/O.

        Bytes of ``step`` staged in :attr:`handoff` by the startup DAG
        (under the same manifest) are served from memory, or waited for
        while in flight; only what no staged piece holds is read from
        the DFS.  The restore drops the staging when it ends.
        """
        with span("ckpt.plan"):
            index, plans = self.plan_restore(
                step, *likes, specs=specs, rules=rules,
                axis_sizes=axis_sizes, coords=coords,
                shard_slices=shard_slices, sched=sched, **plan_kw)
            staging = self.handoff.lookup(step, index)
            reader = HandoffReader(
                self._reader(step, sched=sched, priority=priority,
                             index=index), staging)
        try:
            with span("ckpt.wave.params"):
                results = (self._execute_wave(reader, plans[0],
                                              priority=priority)
                           if plans else {})
            if not async_tail:
                with span("ckpt.wave.opt"):
                    for plan in plans[1:]:
                        results.update(self._execute_wave(
                            reader, plan, priority=priority))
                with span("ckpt.assemble"):
                    return tuple(self._assemble(likes, 0, results))
            with span("ckpt.assemble"):
                first = self._assemble(likes[:1], 0, results)[0]
        finally:
            if not (async_tail and len(likes) > 1):
                self.handoff.drop(staging=staging)
        parent = current()

        def _tail():
            try:
                with span("ckpt.wave.opt", parent=parent):
                    res = {}
                    for plan in plans[1:]:
                        res.update(self._execute_wave(
                            reader, plan, priority=tail_priority))
                    return tuple(self._assemble(likes[1:], 1, res))
            finally:
                self.handoff.drop(staging=staging)

        if len(likes) <= 1:
            fut: Future = Future()
            fut.set_result(())
            return first, fut
        return first, _tail_pool().submit(_tail)

    def restore(self, step: int, *likes: Any,
                shard_slices: Optional[dict] = None, sched=None,
                priority: int = CRITICAL) -> tuple:
        """Restore trees congruent to ``likes`` (pytrees of arrays or
        ShapeDtypeStructs).

        ``shard_slices``: optional {tensor_name: (start_row, n_rows)} for
        sharding-aware partial restore of leading-dim sharded tensors; the
        returned leaves then hold only those rows.  (For arbitrary-dim
        sharding use ``restore_planned`` with PartitionSpec trees.)
        """
        return self.restore_planned(step, *likes, shard_slices=shard_slices,
                                    sched=sched, priority=priority)

    def restore_bytes_for_shard(self, step: int, fraction: float, *,
                                specs=None, rules=None, axis_sizes=None,
                                coords=None,
                                shard_slices: Optional[dict] = None) -> int:
        """Planned bytes for a host reading 1/N of every SHARDED tensor.

        Sharded entries count at ``fraction``; replicated entries are read
        in full by every host and count at 1.0.  Which entries are sharded
        comes from the same ``specs``/``shard_slices`` forms
        ``plan_restore`` takes; with neither, every non-scalar entry is
        assumed sharded (scalars — step counters, loss scales — are always
        replicated and no longer undercounted)."""
        index = self.load_index(step)
        likes: tuple = ()
        if specs is not None:
            likes = (None,) * len(specs)   # _dim_slices only needs arity
        sliced = self._dim_slices(index, likes, specs=specs, rules=rules,
                                  axis_sizes=axis_sizes, coords=coords,
                                  shard_slices=shard_slices)
        have_info = specs is not None or shard_slices
        total = 0.0
        for e in index.entries.values():
            if e.name in sliced or (not have_info and e.shape):
                total += e.nbytes * fraction
            else:
                total += e.nbytes
        return int(total)
