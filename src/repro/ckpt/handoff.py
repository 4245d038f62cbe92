"""Read-once hand-off of checkpoint bytes from the startup DAG to the
planned restore that follows it (BootSeer §4.4, checkpoint resumption).

The startup DAG's checkpoint waves (``core/bootseer.py``) read this
node's plan of the resume step.  A :class:`Staging` keeps what they read
in one host buffer laid out as the step's logical data stream — above
``LayeredReader``, so a delta chain needs nothing of its own — and
records which *pieces* of it hold bytes.  ``Checkpointer.
restore_planned`` then reads through a :class:`HandoffReader`: a tensor
the pieces hold whole is handed out as a view of that buffer, with no
copy; other staged bytes are copied out; bytes of a piece still in
flight are waited for; only the uncovered sub-ranges go to the DFS, in
one batched ``pread_many``.  Each byte of the step crosses the DFS once,
and the restored arrays are the staged bytes themselves, so host memory
holds one copy of the state.

A piece is *pending* (a read is in flight: the deferred optimizer wave
registers its ranges the moment the params wave returns, so a restore
waits for them instead of reading them a second time) or *filled*.  A
pending piece whose read fails is dropped, and whoever waits on it reads
its range from the DFS.  Pieces never overlap: a node whose plan
overlaps bytes another node already staged or claimed reads them into a
throwaway buffer, as every node's reads did before.

A checkpointer holds at most one step's staging (:class:`HandoffStore`):
a new staging replaces the old one, a save of the step drops it, a
restore that consumed it drops it when it ends, and a staging serves
only a restore whose manifest digest is the one it was staged under.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Optional, Sequence

import numpy as np

from repro.core.profiler import count, span


class _Piece:
    """The staged range ``[off, end)``; ``ready`` is set once it is
    filled (``ok``) or failed."""

    __slots__ = ("off", "end", "ok", "ready")

    def __init__(self, off: int, end: int):
        self.off = off
        self.end = end
        self.ok = False
        self.ready = threading.Event()


class Staging:
    """The staged bytes of one checkpoint step, read under the manifest
    whose digest is ``digest``."""

    def __init__(self, step: int, digest: Optional[str], owner,
                 size: int):
        self.step = step
        self.digest = digest
        self.owner = owner
        self.buf = np.empty(size, np.uint8)    # pages land as reads do
        self._lock = threading.Lock()
        self._pieces: list[_Piece] = []        # disjoint, by offset
        self._live = True

    def _overlapping(self, off: int, end: int) -> list[_Piece]:
        """Pieces overlapping ``[off, end)``, by offset (lock held)."""
        i = bisect_left(self._pieces, off, key=lambda p: p.end)
        out = []
        while i < len(self._pieces) and self._pieces[i].off < end:
            if self._pieces[i].end > off:
                out.append(self._pieces[i])
            i += 1
        return out

    def cover(self, off: int, end: int) -> list[tuple]:
        """``[off, end)`` as ``(lo, hi, piece)`` parts, ``piece`` None
        where nothing is staged."""
        with self._lock:
            pieces = self._overlapping(off, end)
        parts, c = [], off
        for p in pieces:
            if p.off > c:
                parts.append((c, p.off, None))
            lo, hi = max(c, p.off), min(p.end, end)
            parts.append((lo, hi, p))
            c = hi
        if c < end:
            parts.append((c, end, None))
        return parts

    def sink(self) -> "Sink":
        """A producer's handle: one per node's checkpoint waves."""
        return Sink(self)

    def close(self) -> None:
        """Stop serving: pending pieces fail, so their waiters read the
        DFS, and nothing is staged any more."""
        with self._lock:
            self._live = False
            for p in self._pieces:
                p.ready.set()
            self._pieces = []


class Sink:
    """Where one node's checkpoint waves land in a :class:`Staging`
    (``read_plan(..., sink=)``).  Its pending pieces are its own: it
    fills them, or fails them in :meth:`abandon`."""

    def __init__(self, staging: Staging):
        self.staging = staging
        self._mine: dict = {}        # (off, end) -> pending piece

    def _claim(self, off: int, end: int) -> list[tuple]:
        """``[off, end)`` as ``(lo, hi, mine)`` parts; unstaged parts
        become this sink's pending pieces (lock held)."""
        st = self.staging
        parts, c = [], off
        for p in st._overlapping(off, end) + [None]:
            nxt = end if p is None else max(p.off, c)
            if nxt > c and st._live:
                piece = _Piece(c, nxt)
                st._pieces.insert(
                    bisect_left(st._pieces, c, key=lambda q: q.off), piece)
                self._mine[(c, nxt)] = piece
                parts.append((c, nxt, True))
            elif nxt > c:
                parts.append((c, nxt, False))
            if p is None:
                break
            lo, hi = max(c, p.off), min(p.end, end)
            parts.append((lo, hi, self._mine.get((p.off, p.end)) is p
                          and not p.ready.is_set()))
            c = hi
        return parts

    def expect(self, ranges: Sequence[tuple[int, int]]) -> None:
        """Claim ``(offset, length)`` ranges a later read of this sink
        will fill: a restore waits for them from now on."""
        with self.staging._lock:
            for off, ln in ranges:
                if ln > 0:
                    self._claim(off, off + ln)

    def into(self, ranges: Sequence[tuple[int, int]]):
        """Split ``ranges`` for one ``pread_many``: the parts this sink
        fills read into the staging's buffer, the rest into throwaway
        buffers.  Returns ``(sub_ranges, buffers)``."""
        subs, bufs = [], []
        with self.staging._lock:
            for off, ln in ranges:
                for lo, hi, mine in self._claim(off, off + ln):
                    subs.append((lo, hi - lo))
                    bufs.append(self.staging.buf[lo:hi] if mine
                                else np.empty(hi - lo, np.uint8))
        return subs, bufs

    def landed(self, ranges: Sequence[tuple[int, int]]) -> None:
        """The sub-ranges of :meth:`into` were read: fill the pieces."""
        with self.staging._lock:
            for off, ln in ranges:
                piece = self._mine.pop((off, off + ln), None)
                if piece is not None:
                    piece.ok = True
                    piece.ready.set()

    def abandon(self) -> None:
        """Fail this sink's pieces still pending (their read failed or
        never ran): waiters read those ranges from the DFS."""
        st = self.staging
        with st._lock:
            for piece in self._mine.values():
                if piece in st._pieces:
                    st._pieces.remove(piece)
                piece.ready.set()
            self._mine.clear()


class HandoffStore:
    """A checkpointer's staged step (at most one)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._staging: Optional[Staging] = None

    def stage(self, step: int, index, owner) -> Staging:
        """The staging ``owner`` (one startup run) fills for ``step``,
        read under the manifest ``index``; a staging of another step,
        manifest or run is replaced."""
        with self._lock:
            cur = self._staging
            if cur is not None and (cur.step, cur.digest, cur.owner) == \
                    (step, index.digest, owner):
                return cur
            new = self._staging = Staging(step, index.digest, owner,
                                          index.total_bytes)
        if cur is not None:
            cur.close()
        return new

    def lookup(self, step: int, index) -> Optional[Staging]:
        """The staging of ``step`` if it was read under the manifest
        ``index``."""
        with self._lock:
            cur = self._staging
            if cur is not None and index.digest is not None \
                    and (cur.step, cur.digest) == (step, index.digest):
                return cur
            return None

    def drop(self, *, step: Optional[int] = None, staging=None,
             owner=None) -> None:
        """Drop the staging if it is of ``step``, is ``staging`` or was
        filled by ``owner``."""
        with self._lock:
            cur = self._staging
            if cur is None or not (cur.step == step or cur is staging
                                   or (owner is not None
                                       and cur.owner == owner)):
                return
            self._staging = None
        cur.close()


def _u8(buf) -> np.ndarray:
    return np.frombuffer(memoryview(buf).cast("B"), np.uint8)


class HandoffReader:
    """``pread``/``pread_many`` over a checkpoint step that serves what
    ``staging`` holds from memory and reads the rest through ``reader``.
    With no staging it is ``reader``, the miss counted."""

    def __init__(self, reader, staging: Optional[Staging]):
        self._reader = reader
        self.staging = staging

    def take(self, off: int, ln: int) -> Optional[np.ndarray]:
        """``[off, off + ln)`` as a view of the staged buffer, with no
        copy, where filled pieces hold all of it (waiting for pieces in
        flight); None otherwise."""
        st = self.staging
        if st is None or ln <= 0:
            return None
        parts = st.cover(off, off + ln)
        if any(p is None for _lo, _hi, p in parts):
            return None
        if not all(p.ready.is_set() for _lo, _hi, p in parts):
            with span("ckpt.handoff.wait"):
                for _lo, _hi, p in parts:
                    p.ready.wait()
        if not all(p.ok for _lo, _hi, p in parts):
            return None
        count("ckpt.handoff.hit_bytes", ln)
        return st.buf[off:off + ln]

    def pread(self, off: int, ln: int) -> bytes:
        return self.pread_many([(off, ln)])[0]

    def pread_many(self, ranges, into=None, priority=None):
        outs = [bytearray(ln) for _, ln in ranges] if into is None else into
        counts = [ln for _, ln in ranges]
        st = self.staging
        staged, misses = [], []
        for i, (off, ln) in enumerate(ranges):
            if ln <= 0:
                continue
            parts = (st.cover(off, off + ln) if st is not None
                     else [(off, off + ln, None)])
            for lo, hi, piece in parts:
                (misses if piece is None else staged).append(
                    (i, lo - off, lo, hi, piece))
        hits, late = 0, []
        for job in staged:                  # filled first, then in flight
            if job[4].ready.is_set():
                hits += self._copy(outs, job, misses)
            else:
                late.append(job)
        self._read(outs, counts, misses, priority)
        fallback: list = []
        if late:
            with span("ckpt.handoff.wait"):
                for job in late:
                    job[4].ready.wait()
            for job in late:
                hits += self._copy(outs, job, fallback)
            self._read(outs, counts, fallback, priority)
        if hits:
            count("ckpt.handoff.hit_bytes", hits)
        miss = sum(hi - lo for _i, _d, lo, hi, _p in misses + fallback)
        if miss:
            count("ckpt.handoff.miss_bytes", miss)
        if into is None:
            return [bytes(b) for b in outs]
        return counts

    def _copy(self, outs, job, misses) -> int:
        """Copy one part out of the staged buffer; a failed piece's part
        goes to ``misses``.  Returns the bytes copied."""
        i, dest, lo, hi, piece = job
        if not piece.ok:
            misses.append(job)
            return 0
        _u8(outs[i])[dest:dest + hi - lo] = self.staging.buf[lo:hi]
        return hi - lo

    def _read(self, outs, counts, jobs, priority) -> None:
        """Read ``jobs``' parts from the DFS in one batched call; a short
        part shortens its range's count."""
        if not jobs:
            return
        got = self._reader.pread_many(
            [(lo, hi - lo) for _i, _d, lo, hi, _p in jobs],
            into=[_u8(outs[i])[d:d + hi - lo] for i, d, lo, hi, _p in jobs],
            priority=priority)
        for (i, _d, lo, hi, _p), n in zip(jobs, got):
            counts[i] -= (hi - lo) - n
