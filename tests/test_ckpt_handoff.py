"""Read-once hand-off (repro.ckpt.handoff): a planned restore after the
startup DAG's staged read returns the same bytes as a DFS read, reads from
the DFS only what no staged piece holds, waits for pieces in flight, falls
back to the DFS for failed ones, and never serves a replaced or re-saved
step."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer
from repro.ckpt.plan import plan_for_rank, read_plan
from repro.core.profiler import SPANS
from repro.dfs.hdfs import HdfsCluster


@pytest.fixture()
def hdfs(tmp_path):
    return HdfsCluster(tmp_path / "h", num_groups=4, block_size=1 << 20)


def _state(seed: int = 0):
    """Params with a bfloat16 entry and an AdamW-like state; every leading
    dim is even, so two nodes' row plans split the stream exactly."""
    rng = np.random.default_rng(seed)
    params = {"w": rng.standard_normal((64, 48)).astype(np.float32),
              "emb": jnp.asarray(rng.standard_normal((32, 16)),
                                 jnp.bfloat16),
              "b": rng.standard_normal((6,)).astype(np.float32)}
    opt = {"mu": {k: np.full(np.shape(v), 0.5 + seed, np.float32)
                  for k, v in params.items()},
           "nu": {k: np.full(np.shape(v), 2.0 + seed, np.float32)
                  for k, v in params.items()}}
    return params, opt


def _save(ck, delta: bool):
    """Step 5 as a full save, or step 6 as a delta over step 5."""
    p, o = _state(0)
    ck.save(5, p, o)
    if not delta:
        return 5
    p2 = dict(p, w=p["w"] + 1.0)
    ck.save_delta(6, p2, o, base=5)
    return 6


def _dag_read(ck, step, *, nodes=2, ranks=None, tail=True, **plan_kw):
    """What the startup DAG's checkpoint tasks do with the optimizer on:
    read each rank's rows plan of the params wave into the staging, then
    register the optimizer wave's ranges as pending.  Returns the staging
    and, per rank, its tail plans and pending pieces."""
    index = ck.load_index(step)
    reader = ck._reader(step, index=index)
    staging = ck.handoff.stage(step, index, owner="run0")
    tails = []
    for rank in ranks if ranks is not None else range(nodes):
        plans = [plan_for_rank(index, rank, nodes, names=names, **plan_kw)
                 for names in index.wave_names()]
        sink = staging.sink()
        read_plan(reader, plans[0], sink=sink)
        sink.expect([(op.offset, op.length)
                     for p in plans[1:] for op in p.reads])
        tails.append((plans[1:], sink))
    if tail:
        for plans, sink in tails:
            for p in plans:
                read_plan(reader, p, sink=sink)
            sink.abandon()
    return staging, reader, tails


def _like():
    p, o = _state(0)
    return jax.eval_shape(lambda: p), jax.eval_shape(lambda: o)


def _restore(ck, step, **plan_kw):
    first, fut = ck.restore_planned(step, *_like(), async_tail=True,
                                    **plan_kw)
    return (first,) + fut.result(timeout=30)


def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _counts(rows):
    return (rows.get("ckpt.handoff.hit_bytes", (0,))[0],
            rows.get("ckpt.handoff.miss_bytes", (0,))[0])


def _index_bytes(ck, step):
    n = len(ck.hdfs.read(ck.index_path(step)))
    if step == 6:
        n += len(ck.hdfs.read(ck.index_path(5)))
    return n


@pytest.mark.parametrize("delta", [False, True], ids=["full", "delta"])
def test_staged_restore_bit_identical_and_read_once(hdfs, delta):
    ck = _save(Checkpointer(hdfs, striped=True, width=4), delta)
    step = ck
    ck = Checkpointer(hdfs, striped=True, width=4)
    total = ck.load_index(step).total_bytes
    idx_bytes = _index_bytes(ck, step)
    cold = _restore(Checkpointer(hdfs, striped=True, width=4), step)

    r0 = hdfs.read_bytes
    _dag_read(ck, step)
    dag = hdfs.read_bytes - r0
    snap = SPANS.snapshot()
    r1 = hdfs.read_bytes
    warm = _restore(ck, step)
    loop = hdfs.read_bytes - r1
    _same(warm, cold)
    # the DAG read each byte of the state once (and the holes its rows
    # plans bridge); the restore only its manifest (and, for a delta
    # step, the chain's)
    index = ck.load_index(step)
    planned = sum(plan_for_rank(index, r, 2, names=n).planned_bytes
                  for r in range(2) for n in index.wave_names())
    assert total <= planned <= 1.05 * total
    assert dag == planned + idx_bytes
    assert loop == idx_bytes
    assert _counts(SPANS.since(snap)) == (total, 0)
    assert ck.handoff.lookup(step, ck.load_index(step)) is None


def test_partial_cover_reads_only_uncovered_bytes(hdfs):
    ck = Checkpointer(hdfs, striped=True, width=4)
    step = _save(ck, delta=False)
    total = ck.load_index(step).total_bytes
    idx_bytes = _index_bytes(ck, step)
    staging, _, _ = _dag_read(ck, step, ranks=[0])   # rank 1's rows missing
    staged = sum(p.end - p.off for p in staging._pieces)
    assert 0 < staged < total
    snap = SPANS.snapshot()
    r0 = hdfs.read_bytes
    warm = _restore(ck, step)
    assert hdfs.read_bytes - r0 == idx_bytes + (total - staged)
    assert _counts(SPANS.since(snap)) == (staged, total - staged)
    _same(warm, _restore(Checkpointer(hdfs, striped=True, width=4), step))


def test_whole_tensors_are_handed_out_without_copy(hdfs):
    ck = Checkpointer(hdfs, striped=True, width=4)
    step = _save(ck, delta=False)
    staging, _, _ = _dag_read(ck, step)
    params, opt = _restore(ck, step)
    # each tensor the two nodes' halves hold whole is a view of the
    # staged stream, not a copy
    for leaf in jax.tree.leaves((params, opt)):
        assert np.shares_memory(np.asarray(leaf), staging.buf)
    _same((params, opt), _state(0))


@pytest.mark.parametrize("outcome", ["filled", "failed"])
def test_pending_pieces_are_waited_for_or_read_again(hdfs, outcome):
    """The optimizer tail waits on pieces still in flight; a failed piece
    is read from the DFS, with the same result either way."""
    ck = Checkpointer(hdfs, striped=True, width=4)
    step = _save(ck, delta=False)
    index = ck.load_index(step)
    opt_bytes = sum(index.entries[n].nbytes for n in index.wave_names()[1])
    staging, reader, tails = _dag_read(ck, step, tail=False)

    def finish():
        time.sleep(0.2)
        for plans, sink in tails:
            if outcome == "filled":
                for p in plans:
                    read_plan(reader, p, sink=sink)
            sink.abandon()

    snap = SPANS.snapshot()
    t = threading.Thread(target=finish)
    t.start()
    r0 = hdfs.read_bytes
    warm = _restore(ck, step)
    t.join()
    rows = SPANS.since(snap)
    assert rows["ckpt.handoff.wait"].count >= 1
    hit, miss = _counts(rows)
    restore_reads = hdfs.read_bytes - r0 - len(ck.hdfs.read(
        ck.index_path(step)))
    if outcome == "filled":
        assert miss == 0                    # the producer's reads only
        assert restore_reads == sum(p.planned_bytes for plans, _ in tails
                                    for p in plans)
    else:
        assert miss == opt_bytes
        assert restore_reads == opt_bytes   # the restore's own reads
    assert hit + miss == index.total_bytes
    _same(warm, _state(0))


def test_new_staging_replaces_old_and_save_invalidates(hdfs, tmp_path):
    ck = Checkpointer(hdfs, striped=True, width=4)
    _save(ck, delta=True)
    i5 = ck.load_index(5)
    old, _, _ = _dag_read(ck, 5)
    assert ck.handoff.lookup(5, i5) is old and old._pieces
    new = ck.handoff.stage(5, i5, owner="run1")      # a second startup
    assert new is not old and not old._pieces
    assert ck.handoff.lookup(5, i5) is new
    ck.handoff.stage(6, ck.load_index(6), owner="run1")
    assert ck.handoff.lookup(5, i5) is None

    # a save of the staged step drops the staging
    _dag_read(ck, 5)
    p, o = _state(1)
    ck.save(5, p, o)
    assert ck.handoff.lookup(5, i5) is None
    assert ck.handoff.lookup(5, ck.load_index(5)) is None
    _same(_restore(ck, 5), (p, o))

    # a re-save through another checkpointer changes the manifest digest,
    # so the stale staging is never served
    _dag_read(ck, 5)
    p2, o2 = _state(2)
    Checkpointer(hdfs, striped=True, width=4).save(5, p2, o2)
    _same(_restore(ck, 5), (p2, o2))


def test_staging_holds_each_byte_once(hdfs):
    """Every node of a "full" resume plan reads the whole state; the
    staging claims each byte for one of them, and the others read theirs
    into throwaway buffers."""
    ck = Checkpointer(hdfs, striped=True, width=4)
    step = _save(ck, delta=False)
    index = ck.load_index(step)
    reader = ck._reader(step, index=index)
    staging = ck.handoff.stage(step, index, owner="run0")
    r0 = hdfs.read_bytes
    for _rank in range(3):
        sink = staging.sink()
        for names in index.wave_names():
            read_plan(reader, plan_for_rank(index, 0, 1, names=names),
                      sink=sink)
    assert hdfs.read_bytes - r0 == 3 * index.total_bytes
    pieces = staging._pieces
    assert all(p.ok for p in pieces)
    assert all(a.end <= b.off for a, b in zip(pieces, pieces[1:]))
    assert sum(p.end - p.off for p in pieces) == index.total_bytes
