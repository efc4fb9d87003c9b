"""WAL crash-consistency units (ISSUE 8): torn-tail truncation, bad-CRC
skip-and-stop, compaction equivalence (replay(snapshot + suffix) ==
replay(full log)), and a randomized kill-offset fuzz (slow).

These run against the raw log and against HeadServer's replay state
machine — the two layers whose agreement IS the durability contract.
"""

import asyncio
import os
import random
import shutil
import struct

import pytest

from ray_tpu._private.wal import MAGIC, WriteAheadLog, replay, scan


def _run(coro):
    return asyncio.run(coro)


async def _write_log(path, ops, fsync_interval_ms=0.0):
    w = WriteAheadLog(path, fsync_interval_ms=fsync_interval_ms)
    w.start()
    for op, data in ops:
        await w.append(op, data)
    await w.close()
    return w


def _ops(n, start=0):
    return [("kv_put", {"ns": "default", "key": b"k%d" % i,
                        "value": b"v%d" % i})
            for i in range(start, start + n)]


# ---------------------------------------------------------------------------
# round trip + ordering
# ---------------------------------------------------------------------------
def test_append_replay_round_trip(tmp_path):
    path = str(tmp_path / "a.wal")
    _run(_write_log(path, _ops(20)))
    recs = replay(path)
    assert [r[0] for r in recs] == list(range(1, 21))  # seq is dense
    assert recs[0][1] == "kv_put"
    assert recs[19][2]["key"] == b"k19"
    # snapshot_seq filtering: the suffix view compaction relies on
    assert [r[0] for r in replay(path, snapshot_seq=15)] == [16, 17, 18, 19, 20]


def test_group_commit_resolves_concurrent_appends(tmp_path):
    path = str(tmp_path / "g.wal")

    async def main():
        w = WriteAheadLog(path, fsync_interval_ms=5.0)
        w.start()
        seqs = await asyncio.gather(
            *[w.append("op", {"i": i}) for i in range(64)])
        assert sorted(seqs) == list(range(1, 65))
        assert w.fsyncs < 64  # batched: one fsync covers the burst
        await w.close()

    _run(main())
    assert len(replay(path)) == 64


def test_reopen_continues_sequence(tmp_path):
    path = str(tmp_path / "r.wal")
    _run(_write_log(path, _ops(5)))
    w = WriteAheadLog(path)
    assert w.seq == 5

    async def more():
        w.start()
        assert await w.append("op", {}) == 6
        await w.close()

    _run(more())
    assert [r[0] for r in replay(path)] == [1, 2, 3, 4, 5, 6]


# ---------------------------------------------------------------------------
# torn tail + bad CRC
# ---------------------------------------------------------------------------
def test_torn_tail_truncated_and_appendable(tmp_path):
    path = str(tmp_path / "t.wal")
    _run(_write_log(path, _ops(10)))
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 5)  # kill -9 mid-record
    recs = replay(path)  # repairs: truncates at the last intact record
    assert [r[0] for r in recs] == list(range(1, 10))
    # the repaired log accepts appends and replays cleanly
    _run(_write_log(path, [("late", {})]))
    recs2 = replay(path)
    assert [r[0] for r in recs2] == list(range(1, 11))
    assert recs2[-1][1] == "late"


def test_bad_crc_record_skip_and_stop(tmp_path):
    """A flipped bit mid-log: replay stops AT the corrupt record —
    records after it are unreachable (boundaries are untrusted) and the
    file is truncated there, never a crash."""
    path = str(tmp_path / "c.wal")
    _run(_write_log(path, _ops(10)))
    # corrupt record #4's payload (walk the framing to find it)
    with open(path, "rb") as f:
        data = bytearray(f.read())
    off = len(MAGIC)
    for _ in range(3):
        length, _crc = struct.unpack_from("<II", data, off)
        off += 8 + length
    length, _crc = struct.unpack_from("<II", data, off)
    data[off + 8 + length // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(data)
    recs = replay(path)
    assert [r[0] for r in recs] == [1, 2, 3]
    assert os.path.getsize(path) < len(data)  # physically truncated


def test_garbage_preamble_resets_log(tmp_path):
    path = str(tmp_path / "junk.wal")
    with open(path, "wb") as f:
        f.write(b"this is not a wal file at all")
    assert replay(path) == []
    # repaired to a clean empty log that accepts appends
    _run(_write_log(path, _ops(2)))
    assert len(replay(path)) == 2


def test_failed_write_rolls_back_torn_record(tmp_path):
    """A commit that dies mid-write (transient ENOSPC/EIO) must not
    leave a torn record mid-file: recovery's scan would stop THERE and
    silently discard every LATER acked batch. The failed batch's acks
    error, the file rolls back to the last fsynced offset, and
    subsequent appends stay durable."""
    path = str(tmp_path / "fail.wal")

    async def main():
        w = WriteAheadLog(path, fsync_interval_ms=0.0)
        w.start()
        await w.append("ok", {"i": 1})
        good_size = w.size_bytes

        real = w._write_and_sync

        def torn_write(buf):
            # half the bytes land, then the device errors
            w._f.write(buf[:len(buf) // 2])
            w._f.flush()
            raise OSError(28, "No space left on device")

        w._write_and_sync = torn_write
        with pytest.raises(RuntimeError):
            await w.append("doomed", {"i": 2})
        w._write_and_sync = real
        assert os.path.getsize(path) == good_size  # torn bytes gone
        # the log still accepts appends and they survive replay
        await w.append("after", {"i": 3})
        await w.close()

    _run(main())
    recs = replay(path)
    assert [(r[1], r[2]["i"]) for r in recs] == [("ok", 1), ("after", 3)]
    assert [r[0] for r in recs] == [1, 3]  # seq 2 was never acked


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------
def _reduce(records, kv=None):
    """Reference reducer: the kv materialization of a record stream,
    optionally applied on top of an existing (snapshot) state."""
    kv = dict(kv or {})
    for _seq, op, data in records:
        if op == "kv_put":
            kv[data["key"]] = data["value"]
        elif op == "kv_del":
            kv.pop(data["key"], None)
    return kv


def test_compaction_equivalence_replay_snapshot_plus_suffix(tmp_path):
    """replay(snapshot + rotated log) == replay(full log): rotation drops
    ONLY records the snapshot covers, keeps flushed-after-snapshot
    records AND pending ones."""
    path = str(tmp_path / "comp.wal")
    full = str(tmp_path / "full.wal")

    async def main():
        w = WriteAheadLog(path, fsync_interval_ms=0.0)
        w.start()
        ops = _ops(30) + [("kv_del", {"key": b"k3"}),
                          ("kv_del", {"key": b"k7"})]
        for op, data in ops[:20]:
            await w.append(op, data)
        snapshot_seq = w.seq  # snapshot "saved" here covers seq <= 20
        snapshot_kv = _reduce(scan(path)[0])
        for op, data in ops[20:]:
            await w.append(op, data)
        shutil.copy(path, full)  # the full-log counterfactual
        await w.rotate(snapshot_seq)
        # post-rotate appends land in the fresh file
        await w.append("kv_put", {"ns": "default", "key": b"post",
                                  "value": b"rotate"})
        await w.close()
        return snapshot_seq, snapshot_kv

    snapshot_seq, snapshot_kv = _run(main())
    suffix = replay(path)
    assert all(seq > snapshot_seq for seq, _op, _d in suffix)
    combined = _reduce(suffix, kv=snapshot_kv)
    full_state = _reduce(replay(full))
    full_state[b"post"] = b"rotate"
    assert combined == full_state


def test_headserver_snapshot_plus_wal_equals_full_replay(tmp_path):
    """Same equivalence one layer up: HeadServer's _apply_snapshot +
    _apply_wal_op suffix must land in the same state as replaying every
    op from scratch."""
    from ray_tpu._private.gcs import HeadServer

    def fresh():
        hs = HeadServer(str(tmp_path), 0, persist_path=None)
        return hs

    ops = []
    for i in range(6):
        ops.append(("actor_create", {
            "actor_id": f"a{i}", "spec_wire": {"class_name": "C"},
            "name": f"n{i}", "namespace": "default", "max_restarts": 0,
            "state": "PENDING_CREATION", "addr": None, "node_id": None,
            "num_restarts": 0, "owner_job": "j", "death_cause": "",
            "pid": 0}))
    ops.append(("actor_update", {"actor_id": "a1", "state": "ALIVE",
                                 "addr": {"host": "h", "port": 1},
                                 "pid": 42, "node_id": "nodeA"}))
    ops.append(("actor_update", {"actor_id": "a2", "state": "DEAD",
                                 "death_cause": "boom", "addr": None,
                                 "drop_name": True}))
    ops.append(("kv_put", {"ns": "default", "key": b"x", "value": b"1",
                           "overwrite": True}))
    ops.append(("kv_del", {"ns": "default", "key": b"x"}))
    ops.append(("kv_put", {"ns": "s", "key": b"y", "value": b"2",
                           "overwrite": True}))
    ops.append(("job", {"key": "j", "job": {"job_id": "j",
                                            "state": "RUNNING"}}))
    ops.append(("node_register", {
        "node_id": "nodeA", "incarnation": 7,
        "addr": {"host": "h", "port": 2},
        "resources": {"total": {"CPU": 4}, "available": {"CPU": 4},
                      "labels": {}}, "alive": True}))
    ops.append(("node_dead", {"node_id": "nodeA", "incarnation": 7,
                              "reason": "test"}))
    ops.append(("pg", {"pg": {"pg_id": "p1", "state": "CREATED",
                              "bundles": [{"CPU": 1}], "strategy": "PACK",
                              "placement": ["nodeA"], "name": ""}}))
    ops.append(("pg_remove", {"pg_id": "p1"}))

    full = fresh()
    for op, data in ops:
        full._apply_wal_op(op, data)

    cut = 9
    mid = fresh()
    for op, data in ops[:cut]:
        mid._apply_wal_op(op, data)
    snapshot = mid._snapshot()

    resumed = fresh()
    resumed._apply_snapshot(snapshot)
    for op, data in ops[cut:]:
        resumed._apply_wal_op(op, data)

    def state_of(hs):
        return {
            "kv": hs.kv,
            "jobs": hs.jobs,
            "named": dict(hs.named_actors),
            "actors": {a.actor_id: (a.state, a.addr, a.node_id,
                                    a.num_restarts, a.death_cause, a.pid)
                       for a in hs.actors.values()},
            "nodes": {n.node_id: (n.incarnation, n.alive)
                      for n in hs.nodes.values() if n.alive},
            "fenced": dict(hs.fenced_incarnations),
            "pgs": hs.placement_groups,
        }

    assert state_of(resumed) == state_of(full)


# ---------------------------------------------------------------------------
# randomized kill-offset fuzz
# ---------------------------------------------------------------------------
def test_fuzz_random_kill_offsets(tmp_path):
    """Truncate the log at EVERY kind of offset a kill -9 could leave
    behind: replay must never raise and must always yield a seq-dense
    prefix of what was written."""
    path = str(tmp_path / "fuzz.wal")
    _run(_write_log(path, [("op", {"i": i, "pad": os.urandom(i % 97)})
                           for i in range(120)]))
    pristine = str(tmp_path / "pristine.wal")
    shutil.copy(path, pristine)
    size = os.path.getsize(pristine)
    rng = random.Random(1234)
    offsets = {rng.randrange(0, size) for _ in range(60)}
    offsets.update({0, 1, len(MAGIC), size - 1, size})
    for cut in sorted(offsets):
        shutil.copy(pristine, path)
        with open(path, "r+b") as f:
            f.truncate(cut)
        recs = replay(path)  # must not raise
        seqs = [r[0] for r in recs]
        assert seqs == list(range(1, len(seqs) + 1)), \
            f"non-prefix replay at cut={cut}"
        # and the repaired file keeps working
        _run(_write_log(path, [("again", {})]))
        assert replay(path)[-1][1] == "again"


def test_fuzz_random_corruption(tmp_path):
    """Flip one byte anywhere: replay yields an intact prefix (checksums
    catch the flip) and never raises."""
    path = str(tmp_path / "flip.wal")
    _run(_write_log(path, [("op", {"i": i}) for i in range(80)]))
    pristine = str(tmp_path / "pristine2.wal")
    shutil.copy(path, pristine)
    size = os.path.getsize(pristine)
    rng = random.Random(99)
    for _ in range(40):
        shutil.copy(pristine, path)
        pos = rng.randrange(len(MAGIC), size)
        with open(path, "r+b") as f:
            f.seek(pos)
            byte = f.read(1)
            f.seek(pos)
            f.write(bytes([byte[0] ^ 0xFF]))
        recs = replay(path)
        seqs = [r[0] for r in recs]
        assert seqs == list(range(1, len(seqs) + 1))
