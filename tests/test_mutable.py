"""Mutable-index tests: WAL framing/replay, kill-at-every-seam crash
recovery, freshness vs fresh rebuilds, snapshot-consistent serving with
bounded recompiles, and the serialize-layer satellites.

The crash-chaos tests are the acceptance gate of the mutability layer:
for each fault seam (``wal.append`` pre/post, ``compact.merge``,
``manifest.swap``) and each mutation kind (insert/delete/upsert), kill
at the seam, reopen the directory cold, and require the recovered
search state to equal either the pre-mutation or the post-mutation
state — bit-for-bit, never a mix.

``TestBackgroundCompaction`` extends that gate to the maintenance
path (pin → rebuild off-lock → catch-up + flip): kills at the new
seams (``compact.pin``, ``compact.replay``, ``compact.flip``, plus
worker-thread death at ``compact.worker``) with mutations arriving
*mid-rebuild* must recover exactly the pre-flip state including those
mutations, and a completed flip must equal a fresh rebuild over the
final live rows.
"""
import dataclasses
import os
import threading
import time

import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.core.errors import CorruptIndexError, LogicError
from raft_tpu.core import serialize as ser
from raft_tpu.mutable import (
    CompactionPolicy,
    Compactor,
    MutableIndex,
    WalRecord,
    WriteAheadLog,
    compact_background,
    replay,
    segment_paths,
)
from raft_tpu.mutable import manifest as man
from raft_tpu.robust import faults


class Kill(RuntimeError):
    """Stand-in for the process dying at a seam."""


DIM = 16


def _rows(rng, n):
    return rng.standard_normal((n, DIM)).astype(np.float32)


# -- WAL framing ------------------------------------------------------------


class TestWal:
    def test_append_replay_roundtrip(self, rng, tmp_path):
        path = str(tmp_path / "wal.log")
        wal, recovered = WriteAheadLog.open(path)
        assert recovered == []
        vecs = _rows(rng, 3)
        wal.append(WalRecord(op="insert", ids=np.arange(3, dtype=np.int64), vectors=vecs))
        wal.append(WalRecord(op="delete", ids=np.array([1], np.int64)))
        wal.append(WalRecord(op="upsert", ids=np.array([2], np.int64), vectors=vecs[:1]))
        wal.close()
        records, good = replay(path)
        assert [r.op for r in records] == ["insert", "delete", "upsert"]
        assert good == os.path.getsize(path)
        np.testing.assert_array_equal(records[0].vectors, vecs)
        assert records[1].vectors is None

    def test_unknown_op_rejected(self, tmp_path):
        wal, _ = WriteAheadLog.open(str(tmp_path / "wal.log"))
        with pytest.raises(LogicError):
            wal.append(WalRecord(op="truncate", ids=np.array([0], np.int64)))

    @pytest.mark.parametrize("damage", ["truncate", "garbage", "bitflip"])
    def test_torn_tail_recovers_prefix(self, rng, tmp_path, damage):
        path = str(tmp_path / "wal.log")
        wal, _ = WriteAheadLog.open(path)
        for i in range(3):
            wal.append(WalRecord(op="insert", ids=np.array([i], np.int64),
                                 vectors=_rows(rng, 1)))
        wal.close()
        with open(path, "rb") as f:
            data = f.read()
        _, full = replay(path)
        assert full == len(data)
        # identically-shaped records, so the third frame starts at 2/3
        frame = len(data) // 3
        cut = 2 * frame
        if damage == "truncate":
            torn = data[: cut + 5]  # mid-header of record 3
        elif damage == "garbage":
            torn = data[:cut] + b"\xde\xad\xbe\xef" + data[cut + 4 :]
        else:
            # flip a payload bit past the 12-byte frame header: the
            # header parses but the CRC check rejects the record
            flip = cut + 15
            torn = data[:flip] + bytes([data[flip] ^ 0x01]) + data[flip + 1 :]
        with open(path, "wb") as f:  # graft-lint: ignore[non-atomic-write] — test fixture damage
            f.write(torn)
        recovered, good = replay(path)
        assert [int(r.ids[0]) for r in recovered] == [0, 1]
        assert good == cut
        # open() truncates the tail and appends cleanly after it
        wal2, recs = WriteAheadLog.open(path)
        assert len(recs) == 2 and os.path.getsize(path) == cut
        wal2.append(WalRecord(op="delete", ids=np.array([0], np.int64)))
        wal2.close()
        recs3, _ = replay(path)
        assert [r.op for r in recs3] == ["insert", "insert", "delete"]

    def test_missing_file_is_empty_log(self, tmp_path):
        records, good = replay(str(tmp_path / "absent.log"))
        assert records == [] and good == 0


# -- WAL segment rotation ----------------------------------------------------


class TestWalRotation:
    #: one insert frame at DIM=16 is ~374 bytes; 1200 holds three
    MAX_BYTES = 1200

    def _fill(self, rng, path, n=20):
        wal, recovered = WriteAheadLog.open(path, max_bytes=self.MAX_BYTES)
        assert recovered == []
        for i in range(n):
            wal.append(WalRecord(op="insert", ids=np.array([i], np.int64),
                                 vectors=_rows(rng, 1)))
        return wal

    def test_rotation_bounds_segments_and_replays_in_order(self, rng, tmp_path):
        path = str(tmp_path / "wal.log")
        wal = self._fill(rng, path)
        segs = wal.segment_paths()
        assert len(segs) > 1 and wal.segment == len(segs) - 1
        # sealed segments respect the cap and end on whole frames
        for sp in segs[:-1]:
            assert os.path.getsize(sp) <= self.MAX_BYTES
            _, good = replay(sp)
            assert good == os.path.getsize(sp)
        wal.close()
        # reopen replays every segment in sequence order
        wal2, recs = WriteAheadLog.open(path, max_bytes=self.MAX_BYTES)
        assert [int(r.ids[0]) for r in recs] == list(range(20))
        assert wal2.segment == len(segs) - 1  # appends continue in the tail
        wal2.append(WalRecord(op="delete", ids=np.array([0], np.int64)))
        wal2.close()
        _, recs3 = WriteAheadLog.open(path)
        assert [r.op for r in recs3] == ["insert"] * 20 + ["delete"]

    def test_oversized_frame_lands_whole(self, rng, tmp_path):
        """A single frame larger than max_bytes is never split — it
        lands whole in its own segment (frames are the atomicity unit)."""
        path = str(tmp_path / "wal.log")
        wal, _ = WriteAheadLog.open(path, max_bytes=256)
        big = _rows(rng, 64)  # frame ~16 KiB >> 256
        wal.append(WalRecord(op="insert", ids=np.arange(64, dtype=np.int64),
                             vectors=big))
        wal.append(WalRecord(op="delete", ids=np.array([1], np.int64)))
        wal.close()
        _, recs = WriteAheadLog.open(path, max_bytes=256)
        assert [r.op for r in recs] == ["insert", "delete"]
        np.testing.assert_array_equal(recs[0].vectors, big)

    def test_torn_tail_in_active_segment_recovers_prefix(self, rng, tmp_path):
        path = str(tmp_path / "wal.log")
        wal = self._fill(rng, path)
        active = wal.segment_paths()[-1]
        wal.close()
        with open(active, "rb") as f:
            data = f.read()
        with open(active, "wb") as f:  # graft-lint: ignore[non-atomic-write] — test fixture damage
            f.write(data[:-3])  # tear the final frame
        wal2, recs = WriteAheadLog.open(path, max_bytes=self.MAX_BYTES)
        assert [int(r.ids[0]) for r in recs] == list(range(19))
        # the tail was truncated; appending continues cleanly
        wal2.append(WalRecord(op="insert", ids=np.array([19], np.int64),
                              vectors=_rows(rng, 1)))
        wal2.close()
        _, recs3 = WriteAheadLog.open(path)
        assert [int(r.ids[0]) for r in recs3] == list(range(20))

    def test_torn_sealed_segment_orphans_later_segments(self, rng, tmp_path):
        """A tear in a *sealed* segment stops recovery at the tear; the
        later segments (written after it) are outside the valid prefix
        and get unlinked so the invariant is restored."""
        path = str(tmp_path / "wal.log")
        wal = self._fill(rng, path)
        segs = wal.segment_paths()
        wal.close()
        sealed = segs[2]
        with open(sealed, "rb") as f:
            data = f.read()
        with open(sealed, "wb") as f:  # graft-lint: ignore[non-atomic-write] — test fixture damage
            f.write(data[:-5])
        wal2, recs = WriteAheadLog.open(path, max_bytes=self.MAX_BYTES)
        # segments 0..1 are whole (3 frames each), segment 2 lost its last
        assert [int(r.ids[0]) for r in recs] == list(range(8))
        assert wal2.segment == 2  # the torn segment became the active one
        for orphan in segs[3:]:
            assert not os.path.exists(orphan)
        wal2.close()

    def test_mutable_index_rotates_and_compaction_cleans_segments(self, rng, tmp_path):
        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM, max_wal_bytes=self.MAX_BYTES)
        data = _rows(rng, 24)
        for row in data:
            mut.insert(row[None])
        assert mut.wal.segment > 0
        mut.close()
        # cold reopen replays across the rotated segments
        mut2 = MutableIndex.open(d, "brute_force", DIM, max_wal_bytes=self.MAX_BYTES)
        assert mut2.size == 24
        old_segs = segment_paths(mut2.wal.path)
        assert len(old_segs) > 1
        mut2.compact()
        for sp in old_segs:  # superseded generation leaves no segments behind
            assert not os.path.exists(sp)
        _, i = mut2.search(data[:2], 1)
        np.testing.assert_array_equal(np.asarray(i)[:, 0], [0, 1])
        mut2.close()


class TestWalSeal:
    """Explicit sealing (replication's shippable-frame boundary)."""

    def test_seal_rotates_at_a_frame_boundary(self, rng, tmp_path):
        path = str(tmp_path / "wal.log")
        wal, _ = WriteAheadLog.open(path)
        assert wal.seal() is False  # empty active segment: nothing to seal
        assert wal.sealed_segments() == []
        for i in range(3):
            wal.append(WalRecord(op="insert", ids=np.array([i], np.int64),
                                 vectors=_rows(rng, 1)))
        assert wal.seal() is True
        sealed = wal.sealed_segments()
        assert [sq for sq, _ in sealed] == [0]
        # the sealed file ends on a whole frame and replays completely
        _, good = replay(sealed[0][1])
        assert good == os.path.getsize(sealed[0][1])
        assert wal.segment == 1 and wal.offset == 0
        assert wal.seal() is False  # still nothing new to seal
        # appends land in the new active segment; a second seal ships them
        wal.append(WalRecord(op="delete", ids=np.array([0], np.int64)))
        assert wal.seal() is True
        assert [sq for sq, _ in wal.sealed_segments()] == [0, 1]
        wal.close()
        _, recs = WriteAheadLog.open(path)
        assert [r.op for r in recs] == ["insert"] * 3 + ["delete"]

    def test_record_count_tracks_durable_records(self, rng, tmp_path):
        path = str(tmp_path / "wal.log")
        wal, _ = WriteAheadLog.open(path)
        assert wal.record_count() == 0
        for i in range(4):
            wal.append(WalRecord(op="insert", ids=np.array([i], np.int64),
                                 vectors=_rows(rng, 1)))
        wal.seal()
        assert wal.record_count() == 4  # sealing moves bytes, not records
        wal.close()
        wal2, _ = WriteAheadLog.open(path)
        assert wal2.record_count() == 4  # recovered count survives reopen
        wal2.append(WalRecord(op="delete", ids=np.array([0], np.int64)))
        assert wal2.record_count() == 5
        wal2.close()


# -- basic mutability semantics ---------------------------------------------


class TestMutableBasics:
    def test_insert_delete_upsert_visibility(self, rng):
        mut = MutableIndex("brute_force", DIM)
        data = _rows(rng, 50)
        ids = mut.insert(data)
        assert mut.size == 50 and list(ids) == list(range(50))
        d, i = mut.search(data[:1], 1)
        assert i[0, 0] == 0
        assert mut.delete(ids[:10]) == 10
        assert mut.size == 40
        d, i = mut.search(data[:1], 5)
        assert not np.isin(i, ids[:10]).any()
        # upsert moves id 0's row far away, then exactly onto a query
        mut.upsert(np.array([0]), _rows(rng, 1))
        assert mut.size == 41
        probe = _rows(rng, 1)
        mut.upsert(np.array([0]), probe)
        assert mut.size == 41
        d, i = mut.search(probe, 1)
        assert i[0, 0] == 0 and d[0, 0] < 1e-4

    def test_duplicate_insert_rejected(self, rng):
        mut = MutableIndex("brute_force", DIM)
        mut.insert(_rows(rng, 2), ids=np.array([7, 9]))
        with pytest.raises(LogicError):
            mut.insert(_rows(rng, 1), ids=np.array([7]))
        mut.upsert(np.array([7]), _rows(rng, 1))  # the sanctioned path
        assert mut.size == 2

    def test_delete_unknown_id_is_noop(self, rng):
        mut = MutableIndex("brute_force", DIM)
        mut.insert(_rows(rng, 3))
        assert mut.delete(np.array([99])) == 0
        assert mut.size == 3

    def test_k_exceeding_size_pads(self, rng):
        mut = MutableIndex("brute_force", DIM)
        mut.insert(_rows(rng, 3))
        d, i = mut.search(_rows(rng, 2), 8)
        assert i.shape == (2, 8)
        assert (i[:, :3] >= 0).all() and (i[:, 3:] == -1).all()
        assert np.isinf(d[:, 3:]).all()

    def test_empty_index_search(self, rng):
        mut = MutableIndex("brute_force", DIM)
        d, i = mut.search(_rows(rng, 2), 4)
        assert (i == -1).all() and np.isinf(d).all()

    def test_snapshot_isolation(self, rng):
        mut = MutableIndex("brute_force", DIM)
        data = _rows(rng, 20)
        ids = mut.insert(data)
        snap = mut.snapshot()
        mut.delete(ids)  # wipe everything after the snapshot
        d, i = snap.search(data[:1], 1)
        assert i[0, 0] == 0  # the snapshot still sees the pre-delete world
        d2, i2 = mut.search(data[:1], 1)
        assert i2[0, 0] == -1

    def test_auto_ids_never_reused_after_reopen(self, rng, tmp_path):
        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM)
        ids = mut.insert(_rows(rng, 5))
        mut.delete(ids)
        mut.compact()
        mut.close()
        mut2 = MutableIndex.open(d, "brute_force", DIM)
        fresh = mut2.insert(_rows(rng, 1))
        assert fresh[0] == 5  # next_id persisted through the manifest
        mut2.close()


# -- crash-recovery chaos: kill at every seam, every mutation kind ----------


def _state(mut_or_dir, queries, k=5):
    """Search fingerprint used to compare pre/post/recovered states."""
    if isinstance(mut_or_dir, MutableIndex):
        d, i = mut_or_dir.search(queries, k)
    else:
        m = MutableIndex.open(mut_or_dir, "brute_force", DIM)
        try:
            d, i = m.search(queries, k)
        finally:
            m.close()
    return np.asarray(d), np.asarray(i)


def _same(a, b):
    return np.array_equal(a[1], b[1]) and np.allclose(a[0], b[0], rtol=1e-5, atol=1e-6)


class TestCrashChaos:
    """Kill at each seam; recovery must be pre- xor post-mutation."""

    # the rotated variant sets max_wal_bytes low enough that every
    # post-seed append triggers a segment rotation, so each seam kill
    # also exercises the rotation path (sealed prefix + fresh segment)
    @pytest.fixture(params=[None, 600], ids=["wal-single", "wal-rotated"])
    def seeded(self, rng, tmp_path, request):
        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM, max_wal_bytes=request.param)
        self.data = _rows(rng, 64)
        self.ids = mut.insert(self.data)
        mut.compact()  # main segment populated, empty delta
        self.extra = mut.insert(_rows(rng, 8))
        self.queries = _rows(rng, 4)
        return d, mut

    def _mutations(self, rng):
        up_rows = _rows(rng, 3)  # pinned: the same rows on every call
        return {
            "insert": lambda m: m.insert(self.data[:3] + 0.25),
            "delete": lambda m: m.delete(np.concatenate([self.ids[:5], self.extra[:2]])),
            "upsert": lambda m: m.upsert(
                np.array([int(self.ids[1]), int(self.extra[0]), 999]),
                up_rows,
            ),
        }

    @pytest.mark.parametrize("op", ["insert", "delete", "upsert"])
    @pytest.mark.parametrize("stage", ["pre", "post"])
    def test_kill_in_wal_append(self, rng, seeded, op, stage):
        d, mut = seeded
        mutate = self._mutations(rng)[op]
        pre = _state(mut, self.queries)
        # compute the post state on a scratch copy of the directory
        # via an in-memory replica fed the same mutation
        replica = MutableIndex("brute_force", DIM)
        live_ids, live_vecs = mut.live_rows()
        replica.insert(live_vecs, ids=live_ids)
        replica.next_id = mut.next_id
        mutate(replica)
        post = _state(replica, self.queries)
        with faults.injected("wal.append", Kill("die"), match={"stage": stage}):
            with pytest.raises(Kill):
                mutate(mut)
        mut.close()  # the "process" is gone; reopen cold from disk
        got = _state(d, self.queries)
        if stage == "pre":
            assert _same(got, pre), "pre-stage kill must recover pre-state"
        else:
            assert _same(got, post), "post-fsync kill must recover post-state"
        assert _same(got, pre) or _same(got, post)

    @pytest.mark.parametrize("seam", ["compact.merge", "manifest.swap"])
    def test_kill_in_compaction(self, rng, seeded, seam):
        d, mut = seeded
        # apply one of each mutation kind first so the recovered WAL
        # replay covers insert+delete+upsert together
        for mutate in self._mutations(rng).values():
            mutate(mut)
        pre = _state(mut, self.queries)
        gen_before = mut.generation
        with faults.injected(seam, Kill("die")):
            with pytest.raises(Kill):
                mut.compact()
        mut.close()
        m2 = MutableIndex.open(d, "brute_force", DIM)
        try:
            assert m2.generation == gen_before, "failed compaction must not flip generations"
            got = _state(m2, self.queries)
        finally:
            m2.close()
        assert _same(got, pre), "killed compaction must recover the pre-state"

    def test_kill_after_swap_recovers_post_state(self, rng, seeded):
        d, mut = seeded
        for mutate in self._mutations(rng).values():
            mutate(mut)
        pre = _state(mut, self.queries)
        gen_before = mut.generation
        # kill *after* the rename: nth=1 fires on the swap's... the swap
        # seam fires before os.replace, so simulate the crash after
        # publish by killing the old-generation cleanup instead: compact
        # normally, then damage nothing — reopen must be post-state
        mut.compact()
        mut.close()
        m2 = MutableIndex.open(d, "brute_force", DIM)
        try:
            assert m2.generation == gen_before + 1
            got = _state(m2, self.queries)
        finally:
            m2.close()
        assert _same(got, pre), "compaction must preserve the visible state"

    def test_orphan_generation_files_are_ignored(self, rng, seeded):
        d, mut = seeded
        with faults.injected("manifest.swap", Kill("die")):
            with pytest.raises(Kill):
                mut.compact()
        mut.close()
        # the orphaned gen-2 dir from the failed publish is present…
        assert os.path.isdir(os.path.join(d, "gen-00000002"))
        # …a cold open ignores it (manifest still names gen 1), and the
        # retried compaction reclaims the same generation number
        m2 = MutableIndex.open(d, "brute_force", DIM)
        try:
            assert m2.generation == 1
            assert m2.compact() == 2
        finally:
            m2.close()


# -- background compaction: serve through rebuilds, never under them --------


class TestBackgroundCompaction:
    """The maintenance path's acceptance gate: kills at each new seam
    with insert/delete/upsert arriving mid-rebuild recover exactly the
    pre-flip state *including* those mutations (never a hybrid), a
    completed flip equals a fresh rebuild over the final live rows, a
    dead worker is restarted without losing its request, and transient
    faults are retried (counted) rather than surfaced."""

    # the rotated variant forces the catch-up replay to read mid-rebuild
    # records across a WAL segment rotation, not just the active tail
    @pytest.fixture(params=[None, 600], ids=["wal-single", "wal-rotated"])
    def seeded(self, rng, tmp_path, request):
        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM, max_wal_bytes=request.param)
        self.data = _rows(rng, 64)
        self.ids = mut.insert(self.data)
        mut.compact()  # main segment populated, small live delta
        self.extra = mut.insert(_rows(rng, 8))
        self.queries = _rows(rng, 4)
        return d, mut

    @pytest.fixture
    def obs_reg(self):
        reg = obs.registry()
        reg.reset()
        obs.enable()
        yield reg
        obs.disable()
        reg.reset()

    def _mid_mutations(self, rng, mut):
        """Mutations applied *mid-rebuild* (between the pin and the
        catch-up replay) — the backlog the flip must carry over."""
        up_rows = _rows(rng, 3)  # pinned: the same rows on every call
        return {
            "insert": lambda: mut.insert(self.data[:3] + 0.25),
            "delete": lambda: mut.delete(
                np.concatenate([self.ids[:5], self.extra[:2]])
            ),
            "upsert": lambda: mut.upsert(
                np.array([int(self.ids[1]), int(self.extra[0]), 999]), up_rows
            ),
        }

    # -- freshness: the flip equals a fresh rebuild --------------------------

    @pytest.mark.parametrize("op", ["insert", "delete", "upsert", "mixed"])
    def test_flip_equals_fresh_rebuild_over_final_rows(self, rng, seeded, op):
        d, mut = seeded
        mid = self._mid_mutations(rng, mut)
        names = ["insert", "delete", "upsert"] if op == "mixed" else [op]
        ran = []

        def hook():
            for name in names:
                mid[name]()
            ran.append(True)

        new_gen = compact_background(mut, _mid_rebuild=hook)
        assert ran and new_gen == mut.generation == 2
        got = _state(mut, self.queries)
        # a fresh index over the final live rows (pinned + replayed, in
        # the index's stable order) must agree: same neighbors, same
        # distances up to the delta-vs-main evaluation route
        live_ids, live_vecs = mut.live_rows()
        fresh = MutableIndex("brute_force", DIM)
        fresh.insert(live_vecs, ids=live_ids)
        want = _state(fresh, self.queries)
        assert np.array_equal(got[1], want[1])
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
        # durable: a cold reopen sees the flipped state…
        mut.close()
        assert _same(_state(d, self.queries), got)
        # …and compacting both sides folds identical rows in identical
        # order through the same builder — bit-for-bit equal
        m2 = MutableIndex.open(d, "brute_force", DIM)
        try:
            m2.compact()
            fresh.compact()
            d1, i1 = m2.search(self.queries, 5)
            d2, i2 = fresh.search(self.queries, 5)
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
            np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        finally:
            m2.close()

    # -- chaos matrix: kill at each new seam × each mutation kind ------------

    @pytest.mark.parametrize("op", ["insert", "delete", "upsert"])
    @pytest.mark.parametrize(
        "seam", ["compact.pin", "compact.replay", "compact.flip", "manifest.swap"]
    )
    def test_kill_at_seam_recovers_pre_flip_state(self, rng, seeded, seam, op):
        d, mut = seeded
        mid = self._mid_mutations(rng, mut)[op]
        ran = []

        def hook():
            mid()
            ran.append(True)

        gen_before = mut.generation
        with faults.injected(seam, Kill("die")):
            with pytest.raises(Kill):
                compact_background(mut, _mid_rebuild=hook)
        # a pin-seam kill dies before the rebuild starts, so the
        # mid-rebuild mutation never ran; every later seam saw it
        assert bool(ran) == (seam != "compact.pin")
        assert mut.generation == gen_before, "failed flip must not change generations"
        # the live object still serves the pre-flip state (old main +
        # delta, including the mid-rebuild mutation when it ran); cold
        # recovery must reproduce exactly that — never a hybrid
        expected = _state(mut, self.queries)
        mut.close()
        got = _state(d, self.queries)
        assert _same(got, expected), (
            f"kill at {seam} with mid-rebuild {op}: cold recovery diverged "
            "from the pre-flip state"
        )
        # the retried compaction reclaims the same generation number
        # (stale catch-up WAL segments from the dead attempt are cleared)
        m2 = MutableIndex.open(d, "brute_force", DIM)
        try:
            assert m2.generation == gen_before
            assert m2.compact() == gen_before + 1
            assert _same(_state(m2, self.queries), expected)
        finally:
            m2.close()

    # -- writers proceed while the rebuild runs ------------------------------

    def test_writers_not_blocked_during_rebuild(self, rng, seeded):
        d, mut = seeded
        comp = Compactor(mut, poll_interval_s=0.002)
        comp.start()
        probe = _rows(rng, 1)
        try:
            # a 0.5 s latency at compact.merge stretches phase 2 (the
            # off-lock rebuild) long enough to write into it
            with faults.injected("compact.merge", latency_s=0.5):
                assert comp.request()
                deadline = time.monotonic() + 5.0
                while mut._capture is None and time.monotonic() < deadline:
                    time.sleep(0.001)
                assert mut._capture is not None, "worker never pinned"
                t0 = time.monotonic()
                new_id = mut.insert(probe)  # lands mid-rebuild
                dt = time.monotonic() - t0
                assert dt < 0.25, f"writer blocked {dt:.3f}s behind the rebuild"
                assert comp.wait_idle(timeout_s=30.0)
        finally:
            comp.stop()
        assert comp.completed == 1 and comp.failed == 0
        assert mut.generation == 2
        # the mid-rebuild insert survived the flip via the catch-up replay
        dd, ii = mut.search(probe, 1)
        assert ii[0, 0] == new_id[0] and dd[0, 0] < 1e-4
        mut.close()

    # -- worker death: the watchdog restarts, the request survives -----------

    def test_worker_death_restarted_without_losing_request(self, rng, seeded):
        d, mut = seeded
        comp = Compactor(mut, poll_interval_s=0.002)
        # the injected death escapes the worker loop by design; silence
        # the default excepthook so the expected traceback stays out of
        # the test log
        old_hook = threading.excepthook
        threading.excepthook = lambda args: None
        try:
            comp.start()
            with faults.injected(
                "compact.worker", Kill("die"), trigger="first_n", first_n=1
            ):
                assert comp.request()
                assert comp.wait_idle(timeout_s=30.0)
        finally:
            threading.excepthook = old_hook
            comp.stop()
        assert comp.worker_restarts == 1
        assert comp.completed == 1 and comp.failed == 0
        assert mut.generation == 2
        mut.close()

    # -- retries: transient faults recover, terminal ones are reported -------

    def test_transient_fault_retried_in_background(self, rng, seeded, obs_reg):
        d, mut = seeded
        comp = Compactor(mut, poll_interval_s=0.002)
        comp.start()
        try:
            with faults.injected(
                "compact.merge", Kill("flaky"), trigger="first_n", first_n=1
            ):
                assert comp.request()
                assert comp.wait_idle(timeout_s=30.0)
        finally:
            comp.stop()
        assert comp.completed == 1 and comp.failed == 0
        assert comp.last_error is None and mut.generation == 2
        counters = obs_reg.as_dict()["counters"]
        retried = [
            v for k, v in counters.items()
            if k.startswith("mutable.compact.retries") and 'mode="background"' in k
        ]
        assert sum(retried) == 1
        mut.close()

    def test_sync_compact_retries_through_seeded_backoff(self, rng, seeded, obs_reg):
        d, mut = seeded
        with faults.injected(
            "compact.merge", Kill("flaky"), trigger="first_n", first_n=1
        ):
            assert mut.compact() == 2
        counters = obs_reg.as_dict()["counters"]
        retried = [
            v for k, v in counters.items()
            if k.startswith("mutable.compact.retries") and 'mode="sync"' in k
        ]
        assert sum(retried) == 1
        mut.close()

    def test_terminal_failure_reported_then_recovers(self, rng, seeded, obs_reg):
        d, mut = seeded
        comp = Compactor(mut, poll_interval_s=0.002)
        comp.start()
        try:
            with faults.injected("compact.flip", Kill("die")):
                assert comp.request()
                assert comp.wait_idle(timeout_s=30.0)
            # every attempt failed: reported (typed, counted), index
            # still live and serving the old generation
            assert comp.failed == 1 and isinstance(comp.last_error, Kill)
            assert mut.generation == 1
            before = _state(mut, self.queries)
            # the fault gone, the same worker completes the next request
            assert comp.request()
            assert comp.wait_idle(timeout_s=30.0)
        finally:
            comp.stop()
        assert comp.completed == 1 and comp.last_error is None
        assert mut.generation == 2
        assert _same(_state(mut, self.queries), before)
        counters = obs_reg.as_dict()["counters"]
        failed = [
            v for k, v in counters.items()
            if k.startswith("mutable.compact.failed") and 'error="Kill"' in k
        ]
        assert sum(failed) == 1
        mut.close()

    # -- auto-compaction policy ----------------------------------------------

    def test_policy_reason_triggers(self, rng, seeded):
        d, mut = seeded  # 8 live delta rows, a durable WAL, no tombstones
        assert CompactionPolicy().reason(mut) is None
        assert CompactionPolicy(delta_rows=9).reason(mut) is None
        assert CompactionPolicy(delta_rows=8).reason(mut) == "delta_rows"
        # a fraction threshold never trips on a tombstone-free index…
        assert CompactionPolicy(tombstone_fraction=0.0).reason(mut) is None
        mut.delete(self.ids[:8])
        # …and fires once deletes accumulate past it
        assert (
            CompactionPolicy(tombstone_fraction=0.05).reason(mut)
            == "tombstone_fraction"
        )
        assert CompactionPolicy(wal_bytes=1).reason(mut) == "wal_bytes"
        assert CompactionPolicy(wal_bytes=10**15).reason(mut) is None
        mut.close()
        # wal_bytes never trips on an in-memory (WAL-less) index
        mem = MutableIndex("brute_force", DIM)
        mem.insert(_rows(rng, 4))
        assert CompactionPolicy(wal_bytes=1).reason(mem) is None

    def test_tick_policy_trigger_and_min_interval(self, rng, seeded, obs_reg):
        d, mut = seeded
        clk = [0.0]
        comp = Compactor(
            mut,
            policy=CompactionPolicy(delta_rows=4, min_interval_s=100.0),
            poll_interval_s=0.002,
            clock=lambda: clk[0],
        )
        comp.start()
        try:
            assert comp.tick() == "delta_rows"  # 8 delta rows >= 4
            assert comp.wait_idle(timeout_s=30.0)
            assert comp.completed == 1 and mut.generation == 2
            mut.insert(_rows(rng, 6))  # re-trip the trigger…
            assert comp.tick() is None  # …rate-limited by min_interval_s
            clk[0] += 101.0
            assert comp.tick() == "delta_rows"
            assert comp.wait_idle(timeout_s=30.0)
        finally:
            comp.stop()
        assert comp.completed == 2 and mut.generation == 3
        gauges = obs_reg.as_dict()["gauges"]
        assert any(k.startswith("mutable.compact.backlog") for k in gauges)
        assert any(k.startswith("mutable.maintenance.heartbeat") for k in gauges)
        mut.close()


# -- freshness: mutable search vs fresh rebuild -----------------------------


class TestFreshness:
    def test_pre_compaction_recall(self, rng):
        """After N inserts + M deletes with an ANN main segment, the
        delta-brute-force + tombstone path stays within recall 0.95 of
        exact ground truth over the live rows."""
        from raft_tpu.neighbors import ivf_flat

        n, n_extra, n_del, k = 1500, 120, 200, 10
        data = _rows(rng, n)
        params = ivf_flat.IvfFlatIndexParams(n_lists=16)
        sparams = ivf_flat.IvfFlatSearchParams(n_probes=16)
        mut = MutableIndex("ivf_flat", DIM, index_params=params, search_params=sparams)
        ids = mut.insert(data)
        mut.compact()
        extra = mut.insert(_rows(rng, n_extra))
        dead = np.asarray(
            np.concatenate([ids[: n_del // 2], extra[: n_del // 4]])
        )
        mut.delete(dead)
        queries = _rows(rng, 32)
        d, got = mut.search(queries, k)
        # exact ground truth over the live rows
        live_ids, live_vecs = mut.live_rows()
        from raft_tpu.neighbors import brute_force

        bf = brute_force.build(live_vecs)
        _, pos = brute_force.search(bf, queries, k, mode="exact")
        want = live_ids[np.asarray(pos)]
        recall = np.mean([
            len(set(got[i]) & set(want[i])) / k for i in range(len(queries))
        ])
        assert recall >= 0.95, recall
        assert not np.isin(got, dead).any()

    @pytest.mark.parametrize("algo", ["brute_force", "ivf_flat", "ivf_pq"])
    def test_post_compaction_bit_for_bit(self, rng, algo):
        """Post-compaction search must equal a from-scratch build over
        the live rows exactly — same distances, same neighbors."""
        mut = MutableIndex(algo, DIM)
        data = _rows(rng, 400)
        ids = mut.insert(data)
        mut.compact()
        mut.insert(_rows(rng, 40))
        mut.delete(ids[::7])
        mut.compact()
        queries = _rows(rng, 8)
        k = 10
        d_mut, i_mut = mut.search(queries, k)
        live_ids, live_vecs = mut.live_rows()
        fresh = MutableIndex(algo, DIM)
        fresh.insert(live_vecs, ids=live_ids)
        fresh.compact()
        d_ref, i_ref = fresh.search(queries, k)
        np.testing.assert_array_equal(i_mut, i_ref)
        np.testing.assert_array_equal(d_mut, d_ref)


# -- delta-segment fused fast path ------------------------------------------


class TestDeltaFusedScan:
    """The fused single-list kernel route must be candidate-exact
    against the plain-XLA brute-force delta scan inside its
    eligibility window (padded delta <= 1024 rows, L2/IP metrics)."""

    def _churned(self, rng, metric):
        mut = MutableIndex("brute_force", DIM, metric=metric)
        base = _rows(rng, 200)
        bids = mut.insert(base)
        mut.compact()  # main segment, then grow a delta with tombstones
        extra = mut.insert(_rows(rng, 90))
        mut.delete(np.concatenate([bids[:10], extra[:7]]))
        return mut

    @pytest.mark.parametrize("metric", ["l2", "l2sqrt", "ip"])
    def test_fused_matches_exact_bitwise(self, rng, metric):
        from raft_tpu.ops.distance import DistanceType

        m = {
            "l2": DistanceType.L2Expanded,
            "l2sqrt": DistanceType.L2SqrtExpanded,
            "ip": DistanceType.InnerProduct,
        }[metric]
        mut = self._churned(rng, m)
        queries = _rows(rng, 33)  # odd count exercises the qt padding
        snap = mut.snapshot()
        d_ex, i_ex = dataclasses.replace(snap, delta_mode="exact").search(queries, 10)
        d_fu, i_fu = dataclasses.replace(snap, delta_mode="fused").search(queries, 10)
        np.testing.assert_array_equal(i_ex, i_fu)
        np.testing.assert_allclose(d_ex, d_fu, rtol=1e-6, atol=1e-6)

    def test_index_level_knob(self, rng):
        mut = MutableIndex("brute_force", DIM, delta_mode="fused")
        ids = mut.insert(_rows(rng, 50))
        mut.delete(ids[:5])
        queries = _rows(rng, 4)
        d, i = mut.search(queries, 8)
        # rebuild the same state on the exact route
        live_ids, live_vecs = mut.live_rows()
        ref2 = MutableIndex("brute_force", DIM, delta_mode="exact")
        ref2.insert(live_vecs, ids=live_ids)
        d2, i2 = ref2.search(queries, 8)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(d), np.asarray(d2), rtol=1e-6, atol=1e-6)

    def test_kernel_failure_degrades_counted(self, rng, monkeypatch):
        """A failing fused delta kernel degrades to the exact scan — same
        answer — and the degrade is counted under ``fallbacks``."""
        from raft_tpu import obs
        from raft_tpu.core.errors import KernelFailure
        from raft_tpu.mutable import segments
        from raft_tpu.robust import reset_warned

        def broken(*args, **kwargs):
            raise KernelFailure("injected")

        mut = MutableIndex("brute_force", DIM, delta_mode="fused")
        mut.insert(_rows(rng, 50))
        queries = _rows(rng, 4)
        d_ref, i_ref = dataclasses.replace(mut.snapshot(), delta_mode="exact").search(
            queries, 8)
        monkeypatch.setattr(segments, "_delta_fused_search", broken)
        obs.enable()
        obs.registry().reset()
        reset_warned()
        try:
            with pytest.warns(RuntimeWarning, match="falling back"):
                d, i = mut.search(queries, 8)
            counts = obs.registry().as_dict()["counters"]
        finally:
            obs.disable()
            obs.registry().reset()
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
        assert counts['fallbacks{algo="mutable_delta",reason="KernelFailure"}'] == 1.0

    def test_routing_and_eligibility(self):
        from raft_tpu.mutable.segments import _delta_route
        from raft_tpu.ops.distance import DistanceType

        from raft_tpu.mutable.segments import (
            _DELTA_FUSED_MAX_BANKS,
            _DELTA_FUSED_MAX_ROWS,
        )

        l2 = DistanceType.L2Expanded
        over = _DELTA_FUSED_MAX_ROWS * _DELTA_FUSED_MAX_BANKS * 2
        assert _delta_route("exact", l2, 256, 10) == "exact"
        assert _delta_route("fused", l2, 1024, 10) == "fused"
        # past one bank the scan tiles — still fused, still lossless
        assert _delta_route("fused", l2, 2048, 10) == "fused"
        # over the banked window, auto falls back to exact
        assert _delta_route("auto", l2, over, 10) == "exact"
        with pytest.raises(LogicError):
            _delta_route("fused", l2, over, 10)  # forced but ineligible
        with pytest.raises(LogicError):
            _delta_route("fused", l2, 256, 300)  # k past one extract width
        with pytest.raises(LogicError):
            _delta_route("bogus", l2, 256, 10)
        with pytest.raises(LogicError):
            MutableIndex("brute_force", DIM, delta_mode="bogus")

    def test_fused_respects_tombstones_and_padding(self, rng):
        """Dead and padding rows must never surface: delete everything
        but 3 delta rows, ask for more than survive."""
        from raft_tpu.ops.distance import DistanceType

        mut = MutableIndex("brute_force", DIM, metric=DistanceType.L2Expanded)
        ids = mut.insert(_rows(rng, 40))
        mut.delete(ids[3:])
        snap = dataclasses.replace(mut.snapshot(), delta_mode="fused")
        d, i = snap.search(_rows(rng, 2), 8)
        assert set(np.asarray(i)[:, :3].ravel()) <= {0, 1, 2}
        assert (np.asarray(i)[:, 3:] == -1).all()
        assert np.isinf(np.asarray(d)[:, 3:]).all()

    @pytest.mark.parametrize("metric", ["l2", "l2sqrt", "ip"])
    def test_banked_fused_matches_exact_past_one_bank(self, rng, metric):
        """The fused path must stay engaged past the old 1024-row cap:
        a 1300-row delta pads to 2048 -> two banks, and the banked
        k-way merge must reproduce the exact scan's ids bit-for-bit
        (tombstones included)."""
        from raft_tpu.ops.distance import DistanceType

        m = {
            "l2": DistanceType.L2Expanded,
            "l2sqrt": DistanceType.L2SqrtExpanded,
            "ip": DistanceType.InnerProduct,
        }[metric]
        mut = MutableIndex("brute_force", DIM, metric=m)
        ids = mut.insert(_rows(rng, 1300))
        mut.delete(ids[5:45])
        queries = _rows(rng, 33)
        snap = mut.snapshot()
        assert int(snap.delta_bf.size) > 1024  # really multi-bank
        d_ex, i_ex = dataclasses.replace(snap, delta_mode="exact").search(queries, 10)
        d_fu, i_fu = dataclasses.replace(snap, delta_mode="fused").search(queries, 10)
        np.testing.assert_array_equal(np.asarray(i_ex), np.asarray(i_fu))
        np.testing.assert_allclose(
            np.asarray(d_ex), np.asarray(d_fu), rtol=1e-6, atol=1e-6
        )

    def test_banked_fused_publishes_bank_gauge(self, rng):
        from raft_tpu.ops.distance import DistanceType

        mut = MutableIndex("brute_force", DIM, metric=DistanceType.L2Expanded)
        mut.insert(_rows(rng, 1300))
        snap = dataclasses.replace(mut.snapshot(), delta_mode="fused")
        obs.enable()
        try:
            snap.search(_rows(rng, 3), 5)
            gauges = obs.registry().as_dict()["gauges"]
            assert gauges["mutable.delta.banks"] == 2.0
        finally:
            obs.disable()
            obs.registry().reset()


# -- snapshot-consistent serving + bounded recompiles -----------------------


class TestServingIntegration:
    def test_generation_in_results_and_bounded_recompiles(self, rng):
        from raft_tpu.serve.bucketing import bucket_sizes
        from raft_tpu.serve.engine import ServingEngine

        mut = MutableIndex("brute_force", DIM)
        data = _rows(rng, 128)
        mut.insert(data)
        mut.compact()
        eng = ServingEngine(max_batch=8, max_wait_ms=0.0)
        eng.register_mutable("live", mut)
        n_buckets = len(bucket_sizes(8))  # log2(8)+1 = 4
        generations = 3
        sizes = [1, 3, 5, 8, 2, 7]
        for _ in range(generations):
            for m in sizes:
                fut = eng.submit("live", _rows(rng, m), k=5)
                eng.run_until_idle()
                res = fut.result()
                assert res.generation == mut.generation
            mut.insert(_rows(rng, 4))
            mut.compact()
        stats = eng.cache.stats()
        assert stats.distinct_programs <= (generations + 1) * n_buckets, stats

    def test_batch_sees_one_snapshot(self, rng):
        """Mutations between submit and dispatch are invisible to the
        already-snapshotted batch only if dispatch snapshots once —
        requests dispatched together must agree on the generation."""
        from raft_tpu.serve.engine import ServingEngine

        mut = MutableIndex("brute_force", DIM)
        data = _rows(rng, 32)
        ids = mut.insert(data)
        mut.compact()
        eng = ServingEngine(max_batch=8, max_wait_ms=1e6)  # hold the batch
        eng.register_mutable("live", mut)
        futs = [eng.submit("live", data[i : i + 1], k=1) for i in range(4)]
        mut.delete(ids[:16])  # mutate while queued
        eng.run_until_idle()
        gens = {f.result().generation for f in futs}
        assert len(gens) == 1
        # all four saw the post-delete snapshot (taken at dispatch)
        for i, f in enumerate(futs[:2]):
            assert f.result().indices[0, 0] != ids[i]


# -- serialize satellites ---------------------------------------------------


class TestSerializeForensics:
    def _stream(self, body=b"payload-bytes", kind="brute_force"):
        import io

        buf = io.BytesIO()
        ser.save_stream(buf, kind, 1, body)
        return buf

    def test_crc_mismatch_carries_offset_and_crcs(self):
        buf = self._stream()
        raw = bytearray(buf.getvalue())
        raw[-3] ^= 0x40  # flip a payload bit
        import io

        with pytest.raises(CorruptIndexError) as ei:
            ser.load_stream(io.BytesIO(bytes(raw)), "brute_force")
        e = ei.value
        assert e.offset is not None and e.offset > 0
        assert e.expected_crc is not None and e.actual_crc is not None
        assert e.expected_crc != e.actual_crc
        assert f"0x{e.expected_crc:08x}" in str(e)
        assert f"offset={e.offset}" in str(e)

    def test_truncation_carries_offset(self):
        buf = self._stream()
        raw = buf.getvalue()[:-4]
        import io

        with pytest.raises(CorruptIndexError) as ei:
            ser.load_stream(io.BytesIO(raw), "brute_force")
        e = ei.value
        assert e.offset is not None
        assert e.expected_crc is None and e.actual_crc is None
        assert "truncated" in str(e)

    def test_legacy_v3_stream_loads_from_manifest(self, rng, tmp_path):
        """A pre-v4 (unchecksummed) main-segment snapshot referenced by
        a new-style manifest still opens: the envelope dispatches on the
        preamble version, so old artifacts survive the manifest era."""
        import io

        from raft_tpu.neighbors import brute_force

        d = str(tmp_path / "idx")
        os.makedirs(os.path.join(d, "gen-00000001"))
        data = _rows(rng, 40)
        idx = brute_force.build(data)
        # legacy framing: v3 preamble + raw body, no length/CRC envelope
        body = io.BytesIO()
        brute_force._write_body(idx, body)
        legacy = io.BytesIO()
        ser.dump_header(legacy, "brute_force", 3)
        legacy.write(body.getvalue())
        main_rel = os.path.join("gen-00000001", "main.idx")
        with open(os.path.join(d, main_rel), "wb") as f:  # graft-lint: ignore[non-atomic-write] — crafting a legacy fixture
            f.write(legacy.getvalue())
        # rows sidecar + manifest are new-style
        from raft_tpu.mutable.segments import _save_rows

        rows_rel = os.path.join("gen-00000001", "rows.bin")
        _save_rows(os.path.join(d, rows_rel),
                   np.arange(40, dtype=np.int64), data)
        man.swap(d, man.Manifest(
            generation=1, algo="brute_force", dim=DIM,
            main=main_rel, rows=rows_rel, wal="wal-00000001.log", next_id=40,
        ))
        mut = MutableIndex.open(d, "brute_force", DIM)
        try:
            assert mut.generation == 1 and mut.size == 40
            dd, ii = mut.search(data[:2], 1)
            np.testing.assert_array_equal(ii[:, 0], [0, 1])
        finally:
            mut.close()


class TestManifest:
    def test_newer_format_rejected(self, tmp_path):
        m = man.Manifest(generation=1, algo="brute_force", dim=4,
                         main=None, rows=None, wal="wal-1.log")
        doc = m.to_json().replace('"format": 1', '"format": 99')
        with pytest.raises(ValueError):
            man.Manifest.from_json(doc)

    def test_swap_is_atomic_under_kill(self, tmp_path):
        d = str(tmp_path)
        m1 = man.Manifest(generation=1, algo="brute_force", dim=4,
                          main=None, rows=None, wal="w1")
        man.swap(d, m1)
        m2 = man.Manifest(generation=2, algo="brute_force", dim=4,
                          main=None, rows=None, wal="w2")
        with faults.injected("manifest.swap", Kill("die")):
            with pytest.raises(Kill):
                man.swap(d, m2)
        got = man.read(d)
        assert got is not None and got.generation == 1  # old pointer intact
        assert not [p for p in os.listdir(d) if p.endswith(".tmp%d" % os.getpid())]


class TestCleanupOffLock:
    """Superseded-generation deletion must run *after* the index lock is
    released: rmtree + WAL unlinks are corpus-proportional filesystem
    work, and holding ``_lock`` across them stalls every writer and
    searcher (the bug the interprocedural ``blocking-under-lock`` rule
    found at its first run over the tree). ``_switch_memory`` therefore
    returns the cleanup arguments instead of deleting inline; these
    tests pin that contract for both compaction paths."""

    def _probe_lock_free(self, mut, witness):
        """Called while cleanup runs: from another thread, the index
        lock must be acquirable (RLock reentrancy makes a same-thread
        probe vacuous, so the probe *must* cross threads)."""
        got = []

        def probe():
            ok = mut._lock.acquire(timeout=2.0)
            got.append(ok)
            if ok:
                mut._lock.release()

        t = threading.Thread(target=probe)
        t.start()
        t.join()
        witness.append(bool(got and got[0]))

    @pytest.mark.parametrize("path", ["sync", "background"])
    def test_old_generation_deleted_off_lock(self, rng, tmp_path, monkeypatch, path):
        import importlib

        # the package re-exports the compact *function*, which shadows
        # the submodule attribute — go through importlib
        compact_mod = importlib.import_module("raft_tpu.mutable.compact")
        maint_mod = importlib.import_module("raft_tpu.mutable.maintenance")
        from raft_tpu.mutable import segments as seg

        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM)
        mut.insert(_rows(rng, 48))
        mut.compact()  # generation 1 on disk
        mut.insert(_rows(rng, 8))
        old_dir = os.path.join(d, seg._gen_dirname(mut.generation))
        assert os.path.isdir(old_dir)

        lock_free_during_cleanup = []
        calls = []
        real = compact_mod._cleanup_old_generation

        def spy(directory, old_gen, old_wal_path):
            self._probe_lock_free(mut, lock_free_during_cleanup)
            calls.append((directory, old_gen))
            real(directory, old_gen, old_wal_path)

        # each caller binds the helper into its own namespace
        monkeypatch.setattr(compact_mod, "_cleanup_old_generation", spy)
        monkeypatch.setattr(maint_mod, "_cleanup_old_generation", spy)

        gen = mut.compact() if path == "sync" else mut.compact_background()
        assert calls == [(d, gen - 1)]
        assert lock_free_during_cleanup == [True], (
            "cleanup ran while the index lock was held — writers and "
            "searchers were stalled behind corpus-proportional rmtree"
        )
        assert not os.path.isdir(old_dir), "old generation must still be deleted"
        mut.close()

    def test_switch_memory_returns_cleanup_args_not_side_effects(self, rng, tmp_path):
        # the in-memory flip itself must never delete anything: it hands
        # the cleanup triple back to the caller
        from raft_tpu.mutable import segments as seg

        d = str(tmp_path / "idx")
        mut = MutableIndex.open(d, "brute_force", DIM)
        mut.insert(_rows(rng, 16))
        gen_before = mut.generation
        mut.compact()
        # in-memory-only index: nothing on disk to clean, returns None
        mem = MutableIndex("brute_force", DIM)
        mem.insert(_rows(rng, 4))
        from raft_tpu.mutable.compact import _switch_memory

        ids, vecs = mem.live_rows()
        with mem._lock:
            assert _switch_memory(mem, mem.generation + 1, ids, vecs, None) is None
        assert os.path.isdir(os.path.join(d, seg._gen_dirname(gen_before + 1)))
        mut.close()
