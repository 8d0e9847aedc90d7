"""Elasticity under failure (DESIGN.md §15): preemption-driven shrink,
chunked checkpoint recovery, and the fault-injection drill.

Four layers of proof, cheapest first:

* **Units** — ``LeaseBoard`` liveness semantics on an injected fake clock;
  ``ElasticController.report_failure`` (k_min floor, FailureEvent sequenced
  before the shrink, both autoscaler cooldown windows armed); partition-
  scoped ``restore_partitions`` bit-equality against the live lost ranges.
* **Staleness boundaries** — kill at the batch AFTER a snapshot, kill
  mid-rebuild-flight (the flight is NOT survived; the ladder re-fires),
  kill during a rescale commit (torn WAL barrier ⇒ fall back to the
  pre-scale state). Replay-tail lengths (``wal_steps``) are pinned.
* **Property** — hypothesis races ingest / rescale / async-rebuild / kill
  interleavings; every run must end bit-identical to a no-failure oracle
  that executed the same decisions without losing state, with each
  controller generation's shared seq strictly monotonic (FailureEvents
  included). A fixed-interleaving test covers the same executor when
  hypothesis is absent (house style: conftest.hypothesis_or_stub).
* **The drill** (subprocess, CI multihost job) — a real SIGKILL of one
  process of a 2×4 cluster mid-stream: lease-expiry detection from the
  parent, group reaped with the victim's partial log surfaced, a fresh
  1×4 recovery cluster restoring from the checkpoint directory and
  continuing — final order byte-identical to the host oracle.
"""
import json
import os
import sys
import time

import numpy as np
import pytest

from conftest import hypothesis_or_stub
from repro.checkpoint import CheckpointError, SlotCheckpoint
from repro.elastic import controller as ec
from repro.elastic.autoscale import AutoscaleConfig, AutoscalePolicy
from repro.launch import multihost as MH
from repro.stream import IncrementalOrderer, StreamingEngine, SyntheticStream
from repro.stream.incremental import StreamConfig

import faults_harness as FH

given, settings, st = hypothesis_or_stub()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_PROCS = 2
DEVS_PER_PROC = 4
# Long enough that the checkpoint-writing survivor is still mid-stream when
# the parent abandons the group (kill at ~step 4 + ~2s lease expiry at 4
# batches/s ≈ step 13): recovery must genuinely replay a tail.
DRILL_BATCHES = 20
KILL_STEP = 4

_UNSUPPORTED_MARKERS = (
    "gloo",
    "cpu_collectives",
    "collectives_implementation",
    "Unable to initialize backend",
    "UNIMPLEMENTED",
    "DEADLINE_EXCEEDED",
)
_BOOTSTRAP_BANNER = "global devices"


# --------------------------------------------------------------- LeaseBoard
class TestLeaseBoard:
    def test_stamp_age_dead(self, tmp_path):
        clk = [0.0]
        board = MH.LeaseBoard(tmp_path, lease_s=1.0, clock=lambda: clk[0])
        board.stamp(0, 5)
        clk[0] = 0.5
        assert board.dead(2) == []  # p1 never stamped but is younger than t0+1s
        assert board.step(0) == 5 and board.step(1) == -1
        clk[0] = 1.5
        assert board.dead(2) == [0, 1]  # frozen stamp ages like silence
        board.stamp(0, 6)
        assert board.dead(2) == [1]
        assert board.survivors(2) == [0]

    def test_surviving_devices_process_major(self, tmp_path):
        clk = [10.0]
        board = MH.LeaseBoard(tmp_path, lease_s=1.0, clock=lambda: clk[0])
        board.stamp(0, 0)
        board.stamp(1, 0)
        clk[0] = 10.5
        board.stamp(0, 1)  # p1's lease now freezes
        clk[0] = 11.2  # p0 age 0.7 (alive), p1 age 1.2 (expired)
        assert board.survivors(2) == [0]
        assert board.surviving_devices(2, 4) == [0, 1, 2, 3]

    def test_torn_lease_reads_as_never_stamped(self, tmp_path):
        clk = [0.0]
        board = MH.LeaseBoard(tmp_path, lease_s=1.0, clock=lambda: clk[0])
        (tmp_path / "lease_p0.json").write_text('{"step": 3, "t"')  # torn
        assert board.read(0) is None
        assert board.step(0) == -1
        clk[0] = 2.0
        assert 0 in board.dead(1)  # aged from board construction

    def test_wait_for_step(self, tmp_path):
        board = MH.LeaseBoard(tmp_path, lease_s=1.0)
        board.stamp(0, 3)
        assert board.wait_for_step(0, 2, timeout=1.0) == 3
        with pytest.raises(TimeoutError):
            board.wait_for_step(1, 0, timeout=0.05, poll_s=0.01)


# ------------------------------------------------------------ report_failure
class TestReportFailure:
    def _controller(self, n, **kw):
        clk = [100.0]
        ctl = ec.ElasticController(n, clock=lambda: clk[0], **kw)
        return ctl, clk

    def test_failure_sequenced_before_shrink(self):
        ctl, _ = self._controller(8)
        fev, sev = ctl.report_failure([4, 5, 6, 7], detect_s=0.25)
        assert fev.kind == "failure" and fev.k_old == 8 and fev.k_new == 4
        assert fev.detect_s == 0.25
        assert sev is not None and sev.kind == "scale_in" and sev.k_new == 4
        assert fev.seq < sev.seq  # detection precedes the plan in the total order
        assert ctl.events == [fev, sev]
        assert ctl.k == 4

    def test_k_min_floor_retains_hosts(self):
        ctl, _ = self._controller(2, k_min=2)
        fev, sev = ctl.report_failure([0, 1])
        assert fev.lost_hosts == () and fev.k_new == 2
        assert sev is None  # the floor retained every candidate: no shrink
        assert ctl.k == 2

    def test_k_min_partial_clamp(self):
        ctl, _ = self._controller(3, k_min=2)
        fev, sev = ctl.report_failure([1, 2])
        assert fev.k_new == 2 and len(fev.lost_hosts) == 1
        assert "clamped at k_min=2" in fev.reason
        assert sev is not None and sev.k_new == 2

    def test_dead_hosts_not_re_evicted(self):
        ctl, _ = self._controller(4)
        ctl.report_failure([3])
        fev, sev = ctl.report_failure([3, 2])  # 3 already dead
        assert fev.lost_hosts == (2,)
        assert ctl.k == 2

    def test_failure_arms_both_autoscaler_cooldowns(self):
        ctl, clk = self._controller(4)
        pol = AutoscalePolicy(AutoscaleConfig(out_cooldown_s=10.0, in_cooldown_s=30.0))
        ctl.attach_autoscaler(pol)
        ctl.report_failure([3])
        assert pol._next_out_t == 100.0 + 10.0
        assert pol._next_in_t == 100.0 + 30.0

    def test_note_external_scale_never_shortens(self):
        pol = AutoscalePolicy(AutoscaleConfig(out_cooldown_s=10.0, in_cooldown_s=30.0))
        pol._next_in_t = 500.0  # already armed further out
        pol.note_external_scale(100.0)
        assert pol._next_in_t == 500.0
        assert pol._next_out_t == 110.0

    def test_failure_event_roundtrips_jsonl(self):
        from repro.obs import log as OL

        ctl, _ = self._controller(4)
        ctl.report_failure([3], detect_s=0.5, restored_bytes=123, replayed_records=2)
        back = OL.events_from_jsonl(ctl.events_jsonl())
        assert back == ctl.events


# ------------------------------------------------------------ shared helpers
def _drill_graph():
    return FH.build_ordered()


def _make_pipeline(src, dst, num_vertices, regions, cfg, **eng_kw):
    o = IncrementalOrderer(src, dst, num_vertices, regions=regions, config=cfg)
    eng = StreamingEngine(o, span_repair="host", **eng_kw)
    ctl = ec.ElasticController(regions)
    ctl.attach_stream(eng)
    return o, eng, ctl


def _slots(o):
    return o.slot_src.copy(), o.slot_dst.copy(), o.slot_valid.copy()


def _assert_slots_equal(a, b, msg=""):
    assert np.array_equal(a[0], b[0]), f"slot_src diverged {msg}"
    assert np.array_equal(a[1], b[1]), f"slot_dst diverged {msg}"
    assert np.array_equal(a[2], b[2]), f"slot_valid diverged {msg}"


# ------------------------------------------------------- staleness boundaries
class TestStalenessBoundaries:
    """Kill at each durability boundary; pin the replay-tail (``wal_steps``)
    the restore must walk."""

    def _stream(self, g, n):
        s = SyntheticStream(g, batch_size=32, delete_frac=0.3, seed=9)
        return [s.batch() for _ in range(n)]

    def test_kill_at_batch_after_snapshot(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg)
        ctl.attach_checkpoint(SlotCheckpoint(tmp_path, interval=4))
        batches = self._stream(g, 6)
        for b in batches:  # snapshots at steps 0 and 4; batch 5 is WAL-only
            ctl.ingest(b)
        want = _slots(o)
        o2, info = SlotCheckpoint(tmp_path, interval=4).restore(config=cfg)
        assert info["manifest_step"] == 4
        assert info["wal_steps"] == [5]  # exactly one record past the snapshot
        assert info["replayed"] == 1
        _assert_slots_equal(_slots(o2), want, "(kill after snapshot)")

    def test_kill_right_on_snapshot_has_empty_tail(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg)
        ctl.attach_checkpoint(SlotCheckpoint(tmp_path, interval=4))
        for b in self._stream(g, 5):  # last batch (step 4) snapshots
            ctl.ingest(b)
        o2, info = SlotCheckpoint(tmp_path, interval=4).restore(config=cfg)
        assert info["wal_steps"] == [] and info["replayed"] == 0
        _assert_slots_equal(_slots(o2), _slots(o), "(kill on snapshot)")

    def test_kill_mid_rebuild_flight_aborts_and_ladder_refires(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(
            src, dst, g.num_vertices, 4, cfg, full_rebuild="geo", rebuild_flight=3
        )
        ctl.attach_checkpoint(SlotCheckpoint(tmp_path, interval=100))
        batches = self._stream(g, 8)
        ctl.ingest(batches[0])  # first batch forces the initial full snapshot
        ctl.ingest(batches[1])
        o.drift = lambda: 1e6
        ctl.ingest(batches[2])  # dispatch
        del o.drift
        assert eng.rebuild_state == "dispatch" and eng.rebuilds_in_flight == 1
        ctl.ingest(batches[3])  # in flight — and this is where we "die"
        assert eng.rebuilds_in_flight == 1
        want = _slots(o)  # flight state never touched the slot arrays

        o2, info = SlotCheckpoint(tmp_path, interval=100).restore(config=cfg)
        assert info["wal_steps"] == [1, 2, 3]  # dispatch batch is a plain record
        _assert_slots_equal(_slots(o2), want, "(kill mid-flight)")
        eng2 = StreamingEngine.from_restored(
            o2, span_repair="host", full_rebuild="geo", rebuild_flight=3
        )
        assert eng2.rebuilds_in_flight == 0  # the flight is NOT survived
        ctl2 = ec.ElasticController(4)
        ctl2.attach_stream(eng2)
        o2.drift = lambda: 1e6  # drift is still past the rung threshold …
        ctl2.ingest(batches[4])
        del o2.drift
        assert eng2.rebuild_state == "dispatch"  # … so the ladder re-fires

    def test_commit_after_flight_forces_full_snapshot(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(
            src, dst, g.num_vertices, 4, cfg, full_rebuild="geo", rebuild_flight=1
        )
        ck = SlotCheckpoint(tmp_path, interval=100)
        ctl.attach_checkpoint(ck)
        batches = self._stream(g, 5)
        ctl.ingest(batches[0])
        o.drift = lambda: 1e6
        ctl.ingest(batches[1])  # dispatch
        del o.drift
        ctl.ingest(batches[2])  # commit: re-layout ⇒ epoch bump
        assert eng.rebuild_state == "commit"
        o2, info = SlotCheckpoint(tmp_path, interval=100).restore(config=cfg)
        # The commit batch's durability record is a FULL snapshot (slot ops
        # cannot replay across the re-layout), so the tail after it is empty.
        assert info["manifest_step"] == 2 and info["wal_steps"] == []
        _assert_slots_equal(_slots(o2), _slots(o), "(rebuild commit)")

    def test_kill_during_rescale_commit(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg)
        ck = SlotCheckpoint(tmp_path, interval=100)
        ctl.attach_checkpoint(ck)
        batches = self._stream(g, 4)
        for b in batches[:3]:
            ctl.ingest(b)
        pre_scale = _slots(o)
        ctl._emit("scale_in", 4, 2, (2, 3), "drill shrink")  # writes the barrier
        post_scale = _slots(o)

        # Committed barrier: restore replays relayout(2) — the re-plan stands.
        o2, info = SlotCheckpoint(tmp_path, interval=100).restore(config=cfg)
        assert o2.regions == 2
        assert info["wal_steps"] == [1, 2]  # batch tail around the barrier
        _assert_slots_equal(_slots(o2), post_scale, "(committed barrier)")

        # Torn barrier (SIGKILL mid-append): the tear truncates the WAL tail,
        # so recovery falls back to the PRE-scale state — the rescale never
        # became durable and simply re-runs after recovery.
        wal = tmp_path / "wal.jsonl"
        lines = wal.read_text().splitlines(keepends=True)
        wal.write_text("".join(lines[:-1]) + lines[-1][: len(lines[-1]) // 2])
        o3, info3 = SlotCheckpoint(tmp_path, interval=100).restore(config=cfg)
        assert o3.regions == 4
        assert info3["wal_steps"] == [1, 2]
        _assert_slots_equal(_slots(o3), pre_scale, "(torn barrier)")


# ------------------------------------------------------- partition-scoped restore
class TestPartitionRestore:
    def test_lost_partitions_bit_equal_and_cheaper(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 8, cfg)
        ck = SlotCheckpoint(tmp_path, interval=3)
        ctl.attach_checkpoint(ck)
        s = SyntheticStream(g, batch_size=32, delete_frac=0.3, seed=9)
        for _ in range(5):  # snapshots at 0, 3; WAL tail covers 4
            ctl.ingest(s.batch())
        spr = o.slots_per_region
        chunks, info = ck.restore_partitions([1, 5])
        for r in (1, 5):
            lo = r * spr
            assert np.array_equal(chunks[r][0], o.slot_src[lo : lo + spr])
            assert np.array_equal(chunks[r][1], o.slot_dst[lo : lo + spr])
            assert np.array_equal(chunks[r][2], o.slot_valid[lo : lo + spr])
        _, full_info = SlotCheckpoint(tmp_path, interval=3).restore(config=cfg)
        assert info["bytes_read"] < full_info["bytes_read"]
        # The recovery bill scales with LOST partitions, not graph size.
        one, one_info = ck.restore_partitions([1])
        assert one_info["bytes_read"] < info["bytes_read"]

    def test_refuses_across_scale_barrier(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 8, cfg)
        ck = SlotCheckpoint(tmp_path, interval=100)
        ctl.attach_checkpoint(ck)
        s = SyntheticStream(g, batch_size=32, seed=9)
        ctl.ingest(s.batch())
        ctl._emit("scale_in", 8, 4, (4, 5, 6, 7), "shrink")
        with pytest.raises(CheckpointError, match="scale"):
            ck.restore_partitions([1])

    def test_out_of_range_partition(self, tmp_path):
        g, src, dst = _drill_graph()
        cfg = FH.drill_config()
        o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg)
        ck = SlotCheckpoint(tmp_path)
        ctl.attach_checkpoint(ck)
        s = SyntheticStream(g, batch_size=32, seed=9)
        ctl.ingest(s.batch())
        with pytest.raises(CheckpointError, match="out of range"):
            ck.restore_partitions([7])


# ----------------------------------------------------------- property (race)
def _run_race(actions, tmp_path):
    """Execute an action interleaving twice — subject (with kills: crash +
    cold restore + failure shrink) and mirror (same decisions, never loses
    state) — and return both final states plus the subject's per-generation
    event logs."""
    g, src, dst = _drill_graph()
    cfg = FH.drill_config()
    stream = SyntheticStream(g, batch_size=32, delete_frac=0.3, seed=11)
    batches = [stream.batch() for _ in range(len(actions) + 1)]
    eng_kw = dict(full_rebuild="geo", rebuild_flight=2)

    o, eng, ctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg, **eng_kw)
    ck = SlotCheckpoint(tmp_path, interval=2)
    ctl.attach_checkpoint(ck)
    bi = 0
    durable = False
    mirror_ops = []  # the decisions the no-failure mirror must repeat
    generations = [ctl]

    def scale_in(c):
        k_old = c.k
        hid = max(h.host_id for h in c.hosts.values() if h.alive)
        c.hosts[hid].alive = False
        c._emit("scale_in", k_old, c.k, (hid,), "race scale_in")

    for act in actions:
        if act in ("ingest", "rebuild") and bi < len(batches):
            if act == "rebuild":
                ctl.stream.orderer.drift = lambda: 1e6
            ctl.ingest(batches[bi])
            if act == "rebuild":
                del ctl.stream.orderer.drift
            mirror_ops.append((act, bi))
            bi += 1
            durable = True
        elif act == "scale_in" and ctl.k > 2:
            scale_in(ctl)
            mirror_ops.append(("scale_in", None))
        elif act == "scale_out":
            ctl.add_hosts(1)
            mirror_ops.append(("scale_out", None))
        elif act == "kill" and durable and ctl.k >= 2:
            # Crash: live state gone; cold-restore, re-home, failure shrink.
            ck = SlotCheckpoint(tmp_path, interval=2)
            o, info = ck.restore(config=cfg)
            eng = StreamingEngine.from_restored(o, span_repair="host", **eng_kw)
            k_cur = o.regions
            ctl = ec.ElasticController(k_cur)
            ctl.attach_stream(eng)
            ctl.attach_checkpoint(ck)
            ctl._batch_step = info["step"]
            fev, sev = ctl.report_failure([k_cur - 1], reason="race kill")
            generations.append(ctl)
            mirror_ops.append(("failure_shrink", sev.k_new if sev else None))

    # Mirror: same decision sequence, no state loss, no checkpoint.
    mo, meng, mctl = _make_pipeline(src, dst, g.num_vertices, 4, cfg, **eng_kw)
    for op, arg in mirror_ops:
        if op in ("ingest", "rebuild"):
            if op == "rebuild":
                mo.drift = lambda: 1e6
            mctl.ingest(batches[arg])
            if op == "rebuild":
                del mo.drift
        elif op == "scale_in":
            scale_in(mctl)
        elif op == "scale_out":
            mctl.add_hosts(1)
        elif op == "failure_shrink" and arg is not None:
            k_old = mctl.k
            lost = sorted(h.host_id for h in mctl.hosts.values() if h.alive)[arg - k_old :]
            for hid in lost:
                mctl.hosts[hid].alive = False
            mctl._emit("scale_in", k_old, arg, tuple(lost), "mirror failure shrink")
    return ctl, mctl, generations


def _assert_race_invariants(ctl, mctl, generations):
    subject, mirror = ctl.stream.orderer, mctl.stream.orderer
    assert subject.regions == mirror.regions
    _assert_slots_equal(_slots(subject), _slots(mirror), "(race vs mirror)")
    ctl.stream.verify_bit_identity()
    mctl.stream.verify_bit_identity()
    for gen_i, c in enumerate(generations):
        seqs = [ev.seq for ev in c.events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs), (
            f"generation {gen_i}: seq not strictly monotonic: {seqs}"
        )
        if gen_i > 0:  # every recovery generation leads with its FailureEvent
            assert c.events and c.events[0].kind == "failure"


@given(
    actions=st.lists(
        st.sampled_from(["ingest", "scale_in", "scale_out", "rebuild", "kill"]),
        min_size=3,
        max_size=7,
    )
)
@settings(max_examples=6, deadline=None)
def test_race_recovery_matches_no_failure_mirror(actions, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("race")
    _assert_race_invariants(*_run_race(actions, tmp))


def test_race_fixed_interleaving(tmp_path):
    """Deterministic fallback of the property test: one interleaving that
    hits every action kind — ingest, scale both ways, an async rebuild
    racing a kill, and a second kill after the recovery."""
    actions = [
        "ingest", "scale_out", "ingest", "rebuild", "kill",
        "ingest", "scale_in", "ingest", "kill", "ingest",
    ]
    _assert_race_invariants(*_run_race(actions, tmp_path))


# ------------------------------------------------------------------ the drill
@pytest.fixture(scope="module")
def drill(tmp_path_factory):
    """Run the full drill once: live 2×4 cluster, SIGKILL process 1 at batch
    KILL_STEP, lease-expiry detection, group reaped, 1×4 recovery cluster
    restores and continues. Returns every artifact the tests below check."""
    shared = tmp_path_factory.mktemp("drill_shared")
    out = tmp_path_factory.mktemp("drill_out")
    harness = os.path.join(ROOT, "tests", "faults_harness.py")
    cluster = MH.launch_local_cluster(
        N_PROCS,
        DEVS_PER_PROC,
        [harness, "--mode", "live", "--dir", str(shared), "--out", str(out),
         "--batches", str(DRILL_BATCHES)],
        cwd=ROOT,
    )
    board = MH.LeaseBoard(shared / "leases", lease_s=FH.LEASE_S)
    deadline = time.monotonic() + 300.0
    try:
        while board.step(1) < KILL_STEP:
            if cluster.poll(0) is not None or cluster.poll(1) is not None:
                res = cluster.wait(10.0)
                logs = res.format_logs()
                print(logs, file=sys.stderr)
                bootstrapped = any(_BOOTSTRAP_BANNER in p.stdout for p in res.procs)
                if not bootstrapped and any(m in logs for m in _UNSUPPORTED_MARKERS):
                    pytest.skip(f"localhost jax.distributed unsupported here:\n{logs[-2000:]}")
                pytest.fail(f"live cluster died before the kill step:\n{logs}")
            if time.monotonic() > deadline:
                cluster.wait(5.0)
                pytest.fail(f"victim never reached batch {KILL_STEP}")
            time.sleep(0.02)

        t_kill = time.monotonic()
        cluster.kill(1, reason="drill preemption")
        while 1 not in board.dead(N_PROCS):
            assert time.monotonic() - t_kill < 60.0, "lease of the killed process never expired"
            time.sleep(0.05)
        detect_s = time.monotonic() - t_kill
        # The survivor is stranded in its next collective (the victim died
        # holding the group) — a real control plane abandons the group.
        cluster.kill(0, reason="stranded survivor abandoned with the group")
    finally:
        live_res = cluster.wait(30.0)

    recover_res = MH.spawn_local_cluster(
        1,
        DEVS_PER_PROC,
        [harness, "--mode", "recover", "--dir", str(shared), "--out", str(out),
         "--batches", str(DRILL_BATCHES), "--detect-s", f"{detect_s:.6f}",
         "--lost-hosts", "4,5,6,7"],
        timeout=540.0,
        cwd=ROOT,
    )
    if not recover_res.ok:
        logs = recover_res.format_logs()
        print(logs, file=sys.stderr)
        pytest.fail(f"recovery cluster failed:\n{logs}")
    with open(out / "recover.json") as fh:
        record = json.load(fh)
    shards = dict(np.load(out / "recover.npz"))
    return {
        "live": live_res,
        "detect_s": detect_s,
        "record": record,
        "shards": shards,
    }


def _drill_oracle(last_durable: int):
    """Host replay of the drill WITHOUT the failure: same batches, same
    re-plan (8 → 4 after the last durable batch), state never lost. Returns
    (final orderer, restore-point slot triple)."""
    g, src, dst = FH.build_ordered()
    o = IncrementalOrderer(
        src, dst, g.num_vertices, regions=FH.REGIONS, config=FH.drill_config()
    )
    stream = SyntheticStream(g, batch_size=FH.STREAM_BATCH, seed=FH.STREAM_SEED)
    snap = None
    for b in range(DRILL_BATCHES):
        o.apply(stream.batch())
        o.needs_resync = False
        o.drain_ops()
        if b == last_durable:
            snap = _slots(o)
            o.relayout(4)
            o.drain_gather_map()
            o.needs_resync = False
    assert snap is not None
    return o, snap


class TestDrill:
    def test_group_reaped_with_partial_logs(self, drill):
        res = drill["live"]
        assert res.procs[1].returncode == -9  # SIGKILL, reaped (no zombie)
        assert res.procs[0].returncode is not None
        # The victim's PARTIAL log survived, attributably prefixed …
        assert any(
            line.startswith("[p1] ") and "live: batch" in line
            for line in res.procs[1].stdout.splitlines()
        )
        # … and the injected kill is recorded where the logs are read.
        assert "SIGKILL injected" in res.procs[1].stderr

    def test_detection_latency_bounded(self, drill):
        # Expiry can't beat the lease window, and on a quiet box the
        # detector fires within a couple of windows of the kill.
        assert 0.0 < drill["detect_s"] < 10 * FH.LEASE_S
        assert drill["record"]["failure_event"]["detect_s"] == pytest.approx(
            drill["detect_s"], abs=1e-6
        )

    def test_failure_event_and_replan(self, drill):
        fe = drill["record"]["failure_event"]
        assert fe["k_old"] == 8 and fe["k_new"] == 4
        assert fe["lost_hosts"] == [4, 5, 6, 7]
        assert fe["restored_bytes"] > 0
        kinds = drill["record"]["event_kinds"]
        assert kinds[0] == "failure" and kinds[1] == "scale_in"
        seqs = drill["record"]["event_seqs"]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert drill["record"]["k_final"] == 4

    def test_recovery_bit_identical_to_oracle(self, drill):
        last_durable = drill["record"]["restore"]["step"]
        assert 0 <= last_durable < DRILL_BATCHES - 1
        oracle, restore_point = _drill_oracle(last_durable)
        sh = drill["shards"]
        # At the recovery point: the restored order IS the pre-failure order.
        _assert_slots_equal(
            (sh["restore_src"], sh["restore_dst"], sh["restore_valid"]),
            restore_point,
            "(drill restore point)",
        )
        # At the end: the recovered run and the never-failed oracle agree
        # byte-for-byte — exactly-once recovery.
        _assert_slots_equal(
            (sh["final_src"], sh["final_dst"], sh["final_valid"]),
            _slots(oracle),
            "(drill final)",
        )

    def test_recovered_pack_matches_oracle_pack(self, drill):
        from repro.graphs import engine as GE

        last_durable = drill["record"]["restore"]["step"]
        oracle, _ = _drill_oracle(last_durable)
        pack = GE.pack_slots(
            oracle.slot_src, oracle.slot_dst, oracle.slot_valid, 4, oracle.num_vertices
        )
        sh = drill["shards"]
        rows = {}
        for key, data in sh.items():
            if key.startswith("final_edges__"):
                _, lo, hi = key.rsplit("__", 2)
                for r in range(int(lo), int(hi)):
                    rows[r] = data[r - int(lo)]
        got = np.stack([rows[r] for r in sorted(rows)])
        # k=4 on g=4 devices: partition_row is the identity, so the global
        # row order IS partition order.
        assert np.array_equal(got, pack.edges)
