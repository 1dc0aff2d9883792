"""Live verification service tests (ISSUE 6): follow cursors, the
JIT-linearization window engine (differential vs the wgl_cpu oracle),
bounded-memory scheduling, the serve-checker CLI, /live web surfaces,
and the in-flight detection acceptance scenario."""

import json
import random
import threading
import time

import pytest

from jepsen_tpu import checker as ck
from jepsen_tpu import cli, core, generator as gen, models, store
from jepsen_tpu import telemetry, web
from jepsen_tpu import tests as tst
from jepsen_tpu.history import (History, HistoryWAL, fail_op, follow,
                                info_op, invoke_op, ok_op)
from jepsen_tpu.independent import KV
from jepsen_tpu.live import engine as live_engine
from jepsen_tpu.live.scheduler import LiveScheduler
from jepsen_tpu.live.service import CheckerService
from jepsen_tpu.live.windows import LaneState, Tenant
from jepsen_tpu.ops import wgl_cpu


@pytest.fixture(autouse=True)
def store_tmpdir(tmp_path, monkeypatch):
    monkeypatch.setattr(store, "BASE", tmp_path / "store")
    yield


# ---------------------------------------------------------------------------
# follow() cursors (satellite: torn-tail resume regression)
# ---------------------------------------------------------------------------

class TestFollowCursor:
    def test_incremental_follow_with_wall_stamps(self, tmp_path):
        p = tmp_path / "history.wal"
        wal = HistoryWAL(p, fsync=False)
        for i in range(3):
            wal.append(invoke_op(0, "write", i, index=i))
        seg = follow(p)
        assert [o.value for o in seg.ops] == [0, 1, 2]
        assert seg.seq == 3 and not seg.corrupt and seg.tail_bytes == 0
        assert all(isinstance(w, float) for w in seg.walls)
        # resume: only the new records come back
        wal.append(ok_op(0, "write", 2, index=3))
        seg2 = follow(p, seg.offset, seg.seq)
        assert len(seg2.ops) == 1 and seg2.ops[0].is_ok
        assert seg2.seq == 4
        # idle follow: empty, cursor unchanged
        seg3 = follow(p, seg2.offset, seg2.seq)
        assert seg3.ops == [] and seg3.offset == seg2.offset
        wal.close()

    def test_torn_tail_resume(self, tmp_path):
        """THE satellite regression: an incomplete trailing line is
        not consumed, and once the writer completes it the follower
        picks the record up whole from the same offset."""
        p = tmp_path / "history.wal"
        wal = HistoryWAL(p, fsync=False)
        wal.append(invoke_op(0, "write", 1, index=0))
        wal.close()
        seg = follow(p)
        assert len(seg.ops) == 1
        # a writer mid-append: half a record, no newline
        import zlib
        from jepsen_tpu.history import _wal_payload
        payload = _wal_payload(ok_op(0, "write", 1, index=1).to_dict())
        line = (f'{{"i":1,"w":1.5,'
                f'"crc":"{zlib.crc32(payload.encode()):08x}",'
                f'"op":{payload}}}\n')
        with open(p, "a") as f:
            f.write(line[:17])        # torn mid-record
        seg2 = follow(p, seg.offset, seg.seq)
        assert seg2.ops == [] and not seg2.corrupt
        assert seg2.tail_bytes == 17
        assert seg2.offset == seg.offset      # NOT consumed
        with open(p, "a") as f:
            f.write(line[17:])        # the writer finishes the append
        seg3 = follow(p, seg2.offset, seg2.seq)
        assert len(seg3.ops) == 1 and seg3.ops[0].is_ok
        assert not seg3.corrupt and seg3.tail_bytes == 0

    def test_corrupt_complete_line_is_permanent(self, tmp_path):
        p = tmp_path / "history.wal"
        wal = HistoryWAL(p, fsync=False)
        for i in range(4):
            wal.append(invoke_op(0, "w", i, index=i))
        wal.close()
        lines = p.read_text().splitlines()
        lines[2] = lines[2].replace('"value":2', '"value":7')
        p.write_text("\n".join(lines) + "\n")
        seg = follow(p)
        assert len(seg.ops) == 2 and seg.corrupt
        assert "crc mismatch" in seg.stop_reason
        # following again from the stop offset stays corrupt
        seg2 = follow(p, seg.offset, seg.seq)
        assert seg2.corrupt and len(seg2.ops) == 0

    def test_max_records_slicing(self, tmp_path):
        p = tmp_path / "history.wal"
        wal = HistoryWAL(p, fsync=False)
        for i in range(10):
            wal.append(invoke_op(0, "w", i, index=i))
        wal.close()
        got, off, seq = [], 0, 0
        while True:
            seg = follow(p, off, seq, max_records=3)
            if not seg.ops:
                break
            got += [o.value for o in seg.ops]
            off, seq = seg.offset, seg.seq
        assert got == list(range(10))

    def test_follow_events_torn_tail(self, tmp_path):
        p = tmp_path / "telemetry.jsonl"
        lg = telemetry.EventLog(p, fsync=False)
        lg.append({"type": "a"})
        lg.append({"type": "b"})
        seg = telemetry.follow_events(p)
        assert [e["type"] for e in seg.events] == ["a", "b"]
        with open(p, "a") as f:
            f.write('{"i":2,"t":1.0,"crc":"00')   # torn
        seg2 = telemetry.follow_events(p, seg.offset, seg.seq)
        assert seg2.events == [] and not seg2.corrupt
        assert seg2.tail_bytes > 0
        lg.close()
        # read_events == full-file follow
        assert [e["type"] for e in telemetry.read_events(p)] \
            == ["a", "b"]

    def test_recover_unaffected_by_wall_stamps(self, tmp_path):
        p = tmp_path / "history.wal"
        wal = HistoryWAL(p, fsync=False)
        wal.append(invoke_op(0, "write", 5, index=0))
        wal.append(ok_op(0, "write", 5, index=1))
        wal.close()
        from jepsen_tpu.history import recover
        h = recover(p)
        assert len(h) == 2 and h.recovery["torn"] is False


# ---------------------------------------------------------------------------
# the window engine: differential vs the wgl_cpu oracle
# ---------------------------------------------------------------------------

def gen_history(n_ops, conc, seed, vmax=4, crash_rate=0.0):
    """chip_smoke.make_history's shape, locally: an etcd-style r/w/cas mix
    against a sequential register simulator (always linearizable
    unless planted otherwise)."""
    rng = random.Random(seed)
    ops, value = [], None
    open_comp = {}
    i = 0
    while i < n_ops:
        p = rng.randrange(conc)
        if p in open_comp:
            ops.append(open_comp.pop(p))
            continue
        i += 1
        f = rng.choice(("read", "read", "write", "cas"))
        if crash_rate and rng.random() < crash_rate and f != "read":
            v = rng.randint(0, vmax) if f == "write" else \
                [rng.randint(0, vmax), rng.randint(0, vmax)]
            ops.append(invoke_op(p, f, v))
            ops.append(info_op(p, f, v))
            continue
        if f == "read":
            ops.append(invoke_op(p, "read", None))
            open_comp[p] = ok_op(p, "read", value)
        elif f == "write":
            v = rng.randint(0, vmax)
            ops.append(invoke_op(p, "write", v))
            value = v
            open_comp[p] = ok_op(p, "write", v)
        else:
            old, new = rng.randint(0, vmax), rng.randint(0, vmax)
            ops.append(invoke_op(p, "cas", [old, new]))
            if value == old:
                value = new
                open_comp[p] = ok_op(p, "cas", [old, new])
            else:
                open_comp[p] = fail_op(p, "cas", [old, new])
    for comp in open_comp.values():
        ops.append(comp)
    return History(ops).index()


def live_verdict(hist, backend, bits=4, max_states=24):
    """Run a full history through the incremental path: ingest, then
    window-check until drained.  Returns (tenant, windows_checked).
    wild_init=False: the differential harness KNOWS the simulator
    starts from None, so exactness against the oracle is pinned."""
    t = Tenant("t", "ts", None, models.CASRegister(None), bits=bits,
               max_states=max_states, max_window_events=64,
               wild_init=False)
    t.ingest(list(hist), [None] * len(hist))
    total = 0
    progressed = True
    while progressed:
        progressed = False
        for key, lane in t.lanes.items():
            w = lane.take_window()
            if w is None:
                continue
            v = live_engine.check_batch([w.dispatch],
                                        backend=backend)[0]
            lane.apply_result(w, v)
            total += 1
            progressed = True
    return t, total


class TestEngineDifferential:
    def test_clean_histories_match_oracle(self):
        model = models.CASRegister(None)
        for seed in range(6):
            h = gen_history(40, 3, seed=seed)
            oracle = wgl_cpu.check(model, h)["valid?"]
            assert oracle is True     # the simulator is linearizable
            for backend in ("host", "device"):
                t, nw = live_verdict(h, backend)
                assert t.verdict_so_far is True, (seed, backend)
                assert nw >= 1
                assert sum(ln.evictions
                           for ln in t.lanes.values()) == 0

    def test_planted_violations_match_oracle(self):
        model = models.CASRegister(None)
        flagged = 0
        for seed in range(6):
            h = gen_history(40, 3, seed=100 + seed)
            # corrupt one mid-history ok read to a never-written value
            target = next(o for o in list(h)[len(h) // 3:]
                          if o.is_ok and o.f == "read")
            target.value = 77
            oracle = wgl_cpu.check(model, h)["valid?"]
            assert oracle is False
            for backend in ("host", "device"):
                t, _ = live_verdict(h, backend)
                assert t.verdict_so_far is False, (seed, backend)
                flag = t.flags[0]
                assert flag["value"] == 77 and flag["f"] == "read"
            flagged += 1
        assert flagged == 6

    def test_crashed_ops_differential(self):
        """Histories with :info mutations (residue path) still agree
        with the oracle, on both backends."""
        model = models.CASRegister(None)
        for seed in range(4):
            h = gen_history(30, 3, seed=200 + seed, crash_rate=0.2)
            oracle = wgl_cpu.check(model, h)["valid?"]
            for backend in ("host", "device"):
                t, _ = live_verdict(h, backend)
                assert t.verdict_so_far is oracle, (seed, backend)

    def test_host_device_window_equality(self):
        """Every window's violated_event matches between the numpy
        oracle and the jitted kernel."""
        h = gen_history(60, 3, seed=42, crash_rate=0.1)
        t1, _ = live_verdict(h, "host")
        t2, _ = live_verdict(h, "device")
        assert t1.verdict_so_far == t2.verdict_so_far
        assert [f["op_index"] for f in t1.flags] \
            == [f["op_index"] for f in t2.flags]

    def test_plan_cache_warm_after_first_dispatch(self):
        live_engine.clear_plan_cache()
        h = gen_history(20, 2, seed=7)
        live_verdict(h, "device")
        stats = live_engine.plan_cache_stats()
        # a second tenant with the same window shapes rides the warm
        # compiled plan: no new compiles
        live_verdict(h, "device")
        stats2 = live_engine.plan_cache_stats()
        assert stats2["miss"] == stats["miss"]
        assert stats2["hit"] > stats["hit"]


class TestLaneSemantics:
    def test_no_window_before_quiescence(self):
        ln = LaneState(models.CASRegister(None), bits=4)
        ln.on_invoke(0, "write", 1, 0, None)
        assert ln.take_window() is None        # op still open
        ln.on_complete(0, "ok", 1, 1, None)
        assert ln.take_window() is not None

    def test_saturation_for_non_register_models(self):
        """Models without wildcard semantics saturate instead of
        widening — honest 'unknown', never a false flag.  Trigger:
        more simultaneously-open ops than slot bits."""
        t = Tenant("t", "ts", None, models.Mutex(), bits=2)
        ops = [invoke_op(p, "acquire", None, index=p)
               for p in range(6)]            # 6 concurrent > 2 bits
        ops += [ok_op(0, "acquire", None, index=10)]
        ops += [info_op(p, "acquire", None, index=10 + p)
                for p in range(1, 6)]
        t.ingest(ops, [None] * len(ops))
        assert t.lanes[None].take_window() is None
        assert t.lanes[None].saturated
        assert t.verdict_so_far == "unknown"

    def test_slot_exhaustion_widens_register_lane(self):
        """Window concurrency beyond the slot bits evicts the stretch
        and widens (register family) — counted, never silent, and the
        lane keeps checking afterwards."""
        t = Tenant("t", "ts", None, models.CASRegister(None), bits=2)
        ops = [invoke_op(p, "write", p, index=p) for p in range(6)]
        ops += [ok_op(p, "write", p, index=6 + p) for p in range(6)]
        t.ingest(ops, [None] * len(ops))
        ln = t.lanes[None]
        assert ln.take_window() is None       # evicted, widened
        assert ln.evictions >= 1
        assert ln.saturated is None
        assert ln.evict_reasons
        # and the lane keeps checking: a read of any value passes the
        # widened (wildcard) frontier
        t.ingest([invoke_op(0, "read", None, index=50),
                  ok_op(0, "read", 3, index=51)], [None, None])
        w = ln.take_window()
        v = live_engine.check_batch([w.dispatch], backend="host")[0]
        assert v["valid?"] is True

    def test_spans_survive_forced_cuts_exactly(self):
        """An op held open across many forced cuts (a wedged writer)
        does NOT evict: its slot spans the windows and the stream
        keeps checking exactly."""
        t = Tenant("t", "ts", None, models.CASRegister(None),
                   bits=4, max_buffer_entries=8, max_window_events=16,
                   wild_init=False)
        ops = [invoke_op(9, "write", 5, index=0)]   # wedged open
        i = 1
        for k in range(20):
            ops += [invoke_op(0, "write", k % 3, index=i),
                    ok_op(0, "write", k % 3, index=i + 1)]
            i += 2
        t.ingest(ops, [None] * len(ops))
        ln = t.lanes[None]
        checked = 0
        while True:
            w = ln.take_window()
            if w is None:
                break
            v = live_engine.check_batch([w.dispatch],
                                        backend="host")[0]
            assert ln.apply_result(w, v) is None
            checked += 1
        assert checked >= 2                   # forced cuts happened
        assert ln.evictions == 0              # no gap, no widening
        assert 9 in ln.span_slot              # the wedged op spans on
        # the wedged write can STILL linearize late: read(5) after all
        # those other writes is justified by it (JIT semantics)
        t.ingest([ok_op(9, "write", 5, index=98),
                  invoke_op(0, "read", None, index=99),
                  ok_op(0, "read", 5, index=100)], [None] * 3)
        w = ln.take_window()
        v = live_engine.check_batch([w.dispatch], backend="host")[0]
        assert ln.apply_result(w, v) is None
        # ...but a read of a never-written value still flags: spans
        # did not cost exactness
        t.ingest([invoke_op(0, "read", None, index=101),
                  ok_op(0, "read", 77, index=102)], [None] * 2)
        w = ln.take_window()
        v = live_engine.check_batch([w.dispatch], backend="host")[0]
        assert ln.apply_result(w, v) is not None

    def test_state_table_compaction_keeps_long_runs_bounded(self):
        """A tenant writing monotonically-growing values (counters,
        ids) must not saturate its state table: dead states compact
        away and checking continues indefinitely."""
        t = Tenant("t", "ts", None, models.CASRegister(None),
                   bits=4, max_states=8)
        ln = t.lane(None)
        idx = 0
        for round_ in range(20):              # 40 distinct values >> 8
            ops = []
            for k in range(2):
                v = round_ * 2 + k + 1000
                ops += [invoke_op(0, "write", v, index=idx),
                        ok_op(0, "write", v, index=idx + 1)]
                idx += 2
            t.ingest(ops, [None] * len(ops))
            w = ln.take_window()
            assert w is not None, f"round {round_} starved"
            v = live_engine.check_batch([w.dispatch],
                                        backend="host")[0]
            assert ln.apply_result(w, v) is None
        assert ln.evictions == 0
        assert len(ln.states) <= 8


# ---------------------------------------------------------------------------
# scheduler: bounded memory (satellite), multi-tenant micro-batching
# ---------------------------------------------------------------------------

def write_wal(run_dir, ops, fsync=False):
    run_dir.mkdir(parents=True, exist_ok=True)
    wal = HistoryWAL(run_dir / "history.wal", fsync=fsync)
    for o in ops:
        wal.append(o)
    wal.close()


class TestBoundedMemory:
    def test_backpressure_bounds_tenant_bytes(self, tmp_path):
        """A tenant streaming faster than the device drains must hit
        cursor backpressure, never unbounded growth: tracked window
        bytes stay under budget + one ingest slice at every tick."""
        root = tmp_path / "store"
        d = root / "fast" / "t1"
        h = gen_history(1200, 3, seed=5)
        write_wal(d, list(h))
        budget = 24_000
        batch = 64
        sched = LiveScheduler(root, backend="host", scan_every=1,
                              tenant_budget_bytes=budget,
                              max_batch_records=batch,
                              bits=4, max_window_events=32)
        from jepsen_tpu.live.windows import ENTRY_COST_B
        slack = batch * ENTRY_COST_B + 4096   # one slice + plane
        paused_seen = False
        high_water = 0
        for _ in range(400):
            sched.tick()
            for t in sched.tenants.values():
                high_water = max(high_water, t.nbytes)
                assert t.nbytes <= budget + slack, \
                    f"tenant bytes {t.nbytes} blew the budget"
                paused_seen = paused_seen or t.paused
            if not sched._has_new_bytes() \
                    and all(t.queue_depth == 0
                            for t in sched.tenants.values()):
                break
        assert paused_seen, "backpressure never engaged"
        assert high_water > budget // 2       # the test actually bit
        # nothing was lost: every completed op got checked
        t = next(iter(sched.tenants.values()))
        st = t.stats()
        assert st["verdict-so-far"] is True
        assert st["ops_checked"] > 1000
        ev = telemetry.read_events(d / "live.jsonl")
        assert any(e["type"] == "live-backpressure" for e in ev)
        assert any(e["type"] == "live-resume" for e in ev)
        sched.close()

    def test_unquiescent_stream_checks_via_spans(self, tmp_path):
        """A lane that never goes quiescent (a wedged open op) still
        checks everything through forced cuts + spanning slots — no
        eviction, no unbounded buffer."""
        root = tmp_path / "store"
        d = root / "wedged" / "t1"
        ops = [invoke_op(9, "write", 1, index=0)]  # never completes
        for i in range(300):
            ops += [invoke_op(0, "write", i % 4, index=2 * i + 1),
                    ok_op(0, "write", i % 4, index=2 * i + 2)]
        write_wal(d, ops)
        sched = LiveScheduler(root, backend="host", scan_every=1,
                              bits=4, max_buffer_entries=64)
        for _ in range(60):
            sched.tick()
        t = next(iter(sched.tenants.values()))
        st = t.stats()
        assert st["evictions"] == 0
        assert st["verdict-so-far"] is True
        assert st["ops_checked"] >= 300
        assert max(len(ln.buffer)
                   for ln in t.lanes.values()) <= 64
        sched.close()


class TestMultiTenant:
    def test_flag_and_shared_dispatch(self, tmp_path):
        root = tmp_path / "store"
        d1, d2 = root / "bad" / "t1", root / "clean" / "t1"
        ops1, ops2 = [], []
        for k in range(3):
            ops1 += [invoke_op(0, "write", KV(k, 10 + k), index=2 * k),
                     ok_op(0, "write", KV(k, 10 + k), index=2 * k + 1)]
            ops2 += [invoke_op(0, "write", KV(k, 20 + k), index=2 * k),
                     ok_op(0, "write", KV(k, 20 + k), index=2 * k + 1)]
        ops1 += [invoke_op(0, "read", KV(1, None), index=8),
                 ok_op(0, "read", KV(1, 99), index=9)]      # planted
        ops2 += [invoke_op(0, "read", KV(1, None), index=8),
                 ok_op(0, "read", KV(1, 21), index=9)]
        write_wal(d1, ops1)
        write_wal(d2, ops2)
        sched = LiveScheduler(root, backend="device", scan_every=1,
                              bits=4, max_states=16)
        sched.drain(50)
        lj1 = json.loads((d1 / "live.json").read_text())
        lj2 = json.loads((d2 / "live.json").read_text())
        assert lj1["verdict-so-far"] is False
        assert lj2["verdict-so-far"] is True
        assert lj1["flags"][0]["value"] == 99
        ev1 = telemetry.read_events(d1 / "live.jsonl")
        ev2 = telemetry.read_events(d2 / "live.jsonl")
        flags = [e for e in ev1 if e["type"] == "live-flag"]
        assert flags and flags[0]["engine"] == "live-jit"
        assert flags[0]["cache"] in ("hit", "miss")
        assert flags[0]["detection_lag_s"] is not None
        assert flags[0]["dispatch_id"]
        ids1 = {e["dispatch_id"] for e in ev1
                if e["type"] == "live-dispatch"}
        ids2 = {e["dispatch_id"] for e in ev2
                if e["type"] == "live-dispatch"}
        shared = ids1 & ids2
        assert shared, "tenants never shared a micro-batched dispatch"
        # the shared dispatch really carried both tenants
        shared_ev = next(e for e in ev1
                         if e["type"] == "live-dispatch"
                         and e["dispatch_id"] in shared)
        assert len(shared_ev["tenants"]) == 2
        sched.close()

    def test_corrupt_stream_goes_unknown(self, tmp_path):
        root = tmp_path / "store"
        d = root / "bitrot" / "t1"
        write_wal(d, [invoke_op(0, "write", 1, index=0),
                      ok_op(0, "write", 1, index=1)])
        with open(d / "history.wal", "a") as f:
            f.write('{"i":2,"crc":"00000000","op":{"f":"x"}}\n')
        sched = LiveScheduler(root, backend="host", scan_every=1)
        sched.tick()
        sched.tick()
        t = next(iter(sched.tenants.values()))
        assert t.corrupt and t.stats()["verdict-so-far"] == "unknown"
        ev = telemetry.read_events(d / "live.jsonl")
        assert any(e["type"] == "live-corrupt" for e in ev)
        sched.close()

    def test_done_tenant_finalizes(self, tmp_path):
        root = tmp_path / "store"
        d = root / "done" / "t1"
        write_wal(d, [invoke_op(0, "write", 1, index=0),
                      ok_op(0, "write", 1, index=1)])
        (d / "results.json").write_text('{"valid?": true}')
        sched = LiveScheduler(root, backend="host", scan_every=1)
        sched.drain(20)
        assert ("done", "t1") in sched.finished
        ev = telemetry.read_events(d / "live.jsonl")
        assert ev[-1]["type"] == "live-done"
        sched.close()

    def test_runner_quarantine_isolates_poison_lane(self, tmp_path):
        """A lane whose dispatch poisons the engine quarantines alone
        (ResilientRunner bisection); the other lane still verdicts."""
        root = tmp_path / "store"
        d = root / "poison" / "t1"
        ops = []
        for k in range(2):
            ops += [invoke_op(0, "write", KV(k, 1), index=2 * k),
                    ok_op(0, "write", KV(k, 1), index=2 * k + 1)]
        write_wal(d, ops)
        sched = LiveScheduler(root, backend="host", scan_every=1)
        sched.tick()                       # adopt + ingest + check
        t = next(iter(sched.tenants.values()))
        # poison one lane's next window by wedging its plane shape,
        # then force another round
        more = [invoke_op(0, "read", KV(0, None), index=10),
                ok_op(0, "read", KV(0, 1), index=11),
                invoke_op(0, "read", KV(1, None), index=12),
                ok_op(0, "read", KV(1, 1), index=13)]
        wal = HistoryWAL(d / "history.wal", fsync=False)
        wal._n = t.seq
        for o in more:
            wal.append(o)
        wal.close()
        bad_lane = t.lanes[0]
        good_lane = t.lanes[1]
        orig = bad_lane.take_window

        def corrupted_take():
            w = orig()
            if w is not None:
                w.dispatch.ev_next = "not an array"   # poisons engine
            return w
        bad_lane.take_window = corrupted_take
        sched.tick()
        assert bad_lane.saturated and "quarantined" in bad_lane.saturated
        assert good_lane.windows_checked >= 2
        assert t.stats()["verdict-so-far"] == "unknown"
        sched.close()


# ---------------------------------------------------------------------------
# CLI + web surfaces
# ---------------------------------------------------------------------------

class TestServeCheckerCLI:
    def test_once_flags_violation_and_exit_code(self, tmp_path):
        root = tmp_path / "store"
        d = root / "r" / "t1"
        write_wal(d, [invoke_op(0, "write", 1, index=0),
                      ok_op(0, "write", 1, index=1),
                      invoke_op(0, "read", None, index=2),
                      ok_op(0, "read", 9, index=3)])
        rc = cli.main(cli.standard_commands(),
                      ["serve-checker", str(root), "--once",
                       "--backend", "host"])
        assert rc == 1
        lj = json.loads((d / "live.json").read_text())
        assert lj["verdict-so-far"] is False

    def test_once_clean_exits_zero(self, tmp_path):
        root = tmp_path / "store"
        d = root / "r" / "t1"
        write_wal(d, [invoke_op(0, "write", 1, index=0),
                      ok_op(0, "write", 1, index=1)])
        rc = cli.main(cli.standard_commands(),
                      ["serve-checker", str(root), "--once",
                       "--backend", "host"])
        assert rc == 0
        assert json.loads((d / "live.json")
                          .read_text())["verdict-so-far"] is True

    def test_missing_root_exits_255(self, tmp_path):
        rc = cli.main(cli.standard_commands(),
                      ["serve-checker", str(tmp_path / "nope"),
                       "--once"])
        assert rc == 255

    def test_suite_command_map_carries_serve_checker(self):
        cmds = cli.single_test_cmd(lambda o: {})
        assert "serve-checker" in cmds


class TestLiveWeb:
    def _mk_flagged_store(self):
        d = store.BASE / "webrun" / "t1"
        write_wal(d, [invoke_op(0, "write", 1, index=0),
                      ok_op(0, "write", 1, index=1),
                      invoke_op(0, "read", None, index=2),
                      ok_op(0, "read", 9, index=3)])
        sched = LiveScheduler(store.BASE, backend="host",
                              scan_every=1)
        sched.drain(20)
        sched.close()

    def test_live_pages_render(self):
        self._mk_flagged_store()
        idx = web.live_index_html().decode()
        assert "webrun" in idx and "false" in idx
        page = web.live_run_html("webrun", "t1").decode()
        assert "verdict so far: false" in page
        assert "Violation flags" in page
        assert "live-host" in page            # engine column
        assert "Micro-batch dispatches" in page

    def test_live_routes_over_http(self):
        self._mk_flagged_store()
        import urllib.request
        srv = web.serve(host="127.0.0.1", port=0, block=False)
        base = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            with urllib.request.urlopen(base + "/live",
                                        timeout=10) as r:
                assert r.status == 200 and b"webrun" in r.read()
            with urllib.request.urlopen(base + "/live/webrun/t1",
                                        timeout=10) as r:
                assert r.status == 200
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                body = r.read().decode()
                assert "live_window_queue_depth" in body
                assert "live_flags_total" in body
        finally:
            srv.shutdown()
            srv.server_close()

    def test_empty_live_index(self):
        idx = web.live_index_html().decode()
        assert "serve-checker" in idx         # the hint renders


# ---------------------------------------------------------------------------
# acceptance: in-flight detection during a real run
# ---------------------------------------------------------------------------

class TestAcceptance:
    def test_flag_lands_before_teardown_with_concurrent_clean_tenant(
            self, tmp_path):
        """The ISSUE 6 acceptance scenario: a kvd-shaped run executes
        for real (core.run over the local atom transport, WAL written
        op by op) with a violation planted mid-stream; the checker
        service, tailing concurrently, must flag it — with dispatch
        record, engine, and plan-cache attribution — BEFORE the run's
        teardown completes.  A second clean tenant fed in parallel
        stays green and shares at least one micro-batched dispatch
        with the run (asserted via the journaled dispatch ids)."""
        state = tst.Atom()
        client = tst.atom_client(state)
        base_invoke = client.invoke
        n_ops = [0]

        def lying_slow_invoke(test, op):
            time.sleep(0.006)
            out = base_invoke(test, op)
            n_ops[0] += 1
            if (op.f == "read" and out.type == "ok"
                    and n_ops[0] > 150):
                return out.assoc(value=42)    # planted mid-stream
            return out
        client.invoke = lying_slow_invoke
        test = dict(tst.noop_test(), **{
            "name": "kvd-live-acceptance",
            "nodes": ["n1"],
            "concurrency": 4,
            "db": tst.atom_db(state),
            "client": client,
            "generator": gen.nemesis(gen.void,
                                     gen.limit(600, gen.cas)),
            "checker": ck.linearizable(
                {"model": models.CASRegister(0)}),
        })

        svc = CheckerService(store.BASE, poll_interval=0.015,
                             backend="device", bits=4, max_states=16,
                             scan_every=1, max_window_events=64)

        clean_dir = store.BASE / "clean-tenant" / "t1"
        clean_dir.mkdir(parents=True)
        clean_wal = HistoryWAL(clean_dir / "history.wal", fsync=False)
        stop_feeder = threading.Event()

        def feed_clean():
            # bounded value domain (0..4, the run's own shape) so the
            # clean lanes' state tables bucket like the run's
            i = 0
            vals = {}
            while not stop_feeder.is_set():
                k = i % 3
                v = (i // 2) % 5
                if k in vals and i % 2:
                    clean_wal.append(invoke_op(0, "read",
                                               KV(k, None), index=i))
                    clean_wal.append(ok_op(0, "read",
                                           KV(k, vals[k]),
                                           index=i + 1))
                else:
                    vals[k] = v
                    clean_wal.append(invoke_op(0, "write",
                                               KV(k, v), index=i))
                    clean_wal.append(ok_op(0, "write", KV(k, v),
                                           index=i + 1))
                i += 2
                time.sleep(0.01)

        feeder = threading.Thread(target=feed_clean, daemon=True)
        run_done_wall = [None]

        def run_test():
            core.run(test)
            run_done_wall[0] = time.time()

        runner = threading.Thread(target=run_test, daemon=True)
        svc.start()
        feeder.start()
        runner.start()
        runner.join(timeout=120)
        assert run_done_wall[0] is not None, "run never finished"
        stop_feeder.set()
        feeder.join(timeout=10)
        clean_wal.close()
        (clean_dir / "results.json").write_text('{"valid?": true}')
        # let the service drain both tails to completion, then stop
        deadline = time.time() + 30
        while time.time() < deadline \
                and len(svc.scheduler.finished) < 2:
            time.sleep(0.05)
        svc.stop()

        run_dir = next((store.BASE / "kvd-live-acceptance").iterdir())
        ev = telemetry.read_events(run_dir / "live.jsonl")
        flags = [e for e in ev if e["type"] == "live-flag"]
        assert flags, "violation never flagged live"
        flag = flags[0]
        # the flag carries its dispatch attribution
        assert flag["engine"] in ("live-jit", "live-host")
        assert flag["cache"] in ("hit", "miss", "n/a")
        assert flag["dispatch_id"]
        assert flag["value"] == 42
        assert flag["detection_lag_s"] is not None
        assert flag["detection_lag_s"] < 30
        # ...and landed BEFORE the run (teardown + analysis) completed
        assert flag["t"] < run_done_wall[0], \
            (flag["t"], run_done_wall[0])
        # the /live page renders the flag
        page = web.live_run_html("kvd-live-acceptance",
                                 run_dir.name).decode()
        assert "verdict so far: false" in page
        assert "42" in page
        # concurrent clean tenant: green, and shared >= 1 dispatch
        clean_ev = telemetry.read_events(clean_dir / "live.jsonl")
        lj = json.loads((clean_dir / "live.json").read_text())
        assert lj["verdict-so-far"] is True
        run_ids = {e["dispatch_id"] for e in ev
                   if e["type"] == "live-dispatch"}
        clean_ids = {e["dispatch_id"] for e in clean_ev
                     if e["type"] == "live-dispatch"}
        assert run_ids & clean_ids, \
            "run and clean tenant never shared a micro-batch"
        # post-hoc analyze agrees (the authoritative verdict)
        res = json.loads((run_dir / "results.json").read_text())
        assert res.get("valid?") is False
