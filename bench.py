#!/usr/bin/env python
"""Headline benchmark: linearizability-check throughput on a 1M-op
multi-key independent-register workload (BASELINE.json config 3 — the
reference's own scaling recipe: `jepsen.independent` shards a test over
many keys with short per-key histories *because* "linearizability ...
requires we verify only short histories", independent.clj:2-7; the etcd
suite checks 300 ops/key, etcd.clj:167-179).

Engine: jepsen_tpu.ops.wgl_seg.check_many — every key is one lane of a
batched bitmap frontier kernel (dense (open-call-mask × model-state)
configuration space, no sorting), all keys advance in lockstep on
device; the default register-delta form ships only per-return invoke
deltas and maintains the open-call set in on-device registers, with a
statically-unrolled closure (exact in <= R rounds).  Baseline: jepsen_tpu.ops.wgl_cpu, the knossos-equivalent
just-in-time-linearization oracle, timed on a sample of the same keys
(the reference delegates this work to knossos on a 32 GB JVM heap,
jepsen/project.clj:30, and publishes no throughput numbers of its own —
see BASELINE.md).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "ops/sec", "vs_baseline": N}
value       = steady-state device throughput over all keys: best of
              three warm runs by kernel time (the cold run pays
              one-time XLA compilation, cached persistently under
              .cache/jax so re-runs skip it)
vs_baseline = device throughput / CPU-oracle throughput.

Secondary stderr lines report BASELINE config 2 (one 100k-op
single-register history via the segment-parallel transfer-matrix
path), config 4 (SCC cycle detection as bool-matmul reachability), and
config 5 (1M-element commutative set folds) — each verified against a
known-correct structure before the headline prints.
"""

import json
import os
import pathlib
import random
import sys
import tempfile
import time

import jax
import numpy as np

from jepsen_tpu.ops import planner

# Persistent compiled-plan cache (ISSUE 8): JAX_COMPILATION_CACHE_DIR
# when set, else the checkout's .cache/jax/, so re-runs AND fresh
# CLI/suite processes skip the cold compile.
planner.ensure_persistent_cache()

from jepsen_tpu import models
from jepsen_tpu.history import (History, fail_op, invoke_op, ok_op,
                                pack_history)
from jepsen_tpu.ops import wgl_cpu, wgl_cpu_native, wgl_deep, wgl_seg


def timed(fn, n: int = 3):
    """(min, median, last_result) over n runs — the min isolates
    kernel time from host noise, the median makes regressions under
    the noise floor visible run over run."""
    ts, out = [], None
    for _ in range(n):
        t0 = time.monotonic()
        out = fn()
        ts.append(time.monotonic() - t0)
    ts.sort()
    return ts[0], ts[len(ts) // 2], out

N_KEYS = 3400
OPS_PER_KEY = 300
CONCURRENCY = 5          # per key — the etcd workload shape
CPU_SAMPLE_KEYS = 100   # large enough that the oracle rate is stable
SINGLE_N_OPS = 100_000   # config 2: the north-star single history
SINGLE_CPU_CAP = 300     # seconds before the CPU oracle is cut off
HARD_N_OPS = 50_000      # config 6: the crashed-ops hard regime
HARD_CPU_CAP = 180


def make_history(n_ops: int, concurrency: int, seed: int = 7,
                 vmax: int = 4, crash_rate: float = 0.0,
                 max_open: int = 0, crash_vmax: int = 0) -> History:
    """An etcd-shaped register workload (r/w/cas mix, etcd.clj:145-147)
    executed against a sequentially-consistent in-memory register with
    process interleaving.  With crash_rate, that fraction of calls
    time out (:info, never taking effect) — the nemesis-run shape the
    reference calls its worst cost driver (a crashed op stays
    concurrent with the entire rest of the history,
    doc/tutorial/06-refining.md:12-19).  max_open > 0 bounds the
    simultaneously-open NORMAL calls (bursty interleaving: many worker
    processes, bounded overlap depth — the live-process count still
    spans `concurrency`)."""
    from jepsen_tpu.history import info_op

    rng = random.Random(seed)
    ops, value = [], None
    open_ops: dict = {}  # process -> (completion op) pending flush
    procs = list(range(concurrency))
    i = 0
    while i < n_ops:
        p = rng.choice(procs)
        if p in open_ops:
            ops.append(open_ops.pop(p))
            continue
        if max_open and len(open_ops) >= max_open:
            if open_ops:
                ops.append(open_ops.pop(rng.choice(list(open_ops))))
            continue
        i += 1
        f = rng.choice(("read", "read", "write", "cas"))
        if crash_rate and rng.random() < crash_rate:
            # timed-out call: invoke journaled, :info completion, no
            # effect on the register (the DB never applied it).
            # crash_vmax > 0 restricts CRASHED ops' values to
            # 0..crash_vmax so a subtle-violation planter can pick a
            # legal value that is provably not crash-explainable
            cm = crash_vmax or vmax
            v = (None if f == "read" else rng.randint(0, cm)
                 if f == "write" else
                 [rng.randint(0, cm), rng.randint(0, cm)])
            ops.append(invoke_op(p, f, v))
            ops.append(info_op(p, f, v))
            continue
        if f == "read":
            ops.append(invoke_op(p, "read", None))
            open_ops[p] = ok_op(p, "read", value)
        elif f == "write":
            v = rng.randint(0, vmax)
            ops.append(invoke_op(p, "write", v))
            value = v
            open_ops[p] = ok_op(p, "write", v)
        else:
            old, new = rng.randint(0, vmax), rng.randint(0, vmax)
            ops.append(invoke_op(p, "cas", [old, new]))
            if value == old:
                value = new
                open_ops[p] = ok_op(p, "cas", [old, new])
            else:
                open_ops[p] = fail_op(p, "cas", [old, new])
    for comp in open_ops.values():
        ops.append(comp)
    h = History(ops).index()
    # The framework's run loop journals ops into a ColumnJournal as
    # they land (core.py), so a real history's columnar representation
    # exists before analysis starts; building it here at construction
    # time reproduces that (the scan engines then never walk Python
    # objects).  The CPU oracle still receives the Op objects.
    h.attach_packed(pack_history(h))
    return h


def plant_stale_read(h: History, frac: float, vmax: int,
                     forbidden=()) -> "tuple[int, int] | None":
    """Plant a SUBTLE violation (VERDICT r3 #4): rewrite one ok-read to
    a LEGAL value w that no linearization can produce — w is excluded
    from the read's concurrency window (not the register value at the
    window start, not written/cas-targeted by any call whose own
    window intersects it) — instead of an out-of-domain constant.  The
    violation is invisible to any local scan (w is written legitimately
    elsewhere in the history) and refuting it requires the search to
    carry the true state set to the read's depth.  `forbidden` removes
    further candidates (e.g. every crashed call's value, so the
    crash-relaxed tier's epsilon-jumps cannot explain w either).
    Mutates h in place; returns (op_position, planted_value) or None.

    Window analysis: walk the ops maintaining the sequential register
    value and each process's open invoke; for the chosen read, V = the
    value at its invoke + every write value / cas target of calls
    whose [invoke, complete] intersects the read's window.  Only such
    calls can linearize inside the window, so any legal w outside V
    (and outside `forbidden`) makes the read impossible."""
    ops = h.ops
    n = len(ops)
    value_at = np.zeros(n + 1, np.int64)     # seq value BEFORE op i
    cur = -1                                 # None encoded as -1
    for i, o in enumerate(ops):
        value_at[i] = cur
        if o.type == "ok" and o.f == "write":
            cur = o.value
        elif o.type == "ok" and o.f == "cas":
            cur = o.value[1]
    value_at[n] = cur
    # per-call (invoke_pos, completion_pos|inf, candidate value):
    # a call can linearize inside a window iff its own span intersects
    # it; crashed calls (no completion) stay open to the end
    pend: dict = {}
    inv_of: dict = {}
    inv_pos, comp_pos, wval = [], [], []
    for i, o in enumerate(ops):
        if o.type == "invoke":
            pend[o.process] = len(inv_pos)
            inv_pos.append(i)
            comp_pos.append(n)
            v = None
            if o.f == "write":
                v = o.value
            elif o.f == "cas":
                v = o.value[1]
            wval.append(-1 if v is None else int(v))
        elif o.process in pend:
            c = pend.pop(o.process)
            comp_pos[c] = i
            inv_of[i] = inv_pos[c]
    inv_pos = np.asarray(inv_pos, np.int64)
    comp_pos = np.asarray(comp_pos, np.int64)
    wval = np.asarray(wval, np.int64)
    reads = [i for i, o in enumerate(ops)
             if o.type == "ok" and o.f == "read"
             and o.value is not None and i in inv_of]
    start = int(len(reads) * frac)
    for i in reads[start:] + reads[:start]:
        lo = inv_of[i]
        # A write X can be the read's last-write in SOME linearization
        # iff X invokes before the read completes AND no write Y is
        # FORCED between them (Y forced <=> inv_Y > comp_X and
        # comp_Y < lo).  With M = max invoke position of writes
        # completing before the window, X qualifies iff comp_X >= M —
        # this keeps real-time-maximal writes that finish before the
        # window opens (ordering them last is legal), which a naive
        # comp >= lo overlap test wrongly excludes.
        before = (comp_pos < lo) & (wval >= 0)
        M = int(inv_pos[before].max()) if before.any() else 0
        touch = (inv_pos <= i) & (comp_pos >= M) & (wval >= 0)
        V = set(int(x) for x in np.unique(wval[touch]))
        V.add(int(value_at[lo]))
        w = next((x for x in range(vmax + 1)
                  if x not in V and x not in forbidden), None)
        if w is None:
            continue
        ops[i].value = w
        h.attach_packed(pack_history(h))
        return i, w
    return None


def bench_live() -> dict:
    """ISSUE 6: the always-on live verification service, priced as a
    service rather than a one-shot engine — N concurrent synthetic
    tenants, each a WAL-fed register run, checked incrementally by the
    LiveScheduler with cross-tenant shape-bucketed micro-batches.

    Three measurements:
      * sustained drain throughput (ops/s across all tenants, warm
        plan cache — the steady-state capacity of one checker daemon);
      * p99 op-append→verdict lag under paced real-time feeders
        (RATE ops/s per tenant appended with wall stamps, the
        scheduler ticking between slices), exact quantile over every
        checked window's journaled lag;
      * detection lag for one violation planted mid-stream in one
        tenant (append→flag, from the live-flag event).

    vs_baseline is the numpy host engine draining the same tenant
    shape (rate vs rate).  Returns the tail-JSON stats dict."""
    import shutil
    import tempfile

    from jepsen_tpu import telemetry as telemetry_mod
    from jepsen_tpu.history import HistoryWAL
    from jepsen_tpu.live import engine as live_engine
    from jepsen_tpu.live.scheduler import LiveScheduler

    N_TEN = 4
    OPS_SUSTAINED = 25_000            # per tenant
    OPS_HOST = 5_000                  # per tenant, host baseline
    OPS_RT = 4_000                    # per tenant, paced phase
    RATE = 2_000                      # completed ops/s per tenant
    rootbase = pathlib.Path(tempfile.mkdtemp(prefix="bench-live-"))

    def write_store(sub: str, n_ops: int, seeds: list) -> tuple:
        root = rootbase / sub
        n_inv = 0
        for i, seed in enumerate(seeds):
            d = root / f"tenant{i}" / "t1"
            d.mkdir(parents=True)
            h = make_history(n_ops, 4, seed=seed)
            n_inv += sum(1 for o in h if o.is_invoke)
            wal = HistoryWAL(d / "history.wal", fsync=False)
            for o in h:
                wal.append(o)
            wal.close()
            (d / "results.json").write_text('{"valid?": true}')
        return root, n_inv

    try:
        # warm the compiled-plan cache on a small same-shaped store so
        # the sustained figure is the no-compile steady state
        warm_root, _ = write_store("warm", 2_000,
                                   [7 + i for i in range(N_TEN)])
        ws = LiveScheduler(warm_root, backend="device", scan_every=1)
        ws.drain()
        ws.close()

        miss0 = live_engine.plan_cache_stats()["miss"]
        main_root, n_inv = write_store(
            "main", OPS_SUSTAINED, [100 + i for i in range(N_TEN)])
        sched = LiveScheduler(main_root, backend="device",
                              scan_every=1)
        t0 = time.monotonic()
        sched.drain()
        drain_s = time.monotonic() - t0
        clean = sched.flags_total == 0
        sched.close()
        new_misses = live_engine.plan_cache_stats()["miss"] - miss0
        sustained = n_inv / drain_s

        # host-engine baseline: same tenant shape, quarter load
        host_root, n_inv_h = write_store(
            "host", OPS_HOST, [300 + i for i in range(N_TEN)])
        hs = LiveScheduler(host_root, backend="host", scan_every=1)
        t0 = time.monotonic()
        hs.drain()
        host_s = time.monotonic() - t0
        hs.close()
        host_rate = n_inv_h / host_s

        # paced real-time phase with one planted mid-stream violation
        rt_root = rootbase / "rt"
        feeders = []
        for i in range(N_TEN):
            d = rt_root / f"rt{i}" / "t1"
            d.mkdir(parents=True)
            ops = list(make_history(OPS_RT, 4, seed=500 + i))
            feeders.append((d, ops))
        planted_at = None
        d0, ops0 = feeders[0]
        for j, o in enumerate(ops0):
            if (o.is_ok and o.f == "read" and o.value is not None
                    and j > len(ops0) * 0.6):
                o.value = 99          # vmax=4: provably never written
                planted_at = j
                break
        wals = [HistoryWAL(d / "history.wal", fsync=False)
                for d, _ in feeders]
        rt = LiveScheduler(rt_root, backend="device", scan_every=1)
        pos = [0] * N_TEN
        t_start = time.monotonic()
        while any(pos[i] < len(feeders[i][1]) for i in range(N_TEN)):
            # entries ≈ 2 per completed op: pace the entry stream
            target = int((time.monotonic() - t_start) * RATE * 2) + 8
            for i, (_d, ops) in enumerate(feeders):
                stop = min(target, len(ops))
                while pos[i] < stop:
                    wals[i].append(ops[pos[i]])
                    pos[i] += 1
            rt.tick()
        for w in wals:
            w.close()
        for d, _ in feeders:
            (d / "results.json").write_text('{"valid?": true}')
        rt.drain()
        rt.close()

        lags: list = []
        det_lag = None
        for d, _ in feeders:
            for ev in telemetry_mod.read_events(d / "live.jsonl"):
                if ev.get("type") == "live-window" \
                        and isinstance(ev.get("lag_s"), (int, float)):
                    lags.append(ev["lag_s"])
                elif ev.get("type") == "live-flag" and det_lag is None:
                    det_lag = ev.get("detection_lag_s")
        lags.sort()
        p99 = lags[min(int(0.99 * len(lags)), len(lags) - 1)] \
            if lags else None
    finally:
        shutil.rmtree(rootbase, ignore_errors=True)

    if not clean:
        print(json.dumps({"metric": "ERROR: live checker flagged a "
                          "clean sustained-drain tenant", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return {"error": True}
    if planted_at is not None and det_lag is None:
        print(json.dumps({"metric": "ERROR: live checker missed the "
                          "planted mid-stream violation", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return {"error": True}

    print(json.dumps({
        "metric": (f"live verification service: {N_TEN} concurrent "
                   f"tenants x {OPS_SUSTAINED // 1000}k-op register "
                   "WALs, sustained incremental drain (warm plan "
                   "cache, cross-tenant micro-batched windows) vs "
                   "the numpy host engine"),
        "value": round(sustained, 1),
        "unit": "ops/sec",
        "vs_baseline": round(sustained / host_rate, 2)}),
        file=sys.stderr)
    print(json.dumps({
        "metric": (f"live p99 op-append->verdict lag under {N_TEN} "
                   f"tenants x {RATE} ops/s paced feeders "
                   f"({len(lags)} windows); planted-violation "
                   "detection lag "
                   f"{det_lag if det_lag is not None else 'n/a'}s"),
        "value": round(p99, 4) if p99 is not None else 0,
        "unit": "seconds",
        "vs_baseline": round(det_lag, 4)
        if det_lag is not None else 0}),
        file=sys.stderr)
    print(f"# live: sustained {sustained:.0f} ops/s over "
          f"{N_TEN}x{OPS_SUSTAINED} ops in {drain_s:.2f}s "
          f"({new_misses} plan compiles after warmup); host engine "
          f"{host_rate:.0f} ops/s; paced-phase p99 lag "
          f"{p99 if p99 is not None else float('nan'):.4f}s, "
          f"detection lag {det_lag}s", file=sys.stderr)
    return {"live_sustained_ops_s": round(sustained, 1),
            "live_p99_lag_s": round(p99, 4) if p99 is not None
            else None,
            "live_detect_lag_s": round(det_lag, 4)
            if det_lag is not None else None,
            "live_vs_host": round(sustained / host_rate, 2)}


def bench_fleet() -> dict:
    """ISSUE 14: the horizontal serve-checker fleet, priced two ways.

    (a) **2-worker vs 1-worker sustained drain** over the PR 6
    paced-feeder tenant shape: the same N-tenant register store
    drained by one lease-less scheduler vs two lease-coordinated
    workers ticking concurrently (leases partition the tenants; the
    workers share nothing but the filesystem).  On a small CPU host
    the two tick loops contend for the GIL and the device, so the
    ratio is an honest "what does a second local worker buy" number,
    not a marketing 2x — real fleets put workers on separate hosts.

    (b) **takeover gap**: two lease-owned workers drain paced
    feeders; one worker's tick loop stops dead (the in-process
    SIGKILL analog — no close, no release); wall seconds until the
    survivor's journaled `lease-takeover` lands is
    `live_fleet_takeover_s` (lease TTL disclosed; the subprocess
    twin of this scenario is pinned by tests/test_fleet.py kill9).

    CPU-scaled per the PR 11 cpu_count discipline; scaled values ride
    the metric labels and the bench_cpus tail key."""
    import shutil
    import tempfile
    import threading

    from jepsen_tpu import telemetry as telemetry_mod
    from jepsen_tpu.history import HistoryWAL
    from jepsen_tpu.live.scheduler import LiveScheduler

    cpus = os.cpu_count() or 1
    n_ten = 4 if cpus >= 8 else 2
    ops = int(os.environ.get("JEPSEN_TPU_BENCH_FLEET_OPS",
                             12_000 if cpus >= 8 else 3_000))
    ttl = 0.4
    rootbase = pathlib.Path(tempfile.mkdtemp(prefix="bench-fleet-"))

    def write_store(sub: str, n_ops: int, seed0: int) -> tuple:
        root = rootbase / sub
        n_inv = 0
        for i in range(n_ten):
            d = root / f"tenant{i}" / "t1"
            d.mkdir(parents=True)
            h = make_history(n_ops, 4, seed=seed0 + i)
            n_inv += sum(1 for o in h if o.is_invoke)
            wal = HistoryWAL(d / "history.wal", fsync=False)
            for o in h:
                wal.append(o)
            wal.close()
            (d / "results.json").write_text('{"valid?": true}')
        return root, n_inv

    def drain_fleet(root, n_workers: int) -> float:
        """Wall seconds for N lease-coordinated workers (threads) to
        finish every tenant."""
        scheds = [LiveScheduler(root, backend="device", scan_every=1,
                                worker_id=f"bw{i}", lease_ttl=5.0)
                  for i in range(n_workers)]
        stop = threading.Event()

        def drive(s):
            while not stop.is_set():
                s.tick()
                if not s.tenants and not s._has_new_bytes():
                    time.sleep(0.002)

        ths = [threading.Thread(target=drive, args=(s,), daemon=True)
               for s in scheds]
        t0 = time.monotonic()
        for t in ths:
            t.start()
        while sum(len(s.finished) for s in scheds) < n_ten \
                and time.monotonic() - t0 < 1200:
            time.sleep(0.01)
        wall = time.monotonic() - t0
        stop.set()
        for t in ths:
            t.join(5)
        flags = sum(s.flags_total for s in scheds)
        for s in scheds:
            s.close()
        assert flags == 0, "fleet bench flagged a clean tenant"
        return wall

    try:
        # warm the plan cache on a small same-shaped store
        warm_root, _ = write_store("warm", 1_000, 7)
        ws = LiveScheduler(warm_root, backend="device", scan_every=1)
        ws.drain()
        ws.close()

        root1, n_inv = write_store("single", ops, 100)
        s1 = LiveScheduler(root1, backend="device", scan_every=1)
        t0 = time.monotonic()
        s1.drain()
        one_s = time.monotonic() - t0
        clean = s1.flags_total == 0
        s1.close()
        if not clean:
            print(json.dumps({"metric": "ERROR: fleet bench single-"
                              "worker flagged a clean tenant",
                              "value": 0, "unit": "ops/sec",
                              "vs_baseline": 0}))
            return {"error": True}
        rate1 = n_inv / one_s

        root2, n_inv2 = write_store("fleet", ops, 100)  # same content
        two_s = drain_fleet(root2, 2)
        rate2 = n_inv2 / two_s

        # takeover gap: paced feeders, stop one worker dead
        root3 = rootbase / "takeover"
        feeders = []
        for i in range(n_ten):
            d = root3 / f"rt{i}" / "t1"
            d.mkdir(parents=True)
            feeders.append((d, list(make_history(ops // 4, 4,
                                                 seed=700 + i))))
        wals = [HistoryWAL(d / "history.wal", fsync=False)
                for d, _ in feeders]
        A = LiveScheduler(root3, backend="device", scan_every=1,
                          worker_id="fA", lease_ttl=ttl)
        B = LiveScheduler(root3, backend="device", scan_every=1,
                          worker_id="fB", lease_ttl=ttl)
        a_stop, all_stop = threading.Event(), threading.Event()

        def drive2(s, gate):
            while not all_stop.is_set() and not gate.is_set():
                s.tick()

        tha = threading.Thread(target=drive2, args=(A, a_stop),
                               daemon=True)
        thb = threading.Thread(target=drive2,
                               args=(B, threading.Event()),
                               daemon=True)
        tha.start()
        thb.start()
        pos = [0] * n_ten
        t0 = time.monotonic()
        kill_at = None
        gap = None
        while any(pos[i] < len(feeders[i][1])
                  for i in range(n_ten)) \
                or kill_at is None or gap is None:
            el = time.monotonic() - t0
            target = int(el * 2_000) + 8
            for i, (_d, fops) in enumerate(feeders):
                stop_i = min(target, len(fops))
                while pos[i] < stop_i:
                    wals[i].append(fops[pos[i]])
                    pos[i] += 1
            if kill_at is None and el > 0.5 and A.tenants:
                a_stop.set()           # the in-process SIGKILL analog
                tha.join(5)
                kill_at = time.monotonic()
            if kill_at is not None and gap is None:
                for d, _f in feeders:
                    p = d / "live.jsonl"
                    if not p.exists():
                        continue
                    if any(e.get("type") == "lease-takeover"
                           for e in telemetry_mod.read_events(p)):
                        gap = time.monotonic() - kill_at
                        break
            if time.monotonic() - t0 > 300:
                break
            time.sleep(0.01)
        for w in wals:
            w.close()
        for d, _f in feeders:
            (d / "results.json").write_text('{"valid?": true}')
        all_stop.set()
        thb.join(5)
        B.drain()
        A.close()
        B.close()
    finally:
        shutil.rmtree(rootbase, ignore_errors=True)

    if gap is None:
        print(json.dumps({"metric": "ERROR: fleet bench survivor "
                          "never took over the dead worker's "
                          "tenants", "value": 0, "unit": "s",
                          "vs_baseline": 0}))
        return {"error": True}

    print(json.dumps({
        "metric": (f"serve-checker fleet: 2 lease-coordinated "
                   f"workers vs 1 over {n_ten} tenants x "
                   f"{ops // 1000}k-op register WALs, sustained "
                   "drain (same host: GIL/device contention "
                   "disclosed — fleets scale across hosts)"),
        "value": round(rate2, 1),
        "unit": "ops/sec",
        "vs_baseline": round(rate2 / rate1, 2)}), file=sys.stderr)
    print(json.dumps({
        "metric": (f"fleet takeover gap after a worker dies "
                   f"mid-drain (lease ttl {ttl}s, {n_ten} paced "
                   "tenants; wall from death to the survivor's "
                   "journaled lease-takeover)"),
        "value": round(gap, 3),
        "unit": "seconds",
        "vs_baseline": round(gap / ttl, 2)}), file=sys.stderr)
    print(f"# fleet: 1-worker {rate1:.0f} ops/s ({one_s:.2f}s), "
          f"2-worker {rate2:.0f} ops/s ({two_s:.2f}s); takeover gap "
          f"{gap:.3f}s at ttl {ttl}s", file=sys.stderr)
    return {"live_fleet_takeover_s": round(gap, 3),
            "live_fleet_vs_single": round(rate2 / rate1, 2),
            "live_fleet_2w_ops_s": round(rate2, 1),
            "live_fleet_ttl_s": ttl}


def bench_live_txn() -> dict:
    """ISSUE 18: the incremental transactional (Elle) tier, priced
    three ways.

    (a) **sustained txn drain**: N tenants of clean paced list-append
    mop WALs drained end-to-end by one scheduler; value is client ops
    (invokes) per second through feed -> delta -> packed-plane update
    -> warm closure -> classify.  Clean streams must stay flag-free
    (asserted, like bench_fleet).

    (b) **commit -> anomaly-flag detection lag**: a paced stream with
    a G-single planted mid-way; wall seconds from appending the
    planted txn's ok record to the durable `live-flag` landing in
    live.jsonl.  This is the headline the incremental mode exists
    for: the one-shot checker's answer arrives only after teardown.

    (c) **txn takeover gap**: two lease-coordinated workers over
    paced txn feeds; worker A's tick loop stops dead; wall to the
    survivor's journaled `lease-takeover`.  The survivor resumes from
    A's checkpointed frontier (resumed txns disclosed) — the
    subprocess twin is pinned by tests/test_txn_fleet.py."""
    import shutil
    import tempfile
    import threading

    from jepsen_tpu import telemetry as telemetry_mod
    from jepsen_tpu.campaign import TxnFleetTarget
    from jepsen_tpu.history import HistoryWAL
    from jepsen_tpu.live.scheduler import LiveScheduler

    cpus = os.cpu_count() or 1
    n_ten = 2
    txns = int(os.environ.get("JEPSEN_TPU_BENCH_TXN_N",
                              600 if cpus >= 8 else 200))
    ttl = 0.4
    NEVER = 10 ** 9                    # plant position that never fires
    rootbase = pathlib.Path(tempfile.mkdtemp(prefix="bench-txn-"))
    mk = TxnFleetTarget(txns_per_tenant=txns)

    def write_store(sub: str, seed0: int) -> tuple:
        root = rootbase / sub
        n_inv = 0
        for i in range(n_ten):
            d = root / f"txn{i}" / "t1"
            d.mkdir(parents=True)
            ops = mk._txn_stream(random.Random(seed0 + i),
                                 "g-single", NEVER)
            n_inv += sum(1 for o in ops if o.type == "invoke")
            wal = HistoryWAL(d / "history.wal", fsync=False)
            for o in ops:
                wal.append(o)
            wal.close()
            (d / "results.json").write_text('{"valid?": true}')
        return root, n_inv

    gap = None
    resumed = 0
    lat_lag = None
    lattice_classes: list = []
    try:
        # (a) sustained drain, clean streams
        root1, n_inv = write_store("drain", 100)
        s1 = LiveScheduler(root1, backend="host", scan_every=1)
        t0 = time.monotonic()
        s1.drain()
        drain_s = time.monotonic() - t0
        clean = s1.flags_total == 0
        s1.close()
        if not clean:
            print(json.dumps({"metric": "ERROR: txn bench flagged a "
                              "clean stream", "value": 0,
                              "unit": "ops/sec", "vs_baseline": 0}))
            return {"error": True}
        rate = n_inv / drain_s

        # (b) commit -> flag detection lag on a paced planted stream
        root2 = rootbase / "lag"
        d2 = root2 / "rt0" / "t1"
        d2.mkdir(parents=True)
        plant_at = txns // 2
        ops2 = mk._txn_stream(random.Random(5), "g-single", plant_at)
        wal2 = HistoryWAL(d2 / "history.wal", fsync=False)
        s2 = LiveScheduler(root2, backend="host", scan_every=1)
        stop2 = threading.Event()

        def drive(s, stop):
            while not stop.is_set():
                s.tick()

        th2 = threading.Thread(target=drive, args=(s2, stop2),
                               daemon=True)
        th2.start()
        # the planted pattern is 3 txns (6 records) ending at the
        # anomalous read's ok; find that record's position
        plant_end = None
        pos2 = 0
        t0 = time.monotonic()
        planted_t = None
        lag = None
        for o in ops2:
            wal2.append(o)
            pos2 += 1
            if o.type == "ok" and isinstance(o.value, list) \
                    and any(m[0] == "r" and m[1] == 101
                            for m in o.value):
                plant_end = pos2
                planted_t = time.monotonic()
            time.sleep(0.001)
        wal2.close()
        (d2 / "results.json").write_text('{"valid?": false}')
        deadline = time.monotonic() + 120
        while lag is None and time.monotonic() < deadline:
            p = d2 / "live.jsonl"
            if p.exists() and any(
                    e.get("type") == "live-flag"
                    for e in telemetry_mod.read_events(p)):
                lag = time.monotonic() - planted_t
            time.sleep(0.005)
        stop2.set()
        th2.join(5)
        s2.drain()
        s2.close()
        if lag is None or plant_end is None:
            print(json.dumps({"metric": "ERROR: txn bench planted "
                              "G-single never flagged", "value": 0,
                              "unit": "s", "vs_baseline": 0}))
            return {"error": True}

        # (b2) commit -> lattice-flag detection lag (ISSUE 20): a
        # monotonic-writes plant — the weakest session rung, which
        # the Adya tier cannot name — paced the same way; wall from
        # the inverted read's ok record to the durable lattice flag
        root2b = rootbase / "lat"
        d2b = root2b / "lt0" / "t1"
        d2b.mkdir(parents=True)
        ops2b = mk._txn_stream(random.Random(6), "mw", plant_at)
        wal2b = HistoryWAL(d2b / "history.wal", fsync=False)
        s2b = LiveScheduler(root2b, backend="host", scan_every=1)
        stop2b = threading.Event()
        th2b = threading.Thread(target=drive, args=(s2b, stop2b),
                                daemon=True)
        th2b.start()
        planted_tb = None
        lat_lag = None
        for o in ops2b:
            wal2b.append(o)
            if o.type == "ok" and isinstance(o.value, list) \
                    and any(m[0] == "r" and m[1] == 105
                            for m in o.value):
                planted_tb = time.monotonic()
            time.sleep(0.001)
        wal2b.close()
        (d2b / "results.json").write_text('{"valid?": false}')
        deadline = time.monotonic() + 120
        while lat_lag is None and time.monotonic() < deadline:
            p = d2b / "live.jsonl"
            if p.exists() and any(
                    e.get("type") == "live-flag"
                    and e.get("lane") == "txn:monotonic-writes"
                    for e in telemetry_mod.read_events(p)):
                lat_lag = time.monotonic() - planted_tb
            time.sleep(0.005)
        stop2b.set()
        th2b.join(5)
        s2b.drain()
        lattice_classes = []
        try:
            with open(d2b / "live.json") as f:
                lattice_classes = ((json.load(f).get("txn") or {})
                                   .get("lattice_classes") or [])
        except (OSError, json.JSONDecodeError):
            pass
        s2b.close()
        if lat_lag is None:
            print(json.dumps({
                "metric": "ERROR: txn bench planted monotonic-writes "
                          "never lattice-flagged", "value": 0,
                "unit": "s", "vs_baseline": 0}))
            return {"error": True}

        # (c) takeover gap with checkpointed-frontier resume
        root3 = rootbase / "takeover"
        feeders = []
        for i in range(n_ten):
            d = root3 / f"rt{i}" / "t1"
            d.mkdir(parents=True)
            feeders.append((d, mk._txn_stream(
                random.Random(700 + i), "g-single", NEVER)))
        wals = [HistoryWAL(d / "history.wal", fsync=False)
                for d, _ in feeders]
        A = LiveScheduler(root3, backend="host", scan_every=1,
                          worker_id="tA", lease_ttl=ttl)
        B = LiveScheduler(root3, backend="host", scan_every=1,
                          worker_id="tB", lease_ttl=ttl)
        a_stop, b_stop = threading.Event(), threading.Event()
        tha = threading.Thread(target=drive, args=(A, a_stop),
                               daemon=True)
        thb = threading.Thread(target=drive, args=(B, b_stop),
                               daemon=True)
        tha.start()
        thb.start()

        def takeovers() -> int:
            n = 0
            for d, _f in feeders:
                p = d / "live.jsonl"
                if p.exists():
                    n += sum(1 for e in telemetry_mod.read_events(p)
                             if e.get("type") == "lease-takeover")
            return n

        pos = [0] * n_ten
        t0 = time.monotonic()
        kill_at = None
        base_takeovers = 0
        survivor = B
        while (any(pos[i] < len(feeders[i][1])
                   for i in range(n_ten))
               or kill_at is None or gap is None) \
                and time.monotonic() - t0 < 300:
            el = time.monotonic() - t0
            target = int(el * 1_000) + 8
            for i, (_d, fops) in enumerate(feeders):
                stop_i = min(target, len(fops))
                while pos[i] < stop_i:
                    wals[i].append(fops[pos[i]])
                    pos[i] += 1
            if kill_at is None and el > 0.5 \
                    and (A.tenants or B.tenants):
                # kill whichever worker won the adoption race — the
                # initial lease scramble can leave either as owner
                base_takeovers = takeovers()
                if A.tenants:
                    a_stop.set()       # the in-process SIGKILL analog
                    tha.join(5)
                else:
                    survivor = A
                    b_stop.set()
                    thb.join(5)
                kill_at = time.monotonic()
            if kill_at is not None and gap is None \
                    and takeovers() > base_takeovers:
                gap = time.monotonic() - kill_at
            time.sleep(0.01)
        for w in wals:
            w.close()
        for d, _f in feeders:
            (d / "results.json").write_text('{"valid?": true}')
        a_stop.set()
        b_stop.set()
        tha.join(5)
        thb.join(5)
        survivor.drain()
        for d, _f in feeders:
            try:
                with open(d / "live.json") as f:
                    resumed += int((json.load(f).get("txn") or {})
                                   .get("resumed_txns") or 0)
            except (OSError, json.JSONDecodeError, ValueError):
                pass
        A.close()
        B.close()
    finally:
        shutil.rmtree(rootbase, ignore_errors=True)

    if gap is None:
        print(json.dumps({"metric": "ERROR: txn bench survivor never "
                          "took over the dead worker's tenants",
                          "value": 0, "unit": "s",
                          "vs_baseline": 0}))
        return {"error": True}

    print(json.dumps({
        "metric": (f"incremental txn tier: sustained drain over "
                   f"{n_ten} tenants x {txns}-txn list-append mop "
                   "WALs (feed -> delta -> packed planes -> warm "
                   "closure -> classify; clean streams flag-free)"),
        "value": round(rate, 1),
        "unit": "ops/sec",
        "vs_baseline": 1.0}), file=sys.stderr)
    print(json.dumps({
        "metric": ("txn commit -> anomaly-flag detection lag "
                   "(G-single planted mid-stream; wall from the "
                   "planted ok record to the durable live-flag — the "
                   "one-shot checker answers only after teardown)"),
        "value": round(lag, 3),
        "unit": "seconds",
        "vs_baseline": 1.0}), file=sys.stderr)
    print(json.dumps({
        "metric": ("txn commit -> lattice-flag detection lag "
                   "(monotonic-writes planted mid-stream; wall from "
                   "the inverted read's ok record to the durable "
                   "session-class live-flag — the lattice pass rides "
                   "every window, not teardown)"),
        "value": round(lat_lag, 3),
        "unit": "seconds",
        "vs_baseline": 1.0}), file=sys.stderr)
    print(json.dumps({
        "metric": (f"txn takeover gap after a worker dies mid-stream "
                   f"(lease ttl {ttl}s; survivor resumes from the "
                   f"checkpointed frontier — {resumed} txns resumed "
                   "without replay)"),
        "value": round(gap, 3),
        "unit": "seconds",
        "vs_baseline": round(gap / ttl, 2)}), file=sys.stderr)
    print(f"# live-txn: drain {rate:.0f} ops/s ({drain_s:.2f}s), "
          f"detect lag {lag:.3f}s, lattice lag {lat_lag:.3f}s "
          f"({','.join(lattice_classes) or 'none'}), takeover gap "
          f"{gap:.3f}s at ttl {ttl}s ({resumed} txns resumed)",
          file=sys.stderr)
    return {"live_txn_ops_s": round(rate, 1),
            "live_txn_detect_lag_s": round(lag, 3),
            "live_lattice_detect_lag_s": round(lat_lag, 3),
            "lattice_classes": lattice_classes,
            "live_txn_takeover_s": round(gap, 3),
            "live_txn_resumed": resumed,
            "live_txn_ttl_s": ttl}


def bench_remote() -> dict:
    """ISSUE 16: the remote-tenant network ingest tier, priced three
    ways over N paced TCP feeders streaming register histories to one
    ingest listener on localhost (the real wire path — crc+seq framed
    lines, cursor acks, lease-epoch registration — not an in-memory
    shortcut).

    (a) **sustained ingest ops/s**: total framed records (invokes +
    completions) landed durably across all tenants / wall from first
    append to last drain.  (b) **p99 ingest lag**: client append
    wall-stamp -> fsynced into the tenant WAL, from the server's own
    live_ingest_lag_seconds histogram (same-host clocks; loopback, so
    this prices framing+fsync+ack, not a WAN).  (c) **reconnect-
    resume gap**: one feeder's socket is severed mid-stream
    (client.kick()); wall until the server journals the re-dialed
    session's cursor resume.  Every tenant WAL is byte-compared
    against its local twin at the end — a lossy drain is an ERROR
    row, never a fast one.

    CPU-scaled per the PR 11 discipline (feeder count stays at the
    ISSUE floor — feeders are socket-bound, not core-bound — the
    per-tenant op count scales); the scaled knobs ride the metric
    label and the bench_cpus tail key."""
    import shutil
    import tempfile
    import threading

    from jepsen_tpu import telemetry as telemetry_mod
    from jepsen_tpu.live.client import StreamingWAL
    from jepsen_tpu.live.ingest import LAG_BUCKETS_S, IngestServer

    cpus = os.cpu_count() or 1
    n_ten = 8                       # the ISSUE 16 floor (N >= 8)
    ops = int(os.environ.get("JEPSEN_TPU_BENCH_REMOTE_OPS",
                             2_500 if cpus >= 8 else 600))
    rootbase = pathlib.Path(tempfile.mkdtemp(prefix="bench-remote-"))
    srv = IngestServer(rootbase / "root", server_id="bench-ingest",
                       lease_ttl=2.0).start()
    gap = None
    try:
        locald = rootbase / "local"
        locald.mkdir()
        wals = []
        for i in range(n_ten):
            h = list(make_history(ops, 4, seed=300 + i))
            wals.append((StreamingWAL(locald / f"w{i}.wal",
                                      f"127.0.0.1:{srv.port}",
                                      f"bt{i}", "t1", writer=f"bw{i}",
                                      fsync=False), h))
        n_rec = sum(len(h) for _w, h in wals)

        def feed(wal, hist):
            for j, o in enumerate(hist):
                wal.append(o)
                if j % 50 == 49:    # paced: yield so 8 feeders + the
                    time.sleep(0.001)   # server share the host fairly

        ths = [threading.Thread(target=feed, args=(w, h), daemon=True)
               for w, h in wals]
        t0 = time.monotonic()
        for t in ths:
            t.start()
        # sever one feeder mid-stream: gap = kick -> the server
        # journals the re-dialed session's cursor resume
        victim = wals[0][0]
        while victim.client.acked_seq < 50 \
                and time.monotonic() - t0 < 60:
            time.sleep(0.005)
        r_before = srv.counts["resumes"]
        tk = time.monotonic()
        victim.client.kick()
        while srv.counts["resumes"] <= r_before \
                and time.monotonic() - tk < 60:
            time.sleep(0.002)
        gap = time.monotonic() - tk
        for t in ths:
            t.join(600)
        for w, _h in wals:
            w.close()               # drains: every frame acked
        wall = time.monotonic() - t0
        lossy = []
        for i, (w, _h) in enumerate(wals):
            remote = srv.root / f"bt{i}" / "t1" / "history.wal"
            if not remote.exists() or remote.read_bytes() \
                    != (locald / f"w{i}.wal").read_bytes():
                lossy.append(f"bt{i}")
        p99 = telemetry_mod.REGISTRY.histogram(
            "live_ingest_lag_seconds",
            buckets=LAG_BUCKETS_S).quantile(0.99)
        fenced = srv.counts["fenced"]
    finally:
        srv.close()
        shutil.rmtree(rootbase, ignore_errors=True)

    if lossy or fenced:
        print(json.dumps({"metric": "ERROR: remote ingest bench lost "
                          f"or corrupted tenant WALs {lossy} "
                          f"(fenced={fenced})", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return {"error": True}
    rate = n_rec / wall
    print(json.dumps({
        "metric": (f"remote-tenant ingest: {n_ten} paced TCP feeders "
                   f"x {ops} ops streamed over localhost (crc+seq "
                   "frames, fsynced tenant WALs, byte-verified; one "
                   "mid-stream disconnect + cursor resume included "
                   "in the wall)"),
        "value": round(rate, 1),
        "unit": "ops/sec",
        "vs_baseline": round(p99, 4)}), file=sys.stderr)
    print(f"# remote ingest: {n_rec} records / {wall:.2f}s "
          f"({rate:.0f} rec/s); p99 append->fsync lag {p99:.4f}s; "
          f"reconnect-resume gap {gap:.3f}s", file=sys.stderr)
    return {"live_remote_ops_s": round(rate, 1),
            "live_remote_p99_lag_s": round(p99, 4),
            "live_remote_reconnect_gap_s": round(gap, 3),
            "live_remote_tenants": n_ten}


def bench_trace() -> dict:
    """ISSUE 19: the causal flight recorder, priced two ways.

    (a) **trace_overhead_pct**: the same N-tenant register store is
    built and drained twice per round — once with every WAL record
    carrying a trace context (an open client span around each append,
    the real `core.run` path, so `follow` parses the `c` envelope and
    the tenant tracks per-op contexts), once envelope-clean — for 3
    rounds; overhead compares the best traced drain against the best
    plain drain.  The recorder's acceptance is < 5%: a regression is
    an ERROR row, never a footnote.

    (b) **lag_segments_p99**: one violation planted per tenant under
    paced wall-stamped traced feeders; every flag's detection lag
    decomposes into the six trace segments and feeds the
    live_lag_segment_seconds histogram; p99 is pooled across segment
    label sets from that instrument (the one /metrics exports —
    including segments the earlier live/fleet/txn rows observed this
    run), with the per-segment p99s disclosed beside it."""
    import shutil
    import tempfile

    from jepsen_tpu import telemetry as telemetry_mod
    from jepsen_tpu import trace as trace_mod
    from jepsen_tpu.history import HistoryWAL
    from jepsen_tpu.live.scheduler import LAG_BUCKETS_S, LiveScheduler

    cpus = os.cpu_count() or 1
    n_ten = 4
    ops = int(os.environ.get("JEPSEN_TPU_BENCH_TRACE_OPS",
                             8_000 if cpus >= 8 else 2_000))
    rootbase = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))

    def write_store(sub: str, traced: bool, seed0: int) -> tuple:
        root = rootbase / sub
        tr = trace_mod.Tracer(enabled=traced)
        tr.set_sink(lambda m: None)
        n_inv = 0
        for i in range(n_ten):
            d = root / f"t{i}" / "t1"
            d.mkdir(parents=True)
            h = make_history(ops, 4, seed=seed0 + i)
            n_inv += sum(1 for o in h if o.is_invoke)
            wal = HistoryWAL(d / "history.wal", fsync=False)
            for o in h:
                if traced:
                    with tr.span("client/invoke"):
                        wal.append(o)
                else:
                    wal.append(o)
            wal.close()
            (d / "results.json").write_text('{"valid?": true}')
        return root, n_inv

    walls: dict = {"plain": [], "traced": []}
    try:
        # warm the compiled-plan cache on a small same-shaped store so
        # neither arm pays a compile inside its timed drain
        warm_root, _ = write_store("warm", False, 7)
        ws = LiveScheduler(warm_root, backend="device", scan_every=1)
        ws.drain()
        ws.close()
        shutil.rmtree(warm_root, ignore_errors=True)

        n_inv = 0
        for rnd in range(3):
            # alternate the arms inside each round so slow host drift
            # lands on both sides, not just one
            for label, traced in (("plain", False), ("traced", True)):
                root, n_inv = write_store(f"{label}{rnd}", traced,
                                          100 + 10 * rnd)
                s = LiveScheduler(root, backend="device", scan_every=1)
                t0 = time.monotonic()
                s.drain()
                walls[label].append(time.monotonic() - t0)
                clean = s.flags_total == 0
                s.close()
                shutil.rmtree(root, ignore_errors=True)
                if not clean:
                    print(json.dumps({
                        "metric": "ERROR: trace bench flagged a clean "
                                  f"{label} tenant", "value": 0,
                        "unit": "%", "vs_baseline": 0}))
                    return {"error": True}
        plain_s, traced_s = min(walls["plain"]), min(walls["traced"])
        overhead_pct = (traced_s - plain_s) / plain_s * 100.0

        # (b) paced traced feeders, one planted violation per tenant
        rt_root = rootbase / "rt"
        tr = trace_mod.Tracer(enabled=True)
        tr.set_sink(lambda m: None)
        feeders = []
        for i in range(n_ten):
            d = rt_root / f"rt{i}" / "t1"
            d.mkdir(parents=True)
            fops = list(make_history(max(ops // 4, 1_000), 4,
                                     seed=500 + i))
            for j, o in enumerate(fops):
                if (o.is_ok and o.f == "read" and o.value is not None
                        and j > len(fops) * 0.6):
                    o.value = 99      # vmax=4: provably never written
                    break
            feeders.append((d, fops))
        wals = [HistoryWAL(d / "history.wal", fsync=False)
                for d, _ in feeders]
        rt = LiveScheduler(rt_root, backend="device", scan_every=1)
        pos = [0] * n_ten
        t_start = time.monotonic()
        while any(pos[i] < len(feeders[i][1]) for i in range(n_ten)):
            target = int((time.monotonic() - t_start) * 2_000 * 2) + 8
            for i, (_d, fops) in enumerate(feeders):
                stop = min(target, len(fops))
                while pos[i] < stop:
                    with tr.span("client/invoke"):
                        wals[i].append(fops[pos[i]])
                    pos[i] += 1
            rt.tick()
        for w in wals:
            w.close()
        for d, _ in feeders:
            (d / "results.json").write_text('{"valid?": false}')
        rt.drain()
        n_flags = rt.flags_total
        rt.close()
        if n_flags < n_ten:
            print(json.dumps({
                "metric": "ERROR: trace bench flagged only "
                          f"{n_flags}/{n_ten} planted tenants",
                "value": 0, "unit": "%", "vs_baseline": 0}))
            return {"error": True}
    finally:
        shutil.rmtree(rootbase, ignore_errors=True)

    # pooled p99 across the per-segment label sets of the session's
    # live_lag_segment_seconds histogram (+ per-segment disclosure)
    _k, by_label = telemetry_mod.REGISTRY.collect().get(
        "live_lag_segment_seconds", (None, {}))
    pool = telemetry_mod.Histogram(buckets=LAG_BUCKETS_S)
    per_seg = {}
    for key, m in by_label.items():
        with m._lock:
            counts, msum, mcount = list(m.counts), m.sum, m.count
        for i, c in enumerate(counts):
            pool.counts[i] += c
        pool.sum += msum
        pool.count += mcount
        per_seg[dict(key).get("segment", "?")] = round(
            m.quantile(0.99), 4)
    if not pool.count:
        print(json.dumps({
            "metric": "ERROR: trace bench observed no lag segments",
            "value": 0, "unit": "%", "vs_baseline": 0}))
        return {"error": True}
    p99 = pool.quantile(0.99)

    if overhead_pct >= 5.0:
        print(json.dumps({
            "metric": ("ERROR: flight-recorder overhead "
                       f"{overhead_pct:.2f}% breaks the < 5% "
                       "acceptance (traced best "
                       f"{traced_s:.3f}s vs plain {plain_s:.3f}s)"),
            "value": round(overhead_pct, 2), "unit": "%",
            "vs_baseline": 0}))
        return {"error": True}

    print(json.dumps({
        "metric": (f"causal flight recorder: {n_ten} tenants x "
                   f"{ops // 1000}k-op register WALs drained traced "
                   "(per-record contexts, trace-flag journaling) vs "
                   "envelope-clean, best of 3 rounds each; "
                   "vs_baseline = pooled detection-lag segment p99 "
                   "over the session's flags"),
        "value": round(overhead_pct, 2),
        "unit": "% overhead",
        "vs_baseline": round(p99, 4)}), file=sys.stderr)
    print(f"# trace: plain {plain_s:.3f}s vs traced {traced_s:.3f}s "
          f"-> {overhead_pct:.2f}% overhead (< 5% acceptance); "
          f"segment p99 pooled {p99:.4f}s over {pool.count} "
          f"observations, per segment {per_seg}", file=sys.stderr)
    return {"trace_overhead_pct": round(overhead_pct, 2),
            "lag_segments_p99": round(p99, 4),
            "lag_segments_p99_by_segment": per_seg,
            "trace_flags": n_flags}


def main() -> int:
    model = models.CASRegister()
    hists = [make_history(OPS_PER_KEY, CONCURRENCY, seed=1000 + k)
             for k in range(N_KEYS)]
    n_ops = sum(sum(1 for o in h if o.is_invoke) for h in hists)

    # --- CPU oracle baseline on a key sample ---------------------------
    t0 = time.monotonic()
    for h in hists[:CPU_SAMPLE_KEYS]:
        cpu_result = wgl_cpu.check(model, h)
        assert cpu_result["valid?"] is True
    cpu_s = time.monotonic() - t0
    cpu_ops = sum(sum(1 for o in h if o.is_invoke)
                  for h in hists[:CPU_SAMPLE_KEYS])
    cpu_rate = cpu_ops / cpu_s
    # Second baseline: the NATIVE oracle (ops/wgl_cpu_native — same
    # algorithm, hot loop + columnar ingest in C).  Reported so no
    # ratio hides an interpreter constant; see BASELINE.md.  One warm
    # pass first: per-key state enumeration traces a tiny CPU-jax
    # expander per distinct uop count, and the device side's compiles
    # are likewise excluded from its timed runs.
    for h in hists[:CPU_SAMPLE_KEYS]:
        wgl_cpu_native.check(model, h)
    t0 = time.monotonic()
    for h in hists[:CPU_SAMPLE_KEYS]:
        assert wgl_cpu_native.check(model, h)["valid?"] is True
    nat_s = time.monotonic() - t0
    nat_rate = cpu_ops / nat_s
    print(f"# baselines: python oracle {cpu_rate:.0f} ops/s; NATIVE "
          f"oracle {nat_rate:.0f} ops/s ({nat_rate / cpu_rate:.1f}x "
          "python — the honest single-core CPU bound)",
          file=sys.stderr)

    # --- Device batch engine: cold run compiles (cached persistently);
    # the steady-state measurement is the best of three warm runs -----
    t0 = time.monotonic()
    cold = wgl_seg.check_many(model, hists)
    cold_s = time.monotonic() - t0
    runs = []
    warm_s, _, results = timed(
        lambda: runs.append(wgl_seg.check_many(model, hists))
        or runs[-1])
    ks = sorted(r[0]["stages"]["kernel"] for r in runs)
    kernel_s, kernel_med = ks[0], ks[len(ks) // 2]
    bad = [i for i, r in enumerate(results) if r["valid?"] is not True]
    if bad or any(r["valid?"] is not True for r in cold):
        print(json.dumps({"metric": "ERROR: benchmark keys judged invalid: "
                          + str(bad[:5]), "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    rate = n_ops / kernel_s

    # --- Secondary: config 4 (cycle detection as bool-matmul SCC) and
    # config 5 (commutative folds), verified + measured before the
    # headline prints so a regression fails the bench loudly ------------
    from jepsen_tpu.ops import cycle as cycle_ops
    from jepsen_tpu.ops import fold as fold_ops

    n = 2048
    rng = random.Random(11)
    adj = np.zeros((n, n), bool)
    for _ in range(6 * n):                 # sparse random digraph...
        adj[rng.randrange(n), rng.randrange(n)] = True
    ring = np.arange(100)                  # ...with a known 100-cycle
    adj[ring, (ring + 1) % 100] = True
    cyc_s, cyc_med, (labels, on_cycle, _) = timed(
        lambda: cycle_ops.scc(adj))
    if not (on_cycle[:100].all() and len(set(labels[:100])) == 1):
        print(json.dumps({"metric": "ERROR: SCC kernel missed the "
                          "embedded 100-cycle", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    print(f"# cycle/SCC: {n}-node dependency graph in {cyc_s:.3f}s "
          f"(median {cyc_med:.3f}s; {int(on_cycle.sum())} nodes on "
          "cycles)", file=sys.stderr)

    adds = np.arange(1_000_000, dtype=np.int64)
    final = adds[adds % 97 != 0]           # ~1% lost elements
    fold_s, fold_med, masks = timed(
        lambda: fold_ops.set_masks(adds, adds, final))
    n_lost = int(np.asarray(masks[2], bool).sum())
    want_lost = (len(adds) - 1) // 97 + 1  # multiples of 97 in range
    if n_lost != want_lost:
        print(json.dumps({"metric": "ERROR: set fold counted "
                          f"{n_lost} lost (expected {want_lost})",
                          "value": 0, "unit": "ops/sec",
                          "vs_baseline": 0}))
        return 1
    print(f"# folds: 1M-element set accounting in {fold_s:.3f}s "
          f"(median {fold_med:.3f}s; {1_000_000 / fold_s / 1e6:.1f}M "
          f"elems/s, {n_lost} lost detected)", file=sys.stderr)

    # --- Secondary: config 2, one long history — the NORTH STAR
    # (BASELINE.json: 100k-op single register history >= 50x CPU
    # knossos).  The CPU oracle is timed on the SAME history (capped),
    # so the reported ratio is direct, not inferred. ------------------
    single = make_history(SINGLE_N_OPS, CONCURRENCY, vmax=9)
    n1 = sum(1 for o in single if o.is_invoke)
    # Two runs on purpose: the first pays one-time XLA compilation, the
    # second is the steady-state measurement reported below.
    single_wall, single_med, r1 = timed(
        lambda: wgl_seg.check(model, single))
    if r1["valid?"] is not True:
        # The history is valid by construction — an invalid verdict
        # means the kernel regressed.
        print(json.dumps({"metric": "ERROR: single-history judged "
                          + str(r1["valid?"]), "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    t0 = time.monotonic()
    cpu_single = wgl_cpu.check(model, single, time_limit=SINGLE_CPU_CAP)
    cpu_single_s = time.monotonic() - t0
    if cpu_single.get("cause"):  # capped: rate over the work it finished
        frac = cpu_single.get("events_done", 0) / max(
            1, cpu_single.get("events_total", 1))
        cpu_single_rate = max(n1 * frac, 1) / cpu_single_s
        cpu_note = (f"CPU capped at {SINGLE_CPU_CAP}s "
                    f"({frac:.0%} of events)")
    else:
        cpu_single_rate = n1 / cpu_single_s
        cpu_note = f"CPU {cpu_single_s:.2f}s"
    single_ratio = (n1 / single_wall) / cpu_single_rate
    # Decompose the wall: a single result fetch
    # costs a fixed round trip that bounds ANY single-shot check from
    # below — measure it so the ratio is interpretable.
    probe = jax.device_put(np.zeros(4, np.int32))
    probe.block_until_ready()
    rtt = float("inf")
    for i in range(3):
        fresh = probe + i          # a NEW device array each time: a
        fresh.block_until_ready()  # cached host copy would time ~0
        t0 = time.monotonic()
        np.asarray(fresh)
        rtt = min(rtt, time.monotonic() - t0)
    compute_s = max(single_wall - rtt, 1e-3)
    print(json.dumps({
        "metric": (f"north star: one {n1 // 1000}k-op register history, "
                   "device wall vs CPU oracle on the SAME history"),
        "value": round(n1 / single_wall, 1), "unit": "ops/sec",
        "vs_baseline": round(single_ratio, 2)}), file=sys.stderr)
    print(f"# north-star decomposition: wall {single_wall:.3f}s = "
          f"fixed fetch round-trip {rtt:.3f}s + plan+compute "
          f"{compute_s:.3f}s; ratio excluding the fixed fetch latency "
          f"{n1 / compute_s / cpu_single_rate:.1f}x.  A single-shot "
          f"check cannot beat CPU_s/RTT = "
          f"{n1 / cpu_single_rate / max(rtt, 1e-3):.0f}x at this "
          "round trip regardless of kernel speed; the steady-state "
          "pipelined line below is the formulation the fixed fetch "
          "cannot bound.", file=sys.stderr)

    # --- THE NORTH STAR, steady-state formulation: N distinct 100k-op
    # histories checked back-to-back on the pipelined engine (host
    # scans history i+1 while the device runs history i; all verdicts
    # come back in ONE 8-byte-per-history fetch).  This is the
    # reference's own `analyze` re-check loop shape (cli.clj:366-397)
    # and amortizes the fixed D2H latency, which bounds any
    # single-shot check (decomposition above). -----------------------
    N_PIPE = 24
    pipe_hists = [single] + [
        make_history(SINGLE_N_OPS, CONCURRENCY, seed=7000 + s, vmax=9)
        for s in range(N_PIPE - 1)]
    wgl_seg.check_pipeline(model, pipe_hists)       # compile warm-up
    # 24 histories amortize the fixed fetch round trip and best-of-7
    # gives the min a chance to catch a clean window, with the median
    # still printed so drift stays visible.  Each run records its
    # per-stage host-time decomposition: the run
    # matching the best wall is printed below, so a future regression
    # is attributable to a stage (scan / fill / dispatch / fetch), not
    # a wall-clock blur.
    pipe_stats: list = []    # (wall_s, stats) per run
    pipe_run_bad: list = []  # any run whose verdicts regressed

    def _pipe_run():
        st: dict = {}
        t0 = time.monotonic()
        out = wgl_seg.check_pipeline(model, pipe_hists, stats=st)
        pipe_stats.append((time.monotonic() - t0, st))
        # EVERY timed window must be valid and pipelined — a min taken
        # over a run that fell off the pipeline would be meaningless
        pipe_run_bad.extend(
            i for i, r in enumerate(out)
            if r["valid?"] is not True or not r.get("pipelined"))
        return out

    # UNCONDITIONAL 10 windows for the device and 5 for the oracle —
    # min and median both drawn from the same disclosed sample.  (An
    # earlier draft extended sampling only when the device was losing;
    # that outcome-conditioned one-sided min would bias vs_native
    # upward in exactly the marginal cases, so it was replaced with
    # this fixed symmetric policy.)
    pipe_wall, pipe_med, _ = timed(_pipe_run, n=10)
    pipe_bad = pipe_run_bad
    if pipe_bad:
        print(json.dumps({"metric": "ERROR: pipelined north star "
                          "judged invalid or fell off the pipeline: "
                          + str(pipe_bad[:5]), "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    # the native oracle on the SAME workload, warmed, same-policy min
    nat_single_s, nat_single_med, rn1 = timed(
        lambda: wgl_cpu_native.check(model, single), n=5)
    per_hist = pipe_wall / N_PIPE
    pipe_ratio = (n1 / per_hist) / cpu_single_rate
    nat_ratio = nat_single_s / per_hist
    best = min(pipe_stats, key=lambda ws: ws[0])[1]  # the min-WALL run
    # measured wire throughput: bytes shipped to the device over the
    # dispatch+fetch window (the transfer-bound stages) — attribution
    # for the tail JSON block, not just a stderr blur
    wire_mb = best.get("wire_bytes", 0) / 1e6
    xfer_s = best.get("dispatch", 0.0) + best.get("fetch", 0.0)
    wire_mb_s = wire_mb / xfer_s if xfer_s > 0 else 0.0
    stages = " ".join(f"{k}={v * 1e3:.0f}ms"
                      for k, v in sorted(best.items())
                      if k != "wire_bytes")
    print(f"# north-star pipelined: {N_PIPE} x {n1} ops in "
          f"{pipe_wall:.3f}s wall (median {pipe_med:.3f}s) = "
          f"{per_hist * 1e3:.1f} ms/history "
          f"({n1 / per_hist / 1e6:.2f}M ops/s; {cpu_note}; "
          f"ratio {pipe_ratio:.1f}x vs the python oracle).  "
          f"The NATIVE oracle checks the same history in "
          f"{nat_single_s * 1e3:.0f} ms (median "
          f"{nat_single_med * 1e3:.0f} ms) on one CPU core (verdict "
          f"{rn1['valid?']}) -> device {nat_ratio:.2f}x the native "
          "C oracle per history.  The fused C stream scan + compact "
          "wire format (round 5) closed the easy regime: the device "
          "now wins every regime, not just crash/refutation/deep.",
          file=sys.stderr)
    print(f"# north-star stage decomposition (best run, host seconds "
          f"summed over {N_PIPE} histories): {stages}",
          file=sys.stderr)
    print(f"# north-star wire: {wire_mb:.2f} MB shipped over "
          f"dispatch+fetch {xfer_s * 1e3:.0f} ms = {wire_mb_s:.1f} "
          "MB/s measured", file=sys.stderr)
    if nat_ratio < 1.0:
        print("# WARNING: pipelined north star below the native "
              f"oracle this run ({nat_ratio:.2f}x) — host/transfer "
              "noise or a regression; see the stage decomposition.",
              file=sys.stderr)

    # --- Config 6: the HARD regime — 16 worker processes, crashed
    # (:info) calls every ~1% of ops.  Crashed ops stay concurrent with
    # the entire rest of the history, the regime where knossos "spins
    # for hoooours" (doc/plan.md:33-38); the CPU oracle is capped and
    # its rate measured over the prefix it finished (generous: it only
    # slows down as pending crashes accumulate). ----------------------
    hard = make_history(HARD_N_OPS, 16, seed=23, crash_rate=0.01,
                        max_open=6)
    nh = sum(1 for o in hard if o.is_invoke)
    n_crash = sum(1 for o in hard if o.type == "info")
    hard_wall, hard_med, rh = timed(
        lambda: wgl_seg.check(model, hard, max_open_bits=12))
    if rh["valid?"] is not True:
        print(json.dumps({"metric": "ERROR: hard-regime history judged "
                          + str(rh["valid?"]), "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    t0 = time.monotonic()
    cpu_hard = wgl_cpu.check(model, hard, time_limit=HARD_CPU_CAP)
    cpu_hard_s = time.monotonic() - t0
    if cpu_hard.get("cause"):
        frac = cpu_hard.get("events_done", 0) / max(
            1, cpu_hard.get("events_total", 1))
        cpu_hard_rate = max(nh * frac, 1) / cpu_hard_s
        hard_note = (f"CPU {cpu_hard.get('cause')} at {cpu_hard_s:.0f}s "
                     f"({frac:.0%} of events)")
    else:
        cpu_hard_rate = nh / cpu_hard_s
        hard_note = f"CPU {cpu_hard_s:.2f}s"
    hard_ratio = (nh / hard_wall) / cpu_hard_rate
    print(json.dumps({
        "metric": (f"hard regime: {nh // 1000}k ops, 16 processes, "
                   f"{n_crash} crashed (:info) calls; device wall vs "
                   "capped CPU oracle"),
        "value": round(nh / hard_wall, 1), "unit": "ops/sec",
        "vs_baseline": round(hard_ratio, 2)}), file=sys.stderr)

    # --- Refutation: the reference's PRODUCT is finding violations
    # (checker.clj:147-158).  Two invalid-history lines measure device
    # time-to-witness on SUBTLE violations (VERDICT r3 #4): a stale
    # read of a LEGAL value excluded from its concurrency window by
    # the planter's window analysis — invisible to any local scan,
    # localizable only by carrying the true state set to the read's
    # depth. ----------------------------------------------------------
    # (a) crash-free 100k history; witness must match the oracle's.
    bad = make_history(SINGLE_N_OPS, CONCURRENCY, seed=31, vmax=9)
    planted = plant_stale_read(bad, 0.95, 9)
    if planted is None:
        print(json.dumps({"metric": "ERROR: no plantable stale read "
                          "in the crash-free history", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    wgl_seg.check(model, bad)             # warm
    bad_wall, bad_med, rb = timed(lambda: wgl_seg.check(model, bad))
    t0 = time.monotonic()
    ob = wgl_cpu.check(model, bad, time_limit=SINGLE_CPU_CAP)
    cpu_bad_s = time.monotonic() - t0
    if (rb["valid?"] is not False
            or (ob["valid?"] is False
                and rb.get("op_index") != ob.get("op_index"))):
        print(json.dumps({"metric": "ERROR: deep-violation verdict/"
                          f"witness mismatch dev={rb.get('op_index')} "
                          f"cpu={ob.get('op_index')}", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    nb = sum(1 for o in bad if o.is_invoke)
    print(json.dumps({
        "metric": (f"refutation: {nb // 1000}k-op history, stale read "
                   "of a LEGAL value planted at 95% depth; device "
                   "wall-to-witness (segment-localized) vs CPU oracle"),
        "value": round(nb / bad_wall, 1), "unit": "ops/sec",
        "vs_baseline": round(cpu_bad_s / bad_wall, 2)}),
        file=sys.stderr)
    print(f"# refutation single: witness op {rb.get('op_index')} "
          f"(== oracle) found in {bad_wall:.3f}s (median "
          f"{bad_med:.3f}s) vs CPU {cpu_bad_s:.2f}s", file=sys.stderr)

    # (b) the crash-heavy regime: the sound crash-relaxed refutation
    # tier must fire (any number of crashed calls) AND name the exact
    # relaxed-death op.  Crashed calls draw values 0..7 (crash_vmax)
    # so the planter can pick a legal value (8 or 9 — written by
    # normal calls elsewhere) that epsilon-jumps provably cannot
    # explain; the planted read's invoke is the expected witness.
    badh = make_history(HARD_N_OPS, 16, seed=23, crash_rate=0.01,
                        max_open=6, crash_vmax=7)
    planted_h = plant_stale_read(badh, 0.9, 9, forbidden=set(range(8)))
    if planted_h is None:
        print(json.dumps({"metric": "ERROR: no plantable stale read "
                          "in the crash regime", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    exp_pos = planted_h[0]
    p_exp = badh.ops[exp_pos].process
    inv_exp = exp_pos
    while inv_exp >= 0 and not (badh.ops[inv_exp].process == p_exp
                                and badh.ops[inv_exp].type == "invoke"):
        inv_exp -= 1
    expected_witness = badh.ops[inv_exp].index
    wgl_seg.check(model, badh, max_open_bits=12,      # warm
                  localize=False)
    badh_wall, badh_med, rbh = timed(
        lambda: wgl_seg.check(model, badh, max_open_bits=12,
                              localize=False))
    if rbh["valid?"] is not False \
            or rbh.get("refutation") != "crash-relaxed" \
            or rbh.get("witness") != "relaxed-exact" \
            or rbh.get("op_index") != expected_witness:
        print(json.dumps({"metric": "ERROR: crash-regime violation "
                          "not refuted exactly by the relaxed tier: "
                          + str({k: rbh.get(k) for k in
                                 ("valid?", "refutation", "witness",
                                  "op_index")})
                          + f" expected witness {expected_witness}",
                          "value": 0, "unit": "ops/sec",
                          "vs_baseline": 0}))
        return 1
    t0 = time.monotonic()
    obh = wgl_cpu.check(model, badh, time_limit=HARD_CPU_CAP)
    cpu_badh_s = time.monotonic() - t0
    nbh = sum(1 for o in badh if o.is_invoke)
    ncbh = sum(1 for o in badh if o.type == "info")
    if obh.get("cause"):
        frac = obh.get("events_done", 0) / max(
            1, obh.get("events_total", 1))
        cpu_badh_rate = max(nbh * frac, 1) / cpu_badh_s
        badh_note = (f"CPU {obh.get('cause')} at {cpu_badh_s:.0f}s "
                     f"({frac:.0%} of events, no verdict)")
    else:
        cpu_badh_rate = nbh / cpu_badh_s
        badh_note = f"CPU {cpu_badh_s:.2f}s"
    badh_ratio = (nbh / badh_wall) / cpu_badh_rate
    print(json.dumps({
        "metric": (f"refutation, crash regime: {nbh // 1000}k ops, "
                   f"{ncbh} crashed calls, stale LEGAL-value read at "
                   "90% depth; sound crash-relaxed refutation with "
                   "EXACT witness vs capped CPU oracle"),
        "value": round(nbh / badh_wall, 1), "unit": "ops/sec",
        "vs_baseline": round(badh_ratio, 2)}), file=sys.stderr)
    print(f"# refutation crash-regime: refuted in {badh_wall:.3f}s "
          f"(median {badh_med:.3f}s; EXACT relaxed witness op "
          f"{rbh.get('op_index')} == planted read, no oracle); "
          f"{badh_note}.  The native oracle cannot hold this regime "
          "either: crashed calls stay pending forever, overflowing "
          "its 64-call mask, and its python fallback is the capped "
          "oracle above — the crash regime is where the device "
          "formulation is structurally, not constant-factor, ahead.",
          file=sys.stderr)

    # (c) the WIDE-STATE crash regime (VERDICT r3 #5): a 40-value
    # CASRegister enumerates ~42 states — past the old u32 closure-mask
    # gate — so the crash-relaxed tier runs on its two-word
    # (sn_words=2) state bitmasks.  Crashed calls draw values 0..30
    # (crash_vmax) so the planter can pick a legal value (31..40,
    # written by normal calls elsewhere) that epsilon-jumps provably
    # cannot explain; the exact relaxed witness must name the planted
    # read.
    badw = make_history(20_000, 16, seed=67, vmax=40, crash_rate=0.01,
                        max_open=6, crash_vmax=30)
    planted_w = plant_stale_read(badw, 0.9, 40,
                                 forbidden=set(range(31)))
    if planted_w is None:
        print(json.dumps({"metric": "ERROR: no plantable stale read "
                          "in the wide-state crash regime", "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    wp = planted_w[0]
    p_w = badw.ops[wp].process
    inv_w = wp
    while inv_w >= 0 and not (badw.ops[inv_w].process == p_w
                              and badw.ops[inv_w].type == "invoke"):
        inv_w -= 1
    expected_w = badw.ops[inv_w].index
    wgl_seg.check(model, badw, max_open_bits=12, localize=False)  # warm
    badw_wall, badw_med, rbw = timed(
        lambda: wgl_seg.check(model, badw, max_open_bits=12,
                              localize=False))
    nbw = sum(1 for o in badw if o.is_invoke)
    ncw = sum(1 for o in badw if o.type == "info")
    if rbw["valid?"] is not False \
            or rbw.get("refutation") != "crash-relaxed" \
            or rbw.get("op_index") != expected_w:
        print(json.dumps({"metric": "ERROR: wide-state crash violation "
                          "not refuted exactly: "
                          + str({k: rbw.get(k) for k in
                                 ("valid?", "refutation", "witness",
                                  "op_index")})
                          + f" expected witness {expected_w}",
                          "value": 0, "unit": "ops/sec",
                          "vs_baseline": 0}))
        return 1
    print(f"# refutation wide-state crash regime (CASRegister, 41 "
          f"values -> Sn > 32, two-word closure masks): {nbw} ops, "
          f"{ncw} crashed, refuted in {badw_wall:.3f}s (median "
          f"{badw_med:.3f}s) with exact witness op "
          f"{rbw.get('op_index')} == planted read", file=sys.stderr)

    # (d) the DEEP regime (VERDICT r4 #3, extended per r5 Next #7 and
    # ISSUE 10): a subtle legal-value stale read planted at 90% depth
    # of R = 10 / 12 / 14 / 15 / 16 histories — the full invalid-half
    # of the envelope at the SAME depths as the valid half below,
    # R = 15/16 now on the word-split sub-plane stack instead of the
    # serial chain.  The wgl_deep kernel reports the exact failing
    # event; witness equality vs the capped oracle is asserted
    # whenever the oracle finishes.
    for mo_d, seed_d in ((10, 53), (12, 57), (14, 59), (15, 61),
                         (16, 63)):
        badd = make_history(20_000, 16, seed=seed_d, vmax=9,
                            max_open=mo_d)
        planted_d = plant_stale_read(badd, 0.9, 9)
        if planted_d is None:
            print(json.dumps({"metric": "ERROR: no plantable stale "
                              f"read in the deep regime R={mo_d}",
                              "value": 0, "unit": "ops/sec",
                              "vs_baseline": 0}))
            return 1
        dp = planted_d[0]
        p_d = badd.ops[dp].process
        inv_d = dp
        while inv_d >= 0 and not (badd.ops[inv_d].process == p_d
                                  and badd.ops[inv_d].type == "invoke"):
            inv_d -= 1
        expected_d = badd.ops[inv_d].index
        # localize=False: the kernel names the exact witness itself;
        # the optional localize tier replays a capped oracle on the
        # prefix for final-paths artifacts, which would time the
        # oracle, not the device (the same measurement choice as the
        # crash-regime lines).  max_open_bits=17 admits every depth up
        # to the word-split boundary (the depth cap is
        # planner.deep_r_max, not this plan gate).
        wgl_seg.check(model, badd, max_open_bits=17,          # warm
                      localize=False)
        badd_wall, badd_med, rbd = timed(
            lambda badd=badd: wgl_seg.check(model, badd,
                                            max_open_bits=17,
                                            localize=False))
        if rbd["valid?"] is not False \
                or rbd.get("engine") != "wgl_deep" \
                or rbd.get("op_index") != expected_d:
            print(json.dumps({"metric": "ERROR: deep-regime "
                              f"(R={mo_d}) violation not refuted by "
                              "wgl_deep with the exact witness: "
                              + str({k: rbd.get(k) for k in
                                     ("valid?", "engine", "op_index")})
                              + f" expected witness {expected_d}",
                              "value": 0, "unit": "ops/sec",
                              "vs_baseline": 0}))
            return 1
        t0 = time.monotonic()
        obd = wgl_cpu.check(model, badd, time_limit=HARD_CPU_CAP)
        cpu_badd_s = time.monotonic() - t0
        nbd = sum(1 for o in badd if o.is_invoke)
        if obd.get("cause"):
            frac = obd.get("events_done", 0) / max(
                1, obd.get("events_total", 1))
            badd_note = (f"CPU {obd.get('cause')} at {cpu_badd_s:.0f}s "
                         f"({frac:.0%} of events, no verdict)")
        else:
            badd_note = f"CPU {cpu_badd_s:.2f}s"
            if obd.get("op_index") != expected_d:
                print(json.dumps({"metric": "ERROR: deep-regime "
                                  f"(R={mo_d}) oracle witness "
                                  "mismatch", "value": 0,
                                  "unit": "ops/sec",
                                  "vs_baseline": 0}))
                return 1
        print(json.dumps({
            "metric": (f"refutation, deep regime: {nbd // 1000}k ops "
                       f"at max_open={mo_d}, stale LEGAL-value read "
                       "at 90% depth; wgl_deep megakernel "
                       "time-to-witness vs capped CPU oracle"),
            "value": round(nbd / badd_wall, 1), "unit": "ops/sec",
            "vs_baseline": round(cpu_badd_s / badd_wall, 2)}),
            file=sys.stderr)
        print(f"# refutation deep regime R={mo_d}: exact witness op "
              f"{rbd.get('op_index')} == planted read in "
              f"{badd_wall:.3f}s (median {badd_med:.3f}s; wgl_deep); "
              f"{badd_note}", file=sys.stderr)

    # --- Envelope: overlap depth (max simultaneously-open calls),
    # the axis the reference's tutorial names as THE cost cliff
    # ("difficulty goes like ~n!", doc/tutorial/07-parameters.md:148).
    # R <= 6 rides the register-delta segment engine; deeper overlap
    # runs the ops.wgl_deep Pallas megakernel (the whole event walk in
    # ONE device program, the 2^R bitmap plane resident in VMEM).  A
    # fixed fetch round trip bounds ANY single-shot check from below
    # (north-star decomposition above), so every row reports the
    # steady-state formulation — N_DEEP distinct histories checked
    # back-to-back, one verdict fetch — with the warmed native
    # oracle's wall on the same workload beside it. ------------------
    # 16 histories per depth: the steady-state formulation must
    # amortize the fixed fetch round trip far enough that the
    # per-history number reflects scan+wire+kernel, not the fetch.
    N_DEEP = 16
    env_wins = []
    shallow_win = None
    # per-depth engine-variant disclosure (ISSUE 10 no-silent-caps:
    # which depths ran the resident plane vs word-split vs hypercube)
    deep_variants: dict = {}
    deep_exchange_rounds: dict = {}
    for mo in (6, 8, 10, 12, 14, 15, 16):
        ehs = [make_history(20_000, 16, seed=41 + mo + 101 * s,
                            vmax=9, max_open=mo)
               for s in range(N_DEEP)]
        ne = sum(1 for o in ehs[0] if o.is_invoke)
        epipe = (wgl_seg.check_pipeline if mo <= 6
                 else wgl_deep.check_pipeline)
        ers = epipe(model, ehs)                          # warm compile
        bad = [i for i, r in enumerate(ers)
               if r["valid?"] is not True]
        if bad:
            print(json.dumps({"metric": "ERROR: envelope histories "
                              f"(max_open={mo}) judged invalid: "
                              + str(bad[:5]), "value": 0,
                              "unit": "ops/sec", "vs_baseline": 0}))
            return 1
        wgl_cpu_native.check(model, ehs[0])              # warm
        # fixed symmetric sampling (5 windows each side), min + median
        # from the same sample — never outcome-conditioned; every
        # device window's verdicts are validated, not just the warm-up
        nmin, nmed, _ = timed(
            lambda: wgl_cpu_native.check(model, ehs[0]), n=5)
        env_run_bad: list = []

        def _env_run(epipe=epipe, ehs=ehs, bad=env_run_bad):
            out = epipe(model, ehs)
            bad.extend(i for i, r in enumerate(out)
                       if r["valid?"] is not True)
            return out

        emin, emed, _ = timed(_env_run, n=5)
        if env_run_bad:
            print(json.dumps({"metric": "ERROR: envelope timed window "
                              f"(max_open={mo}) judged invalid: "
                              + str(env_run_bad[:5]), "value": 0,
                              "unit": "ops/sec", "vs_baseline": 0}))
            return 1
        deep_variants[str(mo)] = (
            "seg" if mo <= 6 else
            "word-split" if mo > wgl_deep.R_BASE else "plane")
        per = emin / N_DEEP
        if mo > 6:
            env_wins.append(nmin / per)
        else:
            # the shallow row must ALSO win now (VERDICT r4 #7: the
            # pen=6 row printed 0.93x in round 4); tracked separately
            # because the summary metric is the DEEP kernel's claim
            shallow_win = nmin / per
        print(f"# envelope max_open={mo}: device "
              f"{ne / per:.0f} ops/s/history ({N_DEEP}x pipelined, "
              f"min {emin:.2f}s median {emed:.2f}s batch; "
              + ("register-delta segment engine" if mo <= 6 else
                 "wgl_deep megakernel" if mo <= wgl_deep.R_BASE else
                 "wgl_deep megakernel, word-split x"
                 f"{2 ** (mo - wgl_deep.R_BASE)}")
              + f"); native oracle {ne / nmin:.0f} ops/s "
              f"(min {nmin * 1e3:.0f}ms median {nmed * 1e3:.0f}ms) "
              f"-> device {nmin / per:.2f}x", file=sys.stderr)
    # --- R = 17 on the hypercube mask shard (ISSUE 10): the top
    # log2(D) mask bits live on the device axis; one pairwise ppermute
    # per high slot per event round.  Runs only where a power-of-2
    # mesh >= 8 exists; a skipped mesh is DISCLOSED in the parsed
    # tail, never silent.  The refutation twin at the same depth
    # asserts the exact planted witness.
    n_devs = len(jax.devices())
    deep_r_max_eff = planner.deep_r_max(None, n_devs)
    if n_devs >= 8:
        from jax.sharding import Mesh
        hmesh = Mesh(np.array(jax.devices()[:8]), ("cfg",))
        base17 = make_history(4_000, 20, seed=987, vmax=9, max_open=14)
        b17 = [invoke_op(300 + p, "write", p % 10) for p in range(17)] \
            + [ok_op(300 + p, "write", p % 10) for p in range(17)]
        h17 = History(list(base17.ops) + b17).index()
        h17.attach_packed(pack_history(h17))
        wgl_deep.check_hypercube(model, [h17], hmesh)       # warm
        hc_wall, hc_med, hcres = timed(
            lambda: wgl_deep.check_hypercube(model, [h17], hmesh), n=3)
        r17 = hcres[0]
        n17 = sum(1 for o in h17 if o.is_invoke)
        planted_17 = plant_stale_read(h17, 0.9, 9)
        if (r17["valid?"] is not True
                or r17.get("deep_variant") != "hypercube"
                or planted_17 is None):
            print(json.dumps({"metric": "ERROR: R=17 hypercube row "
                              "failed (valid/variant/plant): "
                              + str({k: r17.get(k) for k in
                                     ("valid?", "deep_variant")}),
                              "value": 0, "unit": "ops/sec",
                              "vs_baseline": 0}))
            return 1
        p17 = h17.ops[planted_17[0]].process
        inv17 = planted_17[0]
        while inv17 >= 0 and not (h17.ops[inv17].process == p17
                                  and h17.ops[inv17].type == "invoke"):
            inv17 -= 1
        rb17 = wgl_deep.check_hypercube(model, [h17], hmesh)[0]
        if rb17["valid?"] is not False \
                or rb17.get("op_index") != h17.ops[inv17].index:
            print(json.dumps({"metric": "ERROR: R=17 hypercube "
                              "refutation twin missed the planted "
                              "witness: " + str({k: rb17.get(k) for k
                                                 in ("valid?",
                                                     "op_index")}),
                              "value": 0, "unit": "ops/sec",
                              "vs_baseline": 0}))
            return 1
        deep_variants["17"] = "hypercube"
        deep_exchange_rounds["17"] = int(r17["exchange_rounds"])
        print(json.dumps({
            "metric": (f"deep hypercube: one {n17}-op R=17 history "
                       "mask-sharded over 8 devices (one ppermute per "
                       "high slot per event round), valid wall + "
                       "planted-witness refutation twin asserted"),
            "value": round(n17 / hc_wall, 1), "unit": "ops/sec",
            "vs_baseline": round(r17["exchange_rounds"], 0)}),
            file=sys.stderr)
        print(f"# hypercube R=17: {n17} ops in {hc_wall:.2f}s (median "
              f"{hc_med:.2f}s) over shards={r17['shards']}, "
              f"{r17['exchange_rounds']} pairwise exchanges; planted "
              f"witness op {rb17.get('op_index')} exact",
              file=sys.stderr)
    else:
        deep_variants["17"] = f"skipped (mesh has {n_devs} < 8 devices)"
        print(f"# hypercube R=17 row SKIPPED: {n_devs} devices < 8 "
              "(disclosed in the parsed tail)", file=sys.stderr)

    # mixed-depth batch (VERDICT r4 #2, boundary moved by ISSUE 10):
    # R <= 14 histories + one R = 15 (now IN scope, word-split) + one
    # R = 18 beyond every device tier, which must ride the serial
    # fallback chain without poisoning the batch.
    mixed = [make_history(20_000, 16, seed=977 + s, vmax=9,
                          max_open=14) for s in range(3)]
    deep15 = make_history(1_200, 18, seed=981, vmax=9, max_open=14)
    burst = [invoke_op(100 + p, "write", p % 10) for p in range(15)] \
        + [ok_op(100 + p, "write", p % 10) for p in range(15)]
    h15 = History(list(deep15.ops) + burst).index()
    h15.attach_packed(pack_history(h15))
    mixed.append(h15)                # guaranteed R = 15: word-split
    deep18 = make_history(1_200, 22, seed=983, vmax=9, max_open=14)
    burst18 = [invoke_op(100 + p, "write", p % 10) for p in range(18)] \
        + [ok_op(100 + p, "write", p % 10) for p in range(18)]
    h18 = History(list(deep18.ops) + burst18).index()
    h18.attach_packed(pack_history(h18))
    mixed.append(h18)                # guaranteed R = 18 > deep_r_max
    mres = wgl_deep.check_pipeline(model, mixed)
    m_bad = [i for i, r in enumerate(mres) if r["valid?"] is not True]
    if m_bad \
            or mres[3].get("deep_variant") != "word-split" \
            or mres[-1].get("engine") == "wgl_deep":
        print(json.dumps({"metric": "ERROR: mixed-depth deep batch "
                          f"judged invalid ({m_bad[:5]}) or "
                          "mis-routed: R=15 -> "
                          + str(mres[3].get("deep_variant"))
                          + ", R=18 -> "
                          + str(mres[-1].get("engine", "wgl-serial")),
                          "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    print(f"# envelope mixed-depth: R<=14 batch + R=15 (word-split, "
          f"stayed on-device) + R=18 straggler -> all valid; "
          f"straggler engine="
          f"{mres[-1].get('engine', 'wgl-serial')} — the serial "
          "fallback provably still engages beyond the new boundary",
          file=sys.stderr)
    # PRICE the sharding win and the residual serial concession
    # (VERDICT r5 Next #3, ISSUE 10): the SAME R = 15 history on the
    # word-split device path vs the serial chain it used to ride
    # (forced via JEPSEN_TPU_NO_DEEP_SHARD — a prune, so the old
    # routing is exactly reproduced), vs the capped native oracle.
    r15_wall, r15_med, r15res = timed(
        lambda: wgl_deep.check_pipeline(model, [h15]), n=3)
    if r15res[0]["valid?"] is not True \
            or r15res[0].get("deep_variant") != "word-split":
        print(json.dumps({"metric": "ERROR: R=15 device row not "
                          "word-split valid: "
                          + str({k: r15res[0].get(k) for k in
                                 ("valid?", "deep_variant")}),
                          "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    os.environ["JEPSEN_TPU_NO_DEEP_SHARD"] = "1"
    try:
        wgl_deep.check_pipeline(model, [h15])           # warm serial
        strag_wall, strag_med, sres = timed(
            lambda: wgl_deep.check_pipeline(model, [h15]), n=3)
    finally:
        del os.environ["JEPSEN_TPU_NO_DEEP_SHARD"]
    if sres[0]["valid?"] is not True \
            or sres[0].get("engine") == "wgl_deep":
        print(json.dumps({"metric": "ERROR: forced-serial R=15 "
                          "straggler judged "
                          + str(sres[0]["valid?"]) + " on "
                          + str(sres[0].get("engine")), "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    deep_r15_vs_serial = strag_wall / r15_wall
    wgl_cpu_native.check(model, h15)                    # warm
    nat15_s, _, rn15 = timed(
        lambda: wgl_cpu_native.check(model, h15, time_limit=HARD_CPU_CAP),
        n=3)
    n15 = sum(1 for o in h15 if o.is_invoke)
    print(json.dumps({
        "metric": (f"R=15 ceiling broken: one {n15}-op R=15 history "
                   "on the word-split device path vs the serial chain "
                   "it rode before ISSUE 10 (same history, serial "
                   "forced by JEPSEN_TPU_NO_DEEP_SHARD)"),
        "value": round(r15_wall, 4), "unit": "s/history",
        "vs_baseline": round(deep_r15_vs_serial, 2)}),
        file=sys.stderr)
    print(f"# R=15 pricing: word-split device {r15_wall * 1e3:.0f}ms "
          f"(median {r15_med * 1e3:.0f}ms) vs forced serial chain "
          f"{strag_wall * 1e3:.0f}ms (median {strag_med * 1e3:.0f}ms, "
          f"engine {sres[0].get('engine', 'wgl-serial')}) -> "
          f"{deep_r15_vs_serial:.1f}x; native oracle "
          f"{nat15_s * 1e3:.0f}ms (verdict {rn15['valid?']}) on the "
          "same history", file=sys.stderr)
    print(json.dumps({
        "metric": ("deep-overlap envelope: 20k-op histories at "
                   "max_open 8/10/12/14/15/16 (word-split sub-plane "
                   "stacks past 14), pipelined wgl_deep vs warmed "
                   "native C oracle; value = min speedup across "
                   "deep depths"),
        "value": round(min(env_wins), 2), "unit": "x vs native",
        "vs_baseline": round(min(env_wins), 2),
        "shallow_mo6": round(shallow_win, 2)}), file=sys.stderr)

    # --- Multi-key batch with crashed keys: a realistic nemesis run
    # (client timeouts scattered over independent keys) must stay on
    # the batched engine via the per-key crash-stripped twins. --------
    crash_hists = [make_history(OPS_PER_KEY, CONCURRENCY,
                                seed=5000 + k,
                                crash_rate=0.01 if k % 3 == 0 else 0.0)
                   for k in range(N_KEYS // 4)]
    nck = sum(sum(1 for o in h if o.is_invoke) for h in crash_hists)
    ncc = sum(sum(1 for o in h if o.type == "info") for h in crash_hists)
    wgl_seg.check_many(model, crash_hists)          # compile warm-up
    mk_wall = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        rs = wgl_seg.check_many(model, crash_hists)
        mk_wall = min(mk_wall, time.monotonic() - t0)
    bad = [i for i, r in enumerate(rs) if r["valid?"] is not True]
    unbatched = [i for i, r in enumerate(rs)
                 if not r["engine"].startswith("wgl_seg")]
    if bad or unbatched:
        print(json.dumps({"metric": "ERROR: crashed-key batch judged "
                          "invalid " + str(bad[:5]) + " or fell off "
                          "the batched engine " + str(unbatched[:5]),
                          "value": 0,
                          "unit": "ops/sec", "vs_baseline": 0}))
        return 1
    print(f"# multi-key+crashes: {nck} ops / {len(crash_hists)} keys "
          f"({ncc} crashed calls) in {mk_wall:.3f}s wall "
          f"({nck / mk_wall / 1e6:.1f}M ops/s; every key batched, "
          "crash-bearing keys ride as stripped twins)", file=sys.stderr)

    # --- Elle: typed-plane transactional isolation closure (the
    # serializability counterpart of the envelope, ISSUE 5): batched
    # log-squaring closure over stacked ww/wr/rw/po/rt planes, anomaly
    # class decided by masked plane combinations (ops/elle_graph.py).
    # Correctness pinned by a planted G-single in half of each batch
    # and a clean DAG in the other half; throughput = histories/s at
    # 1k- and 10k-txn scales vs the naive host oracle (numpy f32
    # closures; at 10k the host wall is extrapolated from 2 measured
    # squarings of the identical-squaring schedule — disclosed). ----
    import math as math_mod

    from jepsen_tpu.ops import elle_graph

    def elle_stack(n, seed, plant):
        rng = np.random.RandomState(seed)
        st = np.zeros((5, n, n), bool)
        perm = rng.permutation(n)
        pos = np.empty(n, int)
        pos[perm] = np.arange(n)
        fwd = pos[:, None] < pos[None, :]          # DAG: clean by
        for p in range(2):                         # construction;
            st[p] = fwd & (rng.rand(n, n) < 4.0 / n)   # ww + wr only
        # rw stays empty except the plant below — a random forward rw
        # could pair with the planted backward one into a REAL ≥2-rw
        # cycle and turn the expected G-single into a G2
        for a, b in zip(perm, perm[1:]):
            st[3, a, b] = True                     # po chain
        st[4] = fwd & (rng.rand(n, n) < 1.0 / n)   # rt sample
        if plant:
            # ONE backward rw edge: its forward return path rides the
            # po chain, and since every other edge is forward the only
            # cycles are single-rw — exactly G-single, no G0/G1c/G2
            a, b = int(perm[n // 3]), int(perm[2 * n // 3])
            st[2, b, a] = True
        return st

    # CPU-scaled workload knobs (ISSUE 13 satellite): the n^3 closures
    # are sized for the TPU host; on a small CPU host (1-core CI) the
    # stock 10k dense closure and 100k mesh row take hours, so their
    # DEFAULTS derive from os.cpu_count() and the bench completes
    # unattended anywhere.  Env knobs still override; every scaled
    # value is disclosed in the tail JSON (bench_cpus, elle_dense_n,
    # elle_mesh_n, elle_nmax_enabled) and in the metric labels — a
    # reduced row is named, never silent.
    _BENCH_CPUS = os.cpu_count() or 1
    N_DENSE = max(2_048, int(os.environ.get(
        "JEPSEN_TPU_BENCH_ELLE_DENSE_N",
        10_000 if _BENCH_CPUS >= 8 else 4_096)))
    elle_stats = {}
    for n_e, B_e in ((1_000, 8), (N_DENSE, 1)):
        stacks = [elle_stack(n_e, 1000 + n_e + i, plant=(i % 2 == 0))
                  for i in range(B_e)]
        elle_graph.classify_batch(stacks)              # warm compile
        e_bad: list = []

        def _elle_run(stacks=stacks, bad=e_bad):
            rows = elle_graph.classify_batch(stacks)
            for i, r in enumerate(rows):
                want = {"G-single"} if i % 2 == 0 else set()
                if set(r["anomalies"]) != want:
                    bad.append((i, sorted(r["anomalies"])))
            return rows

        ew_min, ew_med, _ = timed(_elle_run, n=3)
        if e_bad:
            print(json.dumps({"metric": "ERROR: elle closure "
                              f"misclassified at n={n_e}: "
                              + str(e_bad[:4]), "value": 0,
                              "unit": "histories/s",
                              "vs_baseline": 0}))
            return 1
        if n_e <= 1_000:
            t0 = time.monotonic()
            for s in stacks:
                elle_graph.classify_host(s)
            host_s = time.monotonic() - t0
            host_note = "measured"
        else:
            steps = max(1, math_mod.ceil(math_mod.log2(n_e - 1)))
            a = (stacks[0][0] | stacks[0][1] | stacks[0][3]
                 | stacks[0][4]).astype(np.float32)
            t0 = time.monotonic()
            for _ in range(2):
                a = (a @ a > 0).astype(np.float32)
            per_sq = (time.monotonic() - t0) / 2
            # the full oracle runs ~6 closure chains of `steps`
            # squarings each (c_ww, c_wwr, 4 matmuls/step in the
            # ≥1-rw pair closure)
            host_s = per_sq * steps * 6 * len(stacks)
            host_note = f"extrapolated from 2/{steps} squarings"
        per_hist_e = ew_min / len(stacks)
        elle_stats[n_e] = (per_hist_e, host_s / ew_min)
        print(json.dumps({
            "metric": (f"elle typed-plane closure: {B_e}x {n_e}-txn "
                       "histories/batch, batched device "
                       "classification (G0/G1c/G-single/G2 masks) "
                       f"vs naive host oracle ({host_note})"),
            "value": round(len(stacks) / ew_min, 2),
            "unit": "histories/s",
            "vs_baseline": round(host_s / ew_min, 2)}),
            file=sys.stderr)
        print(f"# elle n={n_e}: device {ew_min:.3f}s/batch (median "
              f"{ew_med:.3f}s, {per_hist_e * 1e3:.0f}ms/history); "
              f"host {host_s:.2f}s ({host_note})", file=sys.stderr)
        host_persq_dense = per_sq if n_e == N_DENSE else None

    # --- Elle at mesh scale (ISSUE 7): bit-packed uint32 planes +
    # row-sharded mesh closure with device-side early exit
    # (ops/elle_mesh.py).  Four evidence rows: (a) single-device
    # n_max, packed vs dense (OOM ladder, the >=4x acceptance);
    # (b) a 100k-txn history classified on the full mesh, planted
    # AND clean variants, verdict+witness agreed against the sparse
    # host oracle (SCC + bounded rw probes — exact, not extrapolated);
    # (c) mesh-vs-single-device and packed-vs-dense speed lines;
    # (d) a 1M-txn feasibility row extrapolated from the measured
    # per-round wall (n^3 scaling + 20-round cap — DISCLOSED, the
    # naive dense host wall likewise extrapolated as at 10k). -------
    from jepsen_tpu.ops import elle_mesh

    def steps_of(n):
        return max(1, math_mod.ceil(math_mod.log2(max(n - 1, 2))))

    def host_extrap_s(n):
        # the naive dense numpy oracle's wall at n, extrapolated from
        # the 2 squarings measured at N_DENSE (n^3 per squaring, ~6
        # closure matmuls per step) — same disclosure as the dense row
        return (host_persq_dense * (n / float(N_DENSE)) ** 3
                * steps_of(n) * 6)

    ELLE_PROCS = 64                 # worker processes (po chain count)

    def elle_packed_stack(n, seed, plant, n_dev):
        """Sparse-built packed planes (100k x 100k dense bools never
        exist): ~4 forward ww/wr edges per txn over a random
        serialization order, 64 per-process po chains (the worker
        shape real runs have — diameter n/64, so the full planted run
        pays ~log2(n/64) squaring rounds, not log2(n)), sparse rt;
        `plant` adds ONE backward rw edge plus an explicit 8-hop rt
        return path — exactly G-single under include_order, clean
        without the order planes (every dep edge is forward)."""
        n_pad = elle_mesh.pad_for_mesh(n, n_dev)
        rng = np.random.RandomState(seed)
        perm = rng.permutation(n)
        packed = np.zeros((5, n_pad, n_pad // 32), np.uint32)
        q = np.arange(n)
        for p, fan in ((0, 2), (1, 2), (4, 1)):       # ww, wr, rt
            for _ in range(fan):
                d = rng.randint(1, n, size=n)
                ok = q + d < n
                elle_mesh.set_bits(packed[p], perm[q[ok]],
                                   perm[q[ok] + d[ok]])
        # po: process p owns serialization positions p, p+P, p+2P, ...
        src_pos = q[:-ELLE_PROCS]
        elle_mesh.set_bits(packed[3], perm[src_pos],
                           perm[src_pos + ELLE_PROCS])
        if plant:
            ia, ib = n // 3, 2 * n // 3
            elle_mesh.set_bits(packed[2], np.array([perm[ib]]),
                               np.array([perm[ia]]))       # rw b -> a
            hops = np.linspace(ia, ib, 9).astype(np.int64)  # rt a => b
            elle_mesh.set_bits(packed[4], perm[hops[:-1]],
                               perm[hops[1:]])
        return packed

    mesh_stats = {}
    # default scales with the host: the mesh closure is n^3 at fixed
    # device count, and 100k txns only finishes in reasonable wall on
    # a many-core (or real-TPU) host — measured on the 1-core CI
    # driver, 4096 completes where 100k runs for hours
    N_MESH = int(os.environ.get(
        "JEPSEN_TPU_BENCH_ELLE_MESH_N",
        100_000 if _BENCH_CPUS >= 8 else max(4_096,
                                             4_096 * _BENCH_CPUS)))
    n_dev = len(jax.devices())

    # (a) single-device n_max ladder: dense engine up, then one packed
    # single-device attempt at >=4x the dense ceiling.  Every failure
    # is an OOM (fails fast at allocation); every success is a REAL
    # classification, so the boundary is measured, not modeled.
    dense_nmax = 0
    # the OOM ladder's dense rungs are each a full n^3 closure: on a
    # small CPU host the ladder alone outlives any CI budget, so it
    # defaults OFF below 8 cores (JEPSEN_TPU_BENCH_ELLE_NMAX=1 forces
    # it; the tail JSON discloses elle_nmax_enabled either way)
    ELLE_NMAX_ON = os.environ.get(
        "JEPSEN_TPU_BENCH_ELLE_NMAX",
        "1" if _BENCH_CPUS >= 8 else "0") != "0"
    if ELLE_NMAX_ON:
        for n_try in (8_000, 12_000, 16_000, 24_000, 32_000, 48_000):
            try:
                st = [elle_stack(n_try, 4242, plant=True)]
                rows_t = elle_graph.classify_batch(st)
                assert set(rows_t[0]["anomalies"]) == {"G-single"}
                dense_nmax = n_try
                del st
            except Exception as e:      # noqa: BLE001 - OOM boundary
                print(f"# elle dense n_max ladder: n={n_try} failed "
                      f"({type(e).__name__}); ceiling {dense_nmax}",
                      file=sys.stderr)
                break
        packed_target = max(N_MESH, next(
            (t for t in (32_000, 48_000, 64_000, 96_000, 128_000,
                         N_MESH)
             if t >= 4 * dense_nmax), N_MESH))
        try:
            pk = elle_packed_stack(packed_target, 4343, plant=False,
                                   n_dev=1)
            t0 = time.monotonic()
            row_s = elle_mesh.classify_packed(
                [pk], [packed_target], include_order=False,
                max_devices=1)[0]
            single_wall_packed = time.monotonic() - t0
            assert not row_s["anomalies"], row_s
            packed_nmax = packed_target
            mesh_stats["single_wall"] = single_wall_packed
            mesh_stats["single_n"] = packed_target
            mesh_stats["single_rounds"] = row_s["rounds"]
            del pk
        except Exception as e:          # noqa: BLE001 - OOM boundary
            print(f"# elle packed single-device n={packed_target} "
                  f"failed ({type(e).__name__})", file=sys.stderr)
            packed_nmax = 0
        ratio_nmax = (packed_nmax / dense_nmax) if dense_nmax else 0.0
        mesh_stats["dense_nmax"] = dense_nmax
        mesh_stats["packed_nmax"] = packed_nmax
        mesh_stats["nmax_ratio"] = ratio_nmax
        print(json.dumps({
            "metric": ("elle single-device n_max: bit-packed uint32 "
                       "planes vs dense bf16 stacks (measured OOM "
                       "ladder; packed probe is one full "
                       "classification)"),
            "value": packed_nmax, "unit": "txns",
            "vs_baseline": round(ratio_nmax, 2)}), file=sys.stderr)
        print(f"# elle n_max: dense ceiling {dense_nmax} txns, packed "
              f"single-device {packed_nmax} txns "
              f"({ratio_nmax:.1f}x, early-exit rounds "
              f"{mesh_stats.get('single_rounds')})", file=sys.stderr)

    # (b) the 100k-txn certificate on the full mesh: planted
    # (include_order=True, expect exactly G-single) and clean
    # (include_order=False: every dep edge is forward, expect nothing)
    packed_100k = elle_packed_stack(N_MESH, 4343, plant=True,
                                    n_dev=n_dev)
    # clean first: it pays the one (n_pad, devices, block) compile, so
    # the planted certificate row below is a warm measurement
    t0 = time.monotonic()
    row_c = elle_mesh.classify_packed([packed_100k], [N_MESH],
                                      include_order=False)[0]
    mesh_wall_c = time.monotonic() - t0
    t0 = time.monotonic()
    row_p = elle_mesh.classify_packed([packed_100k], [N_MESH])[0]
    mesh_wall_p = time.monotonic() - t0
    # the sparse host oracle must agree on verdict AND witness —
    # measured, not extrapolated (SCC + one rw probe)
    t0 = time.monotonic()
    host_p = elle_mesh.classify_host_packed(packed_100k, N_MESH)
    host_c = elle_mesh.classify_host_packed(packed_100k, N_MESH,
                                            include_order=False)
    host_sparse_s = time.monotonic() - t0
    agree = (set(row_p["anomalies"]) == set(host_p.get("anomalies", {}))
             == {"G-single"}
             and not row_c["anomalies"]
             and not host_c.get("anomalies", {})
             and not host_p.get("unknown") and not host_c.get("unknown")
             and row_p["anomalies"]["G-single"]
             == host_p["anomalies"]["G-single"])
    wit = None
    if agree:
        wit = elle_mesh.find_witness_packed(
            packed_100k, "G-single", row_p["anomalies"]["G-single"],
            N_MESH)
        agree = wit is not None and wit[0] == wit[-1] and len(wit) >= 3
    if not agree:
        print(json.dumps({
            "metric": ("ERROR: elle mesh 100k device/host "
                       f"disagreement: device={row_p['anomalies']} "
                       f"host={host_p} clean={row_c['anomalies']}"),
            "value": 0, "unit": "histories/s", "vs_baseline": 0}))
        return 1
    host_100k_s = host_extrap_s(N_MESH)
    mesh_stats.update(
        wall_p=mesh_wall_p, wall_c=mesh_wall_c,
        rounds_p=row_p["rounds"], rounds_c=row_c["rounds"],
        vs_host=host_100k_s / mesh_wall_p)
    print(json.dumps({
        "metric": (f"elle mesh closure: {N_MESH}-txn list-append "
                   f"history on {row_p['shards']} devices, bit-packed "
                   "planes, planted G-single classified with witness "
                   "(host verdict via sparse SCC oracle, measured; "
                   "dense-host wall extrapolated from 10k squarings)"),
        "value": round(1.0 / mesh_wall_p, 4), "unit": "histories/s",
        "vs_baseline": round(host_100k_s / mesh_wall_p, 1)}),
        file=sys.stderr)
    print(f"# elle mesh n={N_MESH}: planted {mesh_wall_p:.1f}s "
          f"({row_p['rounds']} rounds, witness len {len(wit)}), clean "
          f"{mesh_wall_c:.1f}s ({row_c['rounds']} rounds — early exit "
          f"of {steps_of(elle_mesh.pad_for_mesh(N_MESH, n_dev))}-round "
          f"cap); sparse host oracle {host_sparse_s:.1f}s (agrees); "
          f"dense host extrapolated {host_100k_s:.0f}s", file=sys.stderr)
    if mesh_stats.get("single_wall") \
            and mesh_stats.get("single_n") == N_MESH:
        ratio_ms = mesh_stats["single_wall"] / mesh_wall_c
        print(f"# elle mesh-vs-single n={N_MESH} (clean, early-exit; "
              f"both walls include one compile): {n_dev} devices "
              f"{mesh_wall_c:.1f}s vs 1 device "
              f"{mesh_stats['single_wall']:.1f}s -> {ratio_ms:.1f}x",
              file=sys.stderr)
        mesh_stats["mesh_vs_single"] = ratio_ms
    # packed-vs-dense speed on the SAME dense-row stack (B=1, one
    # device; n = N_DENSE, 10k at stock scale)
    pk10 = elle_mesh.pack_planes(stacks[0], n_dev=1)
    elle_mesh.classify_packed([pk10], [N_DENSE], max_devices=1)  # warm
    t0 = time.monotonic()
    row10 = elle_mesh.classify_packed([pk10], [N_DENSE],
                                      max_devices=1)[0]
    packed_10k_s = time.monotonic() - t0
    assert set(row10["anomalies"]) == {"G-single"}, row10
    mesh_stats["packed_vs_dense_10k"] = \
        elle_stats[N_DENSE][0] / packed_10k_s
    pk_mb = elle_mesh.plane_nbytes(N_DENSE) / 1e6
    dn_mb = elle_mesh.plane_nbytes(N_DENSE, packed=False) / 1e6
    print(f"# elle packed-vs-dense n={N_DENSE}: packed "
          f"{packed_10k_s:.3f}s "
          f"vs dense {elle_stats[N_DENSE][0]:.3f}s per history "
          f"({mesh_stats['packed_vs_dense_10k']:.2f}x; packed plane "
          f"{pk_mb:.0f} MB vs dense bool {dn_mb:.0f} MB resident)",
          file=sys.stderr)
    del packed_100k
    # (d) 1M-txn feasibility, EXTRAPOLATED (disclosed): per-round wall
    # measured at N_MESH scales n^3 at fixed device count; a 1M
    # closure caps at 20 squaring rounds; packed planes are 125 GB/
    # plane, so the all-gathered frontier must stream as k-block
    # tiles (the blocked pmm already consumes it that way) or the
    # mesh must grow past the memory bound.
    per_round_s = mesh_wall_p / max(row_p["rounds"], 1)
    est_1m_s = (per_round_s * (1_000_000 / N_MESH) ** 3
                * steps_of(1_000_000))
    mesh_stats["est_1m_s"] = est_1m_s
    print(json.dumps({
        "metric": ("elle 1M-txn feasibility (EXTRAPOLATED from "
                   f"measured {N_MESH}-txn round wall, n^3/devices, "
                   "20-round cap; packed plane 125 GB => frontier "
                   "tiles must stream or mesh must grow)"),
        "value": round(est_1m_s, 1), "unit": "s/history (est)",
        "vs_baseline": round(host_extrap_s(1_000_000) / est_1m_s, 1)}),
        file=sys.stderr)

    live_stats = bench_live()
    if live_stats.get("error"):
        return 1

    fleet_stats = bench_fleet()
    if fleet_stats.get("error"):
        return 1

    remote_stats = bench_remote()
    if remote_stats.get("error"):
        return 1

    txn_stats = bench_live_txn()
    if txn_stats.get("error"):
        return 1

    trace_stats = bench_trace()
    if trace_stats.get("error"):
        return 1

    # Host-overlap attribution (ISSUE 8/9): the warm multi-key wall vs
    # its kernel time — the double-buffered executor's target is
    # <= 1.5x (plan+pack+dispatch of chunk k+1 hidden behind chunk k's
    # device compute; was 4.4x with the monolithic pack), and the
    # native parallel ingest layer (ISSUE 9) shrinks the host pack
    # itself.  host_pack_s / pack_backend / pack_threads come off the
    # verdicts' own stage decomposition + dispatch record, so the
    # parsed artifact attributes the host side per the no-silent-caps
    # principle.
    overlap_ratio = warm_s / max(kernel_s, 1e-9)
    mk_stages = results[0].get("stages") or {}
    mk_rec = results[0].get("dispatch") or {}
    host_pack_s = mk_stages.get("pack", mk_stages.get("fill", 0.0))
    host_scan_s = mk_stages.get("scan", 0.0)
    mk_pack_backend = mk_rec.get("pack_backend") or \
        (mk_rec.get("plan") or {}).get("pack_backend") or "python"
    mk_pack_threads = mk_rec.get("pack_threads") or \
        (mk_rec.get("plan") or {}).get("pack_threads") or 0
    print(f"# multi-key overlap: warm wall {warm_s:.3f}s / kernel "
          f"{kernel_s:.3f}s = {overlap_ratio:.2f}x (target <= 1.5x; "
          f"host pack {host_pack_s:.3f}s + scan {host_scan_s:.3f}s on "
          f"pack_backend={mk_pack_backend} x{mk_pack_threads}, "
          "double-buffered against device compute)",
          file=sys.stderr)

    print(json.dumps({
        "metric": (f"linearizability check throughput, {N_KEYS} "
                   f"independent {OPS_PER_KEY}-op register histories "
                   f"({n_ops // 1000}k ops total; batched bitmap kernel, "
                   f"{results[0]['backend']})"),
        "value": round(rate, 1),
        "median": round(n_ops / kernel_med, 1),
        "unit": "ops/sec",
        "vs_baseline": round(rate / cpu_rate, 2),
    }), file=sys.stderr)
    # The headline (stdout) is the BASELINE.json north star in its
    # steady-state formulation: 100k-op single-register histories,
    # device vs the CPU oracle ON THE SAME history, fetch amortized
    # over the pipeline (see the decomposition lines above).
    print(json.dumps({
        "metric": (f"north star: {N_PIPE} distinct {n1 // 1000}k-op "
                   "register histories checked back-to-back "
                   "(pipelined segment engine, one verdict fetch); "
                   "per-history device wall vs CPU oracle on the SAME "
                   "workload"),
        "value": round(n1 / per_hist, 1),
        "median": round(n1 / (pipe_med / N_PIPE), 1),
        "unit": "ops/sec",
        "vs_baseline": round(pipe_ratio, 2),
        "vs_native": round(nat_ratio, 2),
        # per-stage attribution of the best run (host seconds summed
        # over the pipeline) + measured wire throughput, so the parsed
        # BENCH artifact carries the decomposition, not just the
        # headline (VERDICT r5 Next #4)
        "stages": {k: round(v, 4) for k, v in sorted(best.items())
                   if k != "wire_bytes"},
        "wire_mb": round(wire_mb, 2),
        "wire_mb_s": round(wire_mb_s, 1),
        "straggler_r15_s": round(strag_wall, 4),
        "straggler_vs_native": round(nat15_s / strag_wall, 2),
        # the deep envelope past the old R=14 ceiling (ISSUE 10): the
        # effective boundary on THIS host's mesh, the R=15 word-split
        # device wall vs the serial chain it replaced, and the
        # per-depth variant + exchange-schedule disclosure (depths the
        # host could not run sharded are named, never silent)
        "deep_r_max_effective": deep_r_max_eff,
        "deep_r15_device_s": round(r15_wall, 4),
        "deep_r15_vs_serial": round(deep_r15_vs_serial, 2),
        "deep_variants": deep_variants,
        "deep_exchange_rounds": deep_exchange_rounds,
        # the transactional-isolation engine's trajectory: device
        # seconds per history for the batched
        # typed-plane closure, and its speedup vs the host oracle
        "elle_1k_hist_s": round(elle_stats[1_000][0], 4),
        "elle_1k_vs_host": round(elle_stats[1_000][1], 2),
        # the dense row keeps its historical 10k key name ONLY at
        # stock scale; a cpu-scaled run renames it elle_dense_* and
        # discloses the size (no silent caps)
        **({"elle_10k_hist_s": round(elle_stats[10_000][0], 4),
            "elle_10k_vs_host": round(elle_stats[10_000][1], 2)}
           if N_DENSE == 10_000 else
           {"elle_dense_hist_s": round(elle_stats[N_DENSE][0], 4),
            "elle_dense_vs_host": round(elle_stats[N_DENSE][1], 2)}),
        # CPU-scaled knob disclosure (ISSUE 13 satellite): what this
        # host actually ran, so a 1-core artifact can never be read
        # as a stock-scale one
        "bench_cpus": _BENCH_CPUS,
        "elle_dense_n": N_DENSE,
        "elle_mesh_n": N_MESH,
        "elle_nmax_enabled": bool(ELLE_NMAX_ON),
        # the mesh-sharded bit-packed closure: 100k-txn
        # certificate wall on the full mesh (planted variant, warm),
        # vs the naive dense host oracle (EXTRAPOLATED from measured
        # 10k squarings, n^3 — disclosed; the verdict itself is
        # checked against the measured sparse SCC oracle), squaring
        # rounds for the planted (full) and clean (early-exit) runs,
        # the single-device n_max raise from bit-packing, and the
        # 1M-txn feasibility estimate (EXTRAPOLATED, n^3/devices,
        # 20-round cap — see the disclosure line above)
        # likewise the mesh certificate: historical 100k key names
        # only at stock scale, elle_mesh_* + elle_mesh_n otherwise
        **({"elle_100k_hist_s": round(mesh_stats["wall_p"], 2),
            "elle_100k_vs_host": round(mesh_stats["vs_host"], 1),
            "elle_100k_rounds": int(mesh_stats["rounds_p"]),
            "elle_100k_early_rounds": int(mesh_stats["rounds_c"])}
           if N_MESH == 100_000 else
           {"elle_mesh_hist_s": round(mesh_stats["wall_p"], 2),
            "elle_mesh_vs_host": round(mesh_stats["vs_host"], 1),
            "elle_mesh_rounds": int(mesh_stats["rounds_p"]),
            "elle_mesh_early_rounds": int(mesh_stats["rounds_c"])}),
        "elle_packed_vs_dense_10k": round(
            mesh_stats["packed_vs_dense_10k"], 2),
        **({"elle_mesh_vs_single_100k": round(
                mesh_stats["mesh_vs_single"], 2)}
           if mesh_stats.get("mesh_vs_single") else {}),
        **({"elle_dense_nmax": mesh_stats["dense_nmax"],
            "elle_packed_nmax": mesh_stats["packed_nmax"],
            "elle_packed_nmax_ratio": round(
                mesh_stats["nmax_ratio"], 2)}
           if mesh_stats.get("packed_nmax") else {}),
        "elle_1m_est_s": round(mesh_stats["est_1m_s"], 1),
        "elle_1m_disclosed": "extrapolated",
        # the live verification service: sustained
        # multi-tenant incremental drain + p99 op-append->verdict lag
        # under paced feeders (bench_live)
        **{k: v for k, v in live_stats.items() if v is not None},
        # the serve-checker fleet (ISSUE 14): 2-worker vs 1-worker
        # sustained drain + the measured takeover gap after a worker
        # dies mid-drain (bench_fleet; ttl disclosed)
        **{k: v for k, v in fleet_stats.items() if v is not None},
        # the remote-tenant network ingest tier (ISSUE 16): sustained
        # framed-record ops/s over N paced TCP feeders, p99 client
        # append -> fsynced-WAL lag, and the measured mid-stream
        # disconnect -> cursor-resume gap (bench_remote; byte-verified
        # drain, feeder count disclosed)
        **{k: v for k, v in remote_stats.items() if v is not None},
        # the incremental transactional tier (ISSUE 18): sustained
        # txn-stream drain ops/s, commit -> anomaly-flag detection
        # lag on a planted G-single, and the txn takeover gap with
        # checkpointed-frontier resume (bench_live_txn; ttl and
        # resumed-txn count disclosed)
        **{k: v for k, v in txn_stats.items() if v is not None},
        # the causal flight recorder (ISSUE 19): traced-vs-untraced
        # drain overhead (< 5% acceptance, asserted) and the session's
        # pooled detection-lag segment p99 with per-segment disclosure
        # (bench_trace)
        **{k: v for k, v in trace_stats.items() if v is not None},
        # the double-buffered executor's wall-vs-kernel ratio on the
        # multi-key row
        "overlap_wall_vs_kernel": round(overlap_ratio, 2),
        # native parallel ingest attribution on the 3400-key row
        # (ISSUE 9): host pack seconds from the verdict's own stage
        # decomposition, the ingest backend + thread count that
        # ACTUALLY packed (from its dispatch record), and the headline
        # wall-vs-kernel ratio the ingest layer targets (<= 1.6x)
        "host_pack_s": round(host_pack_s, 4),
        "host_scan_s": round(host_scan_s, 4),
        "pack_backend": mk_pack_backend,
        "pack_threads": int(mk_pack_threads),
        "wall_vs_kernel": round(overlap_ratio, 2),
    }))
    print(f"# multi-key: {n_ops} ops / {N_KEYS} keys in {kernel_s:.3f}s "
          f"kernel (median {kernel_med:.3f}s; {warm_s:.2f}s wall incl. "
          f"plan; cold {cold_s:.2f}s "
          f"incl. compile); cpu oracle: {cpu_ops} ops in {cpu_s:.3f}s "
          f"({cpu_rate:.0f} ops/s)", file=sys.stderr)
    print(f"# single-history: {n1} ops in {single_wall:.3f}s wall "
          f"(median {single_med:.3f}s; kernel "
          f"{r1['stages']['kernel']:.3f}s; {r1['segments']} "
          f"segments; {cpu_note}; ratio {single_ratio:.1f}x)",
          file=sys.stderr)
    print(f"# hard-regime: {nh} ops ({n_crash} crashed) in "
          f"{hard_wall:.3f}s wall (median {hard_med:.3f}s); "
          f"{hard_note}; ratio {hard_ratio:.1f}x", file=sys.stderr)

    return 0


if __name__ == "__main__":
    sys.exit(main())
