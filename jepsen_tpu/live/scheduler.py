"""Multi-tenant live-checking scheduler.

One `tick()` is the whole pipeline, driven synchronously so tests and
the daemon share the exact same code path:

  1. **discover** — scan the store root for run dirs carrying a
     `history.wal` and adopt them as tenants (model resolved from the
     run's `test.json` `model` key when present, else the service
     default);
  2. **ingest** — advance each unpaused tenant's WAL cursor
     (`history.follow`, bounded records per tick) and feed the ops
     through its lanes; a tenant whose tracked bytes exceed the budget
     is *paused* (backpressure: the WAL is on disk, nothing is lost)
     until dispatching drains it below the low-water mark;
  3. **dispatch** — take at most one ready window per lane across ALL
     tenants and check them as shape-bucketed micro-batches through
     `ops/runner.ResilientRunner` (device OOM bisects the lane batch,
     a poisoned lane quarantines alone, a blown deadline degrades the
     rest of the tick to the numpy host engine via `cpu_fallback`);
  4. **account** — fold verdicts back into lanes, emit `live-flag` /
     `live-dispatch` / `live-window` events into each tenant's
     `live.jsonl` (telemetry.EventLog framing), refresh the per-run
     `live.json` snapshot (atomic replace — web.py renders it), and
     update the Prometheus gauges (`live_detection_lag_seconds`,
     `live_window_queue_depth{tenant=}`, docs/observability.md).

Detection lag is measured from the WAL append wall stamp (`w` field,
history.follow) to the flag emission — true op-append→flag latency
when checker and run share a clock; `live_window_lag_seconds` tracks
the same quantity for every checked window (clean ones included), and
its p99 is the service's headline lag.

**Fleet mode** (`worker_id` + `lease_ttl`, ISSUE 14): N schedulers
over one root partition the tenants through per-run `lease.json`
ownership leases (live/lease.py).  Adoption becomes
acquire-under-lease (a worker only acquires while under its
`fleet_budget_bytes`), leases are renewed with the tenant's *safe*
WAL cursor (every op before it checked AND published), an expired
lease — judged by monotonic observed silence, wall stamps advisory —
is taken over with an epoch bump and resumed from that cursor, and
every publish is fenced: a stale-epoch worker refuses to write,
drops the tenant, and counts `live_lease_fenced_total`.  Flags stay
exactly-once across takeovers because the successor de-duplicates
against the flags already journaled in the tenant's `live.jsonl`
(whose sequence it resumes rather than restarts).  Lease transitions
are durable `lease-acquire` / `lease-expire` / `lease-takeover`
events in the tenant's `live.jsonl`; `lease-fenced` goes to the
stale worker's own `store/fleet/<worker>.jsonl` log (the tenant log
is strictly single-writer-under-lease — a fenced writer touching it
would race the new owner's sequence).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Optional

from jepsen_tpu import history as history_mod
from jepsen_tpu import models as models_mod
from jepsen_tpu import telemetry
from jepsen_tpu import trace as trace_mod
from jepsen_tpu.live import engine as engine_mod
from jepsen_tpu.live import lease as lease_mod
from jepsen_tpu.live.txn import TxnTenant, sniff_txn_workload
from jepsen_tpu.live.windows import Tenant
from jepsen_tpu.ops.runner import ResilientRunner

log = logging.getLogger("jepsen.live")

# Detection-lag histogram buckets: sub-ms through tens of seconds.
LAG_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

# Per-segment SLO bands (seconds): the in-code fallback when
# store/ci/bench-baseline.json has no `lag_segment_<name>_s` row yet.
# `live_lag_slo_burn{segment=}` reports the fraction of flags whose
# segment exceeded its band — the honesty gauge ISSUE 19 ratchets.
_SEGMENT_SLO_S = {"fsync": 0.05, "frame": 0.25, "ack": 0.25,
                  "window": 2.0, "dispatch": 2.0, "flag": 1.0}


def _segment_bands() -> dict:
    """bench-baseline `lag_segment_<name>_s` rows override the in-code
    defaults, so the burn gauge ratchets with the published prices."""
    bands = dict(_SEGMENT_SLO_S)
    try:
        path = Path(__file__).resolve().parents[2] \
            / "store" / "ci" / "bench-baseline.json"
        with open(path) as f:
            rows = json.load(f).get("rows") or {}
        for seg in trace_mod.SEGMENTS:
            row = rows.get(f"lag_segment_{seg}_s")
            if isinstance(row, dict) \
                    and isinstance(row.get("max"), (int, float)):
                bands[seg] = float(row["max"])
    except Exception:  # noqa: BLE001 - bands are advisory
        pass
    return bands

# Store-root entries that are bookkeeping, never run dirs: the same
# exclusion class store.tests() applies (campaigns/ci from PR 11,
# fleet/ worker status + lease bookkeeping from ISSUE 14).
NON_RUN_DIRS = ("ci", "current", "latest", "campaigns", "plan-cache",
                "fleet", "ingest")


def _default_model(name: Optional[str]):
    name = name or "cas-register"
    ctor = models_mod.MODELS.get(name)
    if ctor is None:
        raise ValueError(f"unknown live model {name!r}; one of "
                         f"{sorted(models_mod.MODELS)}")
    return ctor()


class LiveScheduler:
    """The tick-driven scheduling core (no threads of its own — the
    CheckerService wraps it in a loop)."""

    def __init__(self, root, *, model: Optional[str] = None,
                 backend: str = "auto",
                 bits: int = 6, max_states: int = 64,
                 max_window_events: int = 256,
                 max_buffer_entries: int = 4096,
                 wild_init: Optional[bool] = None,
                 tenant_budget_bytes: int = 4 << 20,
                 max_batch_records: int = 4096,
                 deadline_s: Optional[float] = None,
                 scan_every: int = 10,
                 clock=time.time,
                 worker_id: Optional[str] = None,
                 lease_ttl: Optional[float] = None,
                 fleet_budget_bytes: int = 32 << 20,
                 txn_backend: Optional[str] = None,
                 txn_window: int = 32,
                 mono=time.monotonic):
        self.root = Path(root)
        self.default_model = model
        self.backend_opt = backend
        self.backend: Optional[str] = None if backend == "auto" \
            else backend
        self.lane_opts = dict(bits=bits, max_states=max_states,
                              max_window_events=max_window_events,
                              max_buffer_entries=max_buffer_entries,
                              wild_init=wild_init)
        self.tenant_budget_bytes = tenant_budget_bytes
        self.max_batch_records = max_batch_records
        self.deadline_s = deadline_s
        self.scan_every = max(1, scan_every)
        self.clock = clock
        # transactional tenants (ISSUE 18): "device" only when asked —
        # the dense host twin is exact and keeps the device path free
        # for the window micro-batches
        self.txn_backend = txn_backend or (
            "device" if backend == "device" else "host")
        self.txn_window = max(1, int(txn_window))
        self.tenants: dict = {}        # (name, ts) -> Tenant
        self.finished: set = set()
        self._logs: dict = {}          # (name, ts) -> EventLog
        # -- causal flight recorder (ISSUE 19) ---------------------------
        self._tracelogs: dict = {}     # key -> trace-index EventLog
        self._trace_links: dict = {}   # key -> cross-worker span link
        self._transport: dict = {}     # key -> {seq: [fs, recv, syncd]}
        self._transport_lock = threading.Lock()
        self._seg_bands = _segment_bands()
        self._seg_over: dict = {}      # segment -> flags over band
        self._seg_n = 0                # flags with segments observed
        self._seg_max: dict = {}       # segment -> worst seconds seen
        self._tick_n = 0
        self._dispatch_seq = 0
        self.flags_total = 0
        self.last_detection_lag_s: Optional[float] = None
        # -- fleet mode (ISSUE 14): lease-owned tenants ------------------
        self.worker_id = worker_id or f"w{os.getpid()}"
        self.lease_ttl = float(lease_ttl) if lease_ttl else None
        self.fleet_budget_bytes = fleet_budget_bytes
        self.mono = mono
        self._leases: dict = {}        # key -> owned lease_mod.Lease
        self._lease_lock = threading.Lock()
        self._observer = lease_mod.LeaseObserver(mono=mono)
        self._fence_checked: dict = {}  # key -> mono stamp of last ok
        self._last_renew = mono()
        self._last_discover = mono()
        self.unadopted: dict = {}      # key -> why (for /fleet + --once)
        self.takeovers = 0
        self.fenced_writes = 0
        self.max_takeover_lag_s = 0.0
        if self.lease_ttl:
            telemetry.REGISTRY.counter(
                "live_fleet_workers_total").inc()

    # -- backend resolution --------------------------------------------------

    def resolve_backend(self) -> str:
        """Probe the device path once; a host without a usable jax
        backend degrades the whole service to the numpy engine with a
        logged note (no per-dispatch thrash)."""
        if self.backend is None:
            try:
                probe = _probe_lane()
                engine_mod.check_batch([probe], backend="device")
                self.backend = "device"
            except Exception as e:  # noqa: BLE001 - resolve to host
                log.warning("live device path unavailable (%s); "
                            "serving from the numpy host engine", e)
                self.backend = "host"
        return self.backend

    # -- discovery -----------------------------------------------------------

    def _run_dirs(self):
        """(key, ts_dir) for every run dir under the root carrying a
        history.wal — bookkeeping dirs (NON_RUN_DIRS) skipped."""
        if not self.root.is_dir():
            return
        for name_dir in sorted(self.root.iterdir()):
            if not name_dir.is_dir() or name_dir.is_symlink() \
                    or name_dir.name in NON_RUN_DIRS:
                continue
            for ts_dir in sorted(p for p in name_dir.iterdir()
                                 if p.is_dir()
                                 and not p.is_symlink()):
                if (ts_dir / "history.wal").exists():
                    yield (name_dir.name, ts_dir.name), ts_dir

    def discover(self) -> int:
        """Adopt new run dirs under the root.  Returns tenants added.
        In fleet mode adoption is acquire-under-lease: a run dir is
        only adopted once this worker owns its lease (fresh acquire,
        or takeover of an expired/torn/released one), and only while
        this worker's tracked bytes leave room under its fleet
        budget."""
        added = 0
        for key, ts_dir in self._run_dirs():
            if key in self.tenants or key in self.finished:
                continue
            if not self.lease_ttl:
                self._adopt(key, ts_dir)
                added += 1
                continue
            try:
                owned, via = self._acquire(key, ts_dir)
            except Exception:  # noqa: BLE001 - one bad dir must not
                log.warning("lease acquire failed for %s", ts_dir,
                            exc_info=True)   # wedge the scan
                self.unadopted[key] = "acquire error"
                continue
            if owned is None:
                continue
            self._adopt(key, ts_dir, owned=owned, via=via)
            added += 1
        return added

    def _owned_bytes(self) -> int:
        """What this worker is already on the hook for: tracked
        in-memory bytes plus each owned tenant's unread on-disk WAL
        backlog (at adoption time the former is always zero — the
        backlog is what 'can I afford another tenant' must price)."""
        total = 0
        for t in self.tenants.values():
            total += t.nbytes
            try:
                total += max((t.run_dir / "history.wal")
                             .stat().st_size - t.offset, 0)
            except OSError:
                pass
        return total

    def _acquire(self, key, ts_dir):
        """(lease, how) when this worker should adopt `key`; (None, _)
        otherwise.  `how` is 'acquire' or 'takeover'."""
        ls = lease_mod.read(ts_dir)
        if ls is not None and not ls.corrupt and ls.done:
            # terminal release: the tenant was fully drained and its
            # final live.json published.  Never take a finished run
            # back over — a once-fenced worker re-adopting here would
            # re-process the whole WAL and republish the snapshot
            # under its own id/epoch, flapping ownership on a
            # completed tenant.
            self.finished.add(key)
            self.unadopted.pop(key, None)
            return None, None
        if ls is not None and not ls.corrupt \
                and ls.owner == self.worker_id \
                and key in self.tenants:
            return None, None           # already ours and adopted
        if self._owned_bytes() > self.fleet_budget_bytes:
            self.unadopted[key] = "over fleet byte budget"
            return None, None           # can't afford another tenant
        if ls is None:
            got = lease_mod.try_acquire(ts_dir, self.worker_id,
                                        self.lease_ttl,
                                        now=self.clock())
            if got is None:
                self.unadopted[key] = "lost an acquire race"
                return None, None
            self.unadopted.pop(key, None)
            telemetry.REGISTRY.counter(
                "live_lease_acquired_total").inc()
            return got, "acquire"
        silent = self._observer.silent_s(key, ls)
        if not self._observer.expired(key, ls, self.lease_ttl):
            self.unadopted[key] = (f"lease held by {ls.owner} "
                                   f"(epoch {ls.epoch})")
            return None, None
        got = lease_mod.takeover(ts_dir, self.worker_id,
                                 self.lease_ttl, ls,
                                 now=self.clock())
        if got is None:
            self.unadopted[key] = "lost a takeover race"
            return None, None
        self.unadopted.pop(key, None)
        self._observer.forget(key)
        self.takeovers += 1
        lag = max(silent, 0.0)
        self.max_takeover_lag_s = max(self.max_takeover_lag_s, lag)
        telemetry.REGISTRY.counter("live_lease_takeover_total").inc()
        telemetry.REGISTRY.counter("live_lease_expired_total").inc()
        telemetry.REGISTRY.gauge(
            "live_lease_max_takeover_lag_seconds").set(
            self.max_takeover_lag_s)
        got._takeover_of = ls           # for the journal entry
        got._silent_s = lag
        return got, "takeover"

    def _adopt(self, key, ts_dir, owned=None, via=None) -> None:
        # transactional runs adopt as TxnTenant when the lease carries
        # a txn checkpoint pointer or test.json names an elle workload;
        # anything undecidable adopts as a window tenant and may still
        # promote on its FIRST ingested batch (nothing consumed yet)
        if self._is_txn_run(ts_dir, owned):
            t = self.tenants[key] = TxnTenant(
                key[0], key[1], ts_dir,
                backend=self.txn_backend,
                window_txns=self.txn_window)
            telemetry.REGISTRY.counter("live_txn_tenants_total").inc()
        else:
            t = self.tenants[key] = Tenant(
                key[0], key[1], ts_dir,
                self._model_for(ts_dir), **self.lane_opts)
        # takeovers resume the tenant log's sequence (and truncate a
        # torn tail) instead of restarting at 0, so the timeline stays
        # one readable log across owners; flags already journaled are
        # loaded for exactly-once de-duplication
        resume = bool(self.lease_ttl)
        if resume and (ts_dir / "live.jsonl").exists():
            try:
                for ev in telemetry.read_events(ts_dir / "live.jsonl"):
                    if ev.get("type") == "live-flag":
                        t.flags_emitted.add((ev.get("lane"),
                                             ev.get("op_index")))
            except Exception:  # noqa: BLE001 - dedupe is best-effort
                pass
        # fleet logs are epoch-stamped: a SIGSTOP-resumed stale worker
        # finishing an in-flight append after takeover is fenced by
        # READERS (lower-epoch records skipped), since no writer-side
        # check can cover a pause landing after the fence gate
        self._logs[key] = telemetry.EventLog(
            ts_dir / "live.jsonl", resume=resume,
            epoch=owned.epoch if owned is not None else None)
        # the trace index rides beside live.jsonl with the same
        # resume/epoch discipline: one causal record per flag, plus
        # the cross-worker span links a takeover mints
        self._tracelogs[key] = telemetry.EventLog(
            ts_dir / "trace-index.jsonl", resume=resume,
            epoch=owned.epoch if owned is not None else None)
        if owned is not None:
            with self._lease_lock:
                self._leases[key] = owned
            self._fence_checked[key] = self.mono()
            # resume from the recorded safe cursor, seeding the lanes
            # with the lease-carried checker frontier (captured at
            # that exact cursor).  No restorable frontier -> re-check
            # from byte 0 instead: leniently resuming wild mid-stream
            # could MISS a violation whose constraining writes predate
            # the cursor, and a full replay only costs time (flags
            # de-dup against live.jsonl, so still exactly-once).
            restored = 0
            if owned.state and (owned.offset or owned.seq):
                restored = t.restore_frontier(owned.state)
            if restored or not (owned.offset or owned.seq):
                t.offset, t.seq = owned.offset, owned.seq
                t._record_n = owned.seq
                t.safe_offset, t.safe_seq = owned.offset, owned.seq
                t.safe_state = owned.state
            else:
                telemetry.REGISTRY.counter(
                    "live_fleet_full_replays_total").inc()
        self._emit(key, "live-adopt", durable=True,
                   model=type(t.model).__name__)
        if via == "acquire":
            self._emit(key, "lease-acquire", durable=True,
                       worker=self.worker_id, epoch=owned.epoch,
                       ttl=owned.ttl)
        elif via == "takeover":
            old = getattr(owned, "_takeover_of", None)
            self._emit(key, "lease-expire", durable=True,
                       worker=getattr(old, "owner", None),
                       epoch=getattr(old, "epoch", None),
                       reason=(getattr(old, "corrupt", None)
                               or ("released"
                                   if getattr(old, "released", False)
                                   else "heartbeat silent")),
                       silent_s=round(
                           getattr(owned, "_silent_s", 0.0), 3))
            self._emit(key, "lease-takeover", durable=True,
                       worker=self.worker_id, epoch=owned.epoch,
                       from_worker=getattr(old, "owner", None),
                       cursor={"offset": owned.offset,
                               "seq": owned.seq},
                       silent_s=round(
                           getattr(owned, "_silent_s", 0.0), 3))
            self._link_trace(key, owned, old)

    def _is_txn_run(self, ts_dir, owned) -> bool:
        st = getattr(owned, "state", None)
        if isinstance(st, dict) and "txn" in st:
            return True
        try:
            with open(ts_dir / "test.json") as f:
                wl = json.load(f).get("workload")
        except Exception:  # noqa: BLE001 - absent/partial test.json
            return False
        return wl in ("list-append", "rw-register")

    def _promote_txn(self, key, old, workload: str):
        """Swap a just-adopted window tenant for a TxnTenant before
        any op is consumed (first-batch sniff found mop-list txns).
        Cursor/flag bookkeeping carries over losslessly — nothing was
        demuxed into lanes yet."""
        t = self.tenants[key] = TxnTenant(
            key[0], key[1], old.run_dir, workload=workload,
            backend=self.txn_backend, window_txns=self.txn_window)
        for f in ("offset", "seq", "safe_offset", "safe_seq",
                  "safe_state", "paused", "done", "_record_n",
                  "ops_ingested", "skipped"):
            setattr(t, f, getattr(old, f))
        t.flags_emitted = set(old.flags_emitted)
        telemetry.REGISTRY.counter("live_txn_tenants_total").inc()
        self._emit(key, "live-adopt-txn", durable=True,
                   workload=workload)
        return t

    def _model_for(self, run_dir: Path):
        try:
            with open(run_dir / "test.json") as f:
                name = json.load(f).get("model")
        except Exception:  # noqa: BLE001 - absent/partial test.json
            name = None
        try:
            return _default_model(name if isinstance(name, str)
                                  else self.default_model)
        except ValueError:
            return _default_model(self.default_model)

    # -- events --------------------------------------------------------------

    def _emit(self, key, type_: str, durable: bool = False,
              **fields) -> None:
        lg = self._logs.get(key)
        if lg is not None:
            lg.append({"type": type_, **fields}, durable=durable)

    def _emit_trace(self, key, type_: str, **fields) -> None:
        lg = self._tracelogs.get(key)
        if lg is not None:
            lg.append({"type": type_, **fields}, durable=True)

    # -- causal flight recorder (ISSUE 19) -----------------------------------

    def _link_trace(self, key, owned, old) -> None:
        """Mint the cross-worker span link on takeover: the dead
        worker's checkpointed span (riding the lease `state` slot
        exactly like the checker frontier) links to THIS worker's
        deterministic resume span.  Journaled durably into the trace
        index — once per takeover — so the flag's causal chain can
        shade the handoff gap."""
        st = getattr(owned, "state", None)
        prev = st.get("trace") if isinstance(st, dict) else None
        prev = prev if isinstance(prev, dict) else {}
        parsed = trace_mod.parse_ctx(trace_mod.synth_ctx(
            key[0], key[1], self.worker_id, owned.epoch))
        trace_id, resume_span = parsed
        link = {"trace_id": prev.get("trace_id") or trace_id,
                "from_worker": prev.get("worker")
                or getattr(old, "owner", None),
                "from_epoch": prev.get("epoch")
                or getattr(old, "epoch", None),
                "from_span": prev.get("span"),
                "to_worker": self.worker_id,
                "to_epoch": owned.epoch,
                "resume_span": resume_span,
                "silent_s": round(
                    getattr(owned, "_silent_s", 0.0), 3)}
        self._trace_links[key] = link
        self._emit_trace(key, "trace-link", **link)
        telemetry.REGISTRY.counter("live_trace_links_total").inc()

    def _wrap_trace_state(self, key, fs_state):
        """Ride this worker's checkpoint span on the lease state slot
        beside the checker frontier.  Extra keys are invisible to both
        restore paths (window tenants match on `model`, txn tenants on
        `txn`), so old readers behave exactly as before."""
        with self._lease_lock:
            mine = self._leases.get(key)
        epoch = getattr(mine, "epoch", 0)
        parsed = trace_mod.parse_ctx(trace_mod.synth_ctx(
            key[0], key[1], self.worker_id, epoch))
        out = dict(fs_state) if isinstance(fs_state, dict) else {}
        out["trace"] = {"worker": self.worker_id, "epoch": epoch,
                        "trace_id": parsed[0], "span": parsed[1]}
        return out

    def note_transport(self, key, rows) -> None:
        """Transport stamps pushed by an in-process ingest server:
        `rows` is [(seq, fs, recv, synced)] per traced record.  Late
        stamps (a mark outrun by its ack) merge field-wise; the dict
        is bounded per tenant — stamps are advisory, the flag path
        collapses a missing one to a zero-width segment."""
        key = tuple(key)
        with self._transport_lock:
            stamps = self._transport.setdefault(key, {})
            for row in rows:
                seq = row[0]
                if not isinstance(seq, int):
                    continue
                slot = stamps.setdefault(seq, [None, None, None])
                for j, v in enumerate(row[1:4]):
                    if v is not None and slot[j] is None:
                        slot[j] = float(v)
            if len(stamps) > 8192:
                for s in sorted(stamps)[:4096]:
                    del stamps[s]

    def _transport_for(self, key, seq) -> tuple:
        if not isinstance(seq, int):
            return (None, None, None)
        with self._transport_lock:
            slot = self._transport.get(tuple(key), {}).get(seq)
            return tuple(slot) if slot else (None, None, None)

    def _trace_flag(self, key, t, lane_repr: str, flag: dict,
                    det, now: float, win_wall, dis_s,
                    dispatch_id, engine) -> tuple:
        """Journal one causal `trace-flag` record for a just-emitted
        flag and feed the segment histograms + SLO burn gauges.
        Returns (trace_id, dominant_segment) for the live-flag row.
        Advisory end to end: any failure here must never block the
        exactly-once flag emission, so the caller wraps it."""
        ctx = flag.get("ctx")
        parsed = trace_mod.parse_ctx(ctx) if ctx else None
        if parsed is None:
            parsed = trace_mod.parse_ctx(trace_mod.synth_ctx(
                key[0], key[1], flag.get("op_index")))
        trace_id, span_id = parsed
        fs, recv, synced = self._transport_for(key, flag.get("seq"))
        stamps = {"w": flag.get("wall"), "fs": fs, "recv": recv,
                  "synced": synced, "win": win_wall, "dis_s": dis_s,
                  "flag": now}
        segs = trace_mod.lag_segments(stamps)
        dominant = trace_mod.dominant_segment(segs)
        if segs is not None:
            self._seg_n += 1
            for seg, v in segs.items():
                telemetry.REGISTRY.histogram(
                    "live_lag_segment_seconds", segment=seg,
                    buckets=LAG_BUCKETS_S).observe(v)
                if v > self._seg_max.get(seg, 0.0):
                    self._seg_max[seg] = v
                    telemetry.REGISTRY.gauge(
                        "live_trace_max_segment_seconds",
                        segment=seg).set(round(v, 6))
                if v > self._seg_bands.get(seg, float("inf")):
                    self._seg_over[seg] = \
                        self._seg_over.get(seg, 0) + 1
            for seg in trace_mod.SEGMENTS:
                telemetry.REGISTRY.gauge(
                    "live_lag_slo_burn", segment=seg).set(round(
                        self._seg_over.get(seg, 0) / self._seg_n, 6))
        link = self._trace_links.get(key)
        self._emit_trace(
            key, "trace-flag", trace_id=trace_id, span=span_id,
            parent=link.get("resume_span") if link else None,
            ctx_source="wal" if ctx else "synth",
            lane=lane_repr, op_index=flag.get("op_index"),
            f=flag.get("f"), event=flag.get("event"),
            seq=flag.get("seq"),
            stamps={k: round(v, 6) for k, v in stamps.items()
                    if isinstance(v, (int, float))},
            segments=segs,
            lag_s=round(det, 6) if det is not None else None,
            dominant=dominant, worker=self.worker_id,
            epoch=getattr(self._leases.get(key), "epoch", None),
            dispatch_id=dispatch_id, engine=engine, link=link)
        telemetry.REGISTRY.counter("live_trace_records_total").inc()
        return trace_id, dominant

    # -- fencing (fleet mode) ------------------------------------------------

    def _fenced(self, key, fresh: bool = False) -> bool:
        """True when this worker may no longer publish for `key`.
        Cached reads are re-validated after a quarter-TTL — measured
        on OUR monotonic clock, so a SIGSTOP/resume gap (the exact
        split-brain window) invalidates the cache by construction.
        `fresh=True` forces a re-read (the pre-flag hard check)."""
        if not self.lease_ttl:
            return False
        with self._lease_lock:
            mine = self._leases.get(key)
        if mine is None:
            return True
        now = self.mono()
        if not fresh:
            last = self._fence_checked.get(key)
            if last is not None and now - last < self.lease_ttl / 4:
                return False
        t = self.tenants.get(key)
        if t is None or not lease_mod.check_fence(t.run_dir, mine):
            return True
        self._fence_checked[key] = now
        return False

    def _drop_fenced(self, key) -> None:
        """A stale-epoch worker refusing to publish: release the
        tenant WITHOUT writing anything into its run dir (the new
        owner holds the log now), count it, and journal the refusal
        into this worker's own fleet log."""
        self.fenced_writes += 1
        telemetry.REGISTRY.counter("live_lease_fenced_total").inc()
        with self._lease_lock:
            mine = self._leases.pop(key, None)
        self._fence_checked.pop(key, None)
        self._observer.forget(key)
        t = self.tenants.pop(key, None)
        lg = self._logs.pop(key, None)
        if lg is not None:
            lg.close()
        tlg = self._tracelogs.pop(key, None)
        if tlg is not None:
            tlg.close()
        self._trace_links.pop(key, None)
        with self._transport_lock:
            self._transport.pop(key, None)
        log.warning("worker %s fenced off %s/%s (stale epoch %s); "
                    "publish refused, tenant dropped", self.worker_id,
                    key[0], key[1],
                    getattr(mine, "epoch", "?"))
        self._fleet_log("lease-fenced", tenant=f"{key[0]}/{key[1]}",
                        epoch=getattr(mine, "epoch", None),
                        offset=getattr(t, "offset", None))

    _fleet_logger = None

    def _fleet_log(self, type_: str, **fields) -> None:
        """Append to this worker's own store/fleet/<worker>.jsonl —
        the single-writer home for events about the WORKER (fencing
        refusals) rather than a tenant it may no longer own."""
        if not self.lease_ttl:
            return
        try:
            if self._fleet_logger is None:
                d = self.root / "fleet"
                d.mkdir(parents=True, exist_ok=True)
                self._fleet_logger = telemetry.EventLog(
                    d / f"{self.worker_id}.jsonl", resume=True)
            self._fleet_logger.append(
                {"type": type_, "worker": self.worker_id, **fields},
                durable=True)
        except Exception:  # noqa: BLE001 - bookkeeping must not wedge
            log.debug("fleet log write failed", exc_info=True)

    def renew_leases(self, force: bool = False) -> int:
        """Heartbeat: re-stamp every owned lease with its tenant's
        safe cursor.  Called from the tick loop (quarter-TTL cadence)
        and from the service's heartbeat thread (so a long device
        dispatch cannot silently expire us).  A failed renewal means
        we were fenced — the tenant is dropped without publishing.
        Returns leases renewed."""
        if not self.lease_ttl:
            return 0
        now = self.mono()
        if not force and now - self._last_renew < self.lease_ttl / 4:
            return 0
        self._last_renew = now
        renewed = 0
        with self._lease_lock:
            items = list(self._leases.items())
        for key, mine in items:
            t = self.tenants.get(key)
            cursor = (t.safe_offset, t.safe_seq) if t is not None \
                else None
            nxt = lease_mod.renew(t.run_dir if t is not None
                                  else self.root / key[0] / key[1],
                                  mine, cursor=cursor,
                                  state=getattr(t, "safe_state", None),
                                  now=self.clock())
            if nxt is None:
                self._drop_fenced(key)
                continue
            with self._lease_lock:
                if key in self._leases:
                    self._leases[key] = nxt
            self._fence_checked[key] = self.mono()
            telemetry.REGISTRY.counter(
                "live_lease_renewals_total").inc()
            renewed += 1
        return renewed

    def _release_lease(self, key, t, done: bool = False) -> None:
        """Mark an owned lease released.  A plain release is a clean
        handoff (the next worker may take over immediately, no TTL
        wait); `done=True` is terminal — the tenant drained and its
        final snapshot published, so no worker may ever re-adopt."""
        with self._lease_lock:
            mine = self._leases.pop(key, None)
        self._fence_checked.pop(key, None)
        if mine is not None and t is not None:
            lease_mod.renew(t.run_dir, mine,
                            cursor=(t.safe_offset, t.safe_seq),
                            state=getattr(t, "safe_state", None),
                            now=self.clock(), released=True,
                            done=done)

    # -- ingest --------------------------------------------------------------

    def _ingest(self, key, t: Tenant) -> None:
        if t.corrupt or t.done:
            return
        # backpressure: over budget -> stop reading (the cursor simply
        # does not advance; disk holds the backlog); resume below the
        # half-budget low-water mark
        nbytes = t.nbytes
        if t.paused:
            if nbytes <= self.tenant_budget_bytes // 2:
                t.paused = False
                self._emit(key, "live-resume", durable=True,
                           bytes=nbytes)
            else:
                return
        elif nbytes > self.tenant_budget_bytes:
            t.paused = True
            telemetry.REGISTRY.counter(
                "live_backpressure_total").inc()
            self._emit(key, "live-backpressure", durable=True,
                       bytes=nbytes,
                       budget=self.tenant_budget_bytes)
            return
        wal = t.run_dir / "history.wal"
        try:
            seg = history_mod.follow(wal, t.offset, t.seq,
                                     max_records=self.max_batch_records)
        except OSError as e:
            t.corrupt = f"wal unreadable: {e}"
            return
        if seg.ops:
            now = self.clock()
            walls = [w if w is not None else now for w in seg.walls]
            if not getattr(t, "is_txn", False) \
                    and t.ops_ingested == 0 and not t.lanes:
                wl = sniff_txn_workload(seg.ops)
                if wl is not None:
                    t = self._promote_txn(key, t, wl)
            t.ingest(seg.ops, walls, ctxs=seg.ctxs, seqs=seg.seqs)
            t.offset, t.seq = seg.offset, seg.seq
            telemetry.REGISTRY.counter(
                "live_ops_ingested_total").inc(len(seg.ops))
        if seg.corrupt:
            t.corrupt = seg.stop_reason
            self._emit(key, "live-corrupt", durable=True,
                       reason=seg.stop_reason)
        elif not seg.ops and seg.tail_bytes == 0 \
                and (t.run_dir / "results.json").exists():
            # run analyzed + nothing left to read: the tenant is done
            # once its queued windows drain
            t.done = True

    # -- dispatch ------------------------------------------------------------

    def _collect(self) -> list:
        items = []
        cut = self.clock()             # the window-cut stamp (`win`)
        for key, t in self.tenants.items():
            for lane_key, lane in t.lanes.items():
                w = lane.take_window()
                if w is not None:
                    w.lane_key = lane_key
                    w.cut_wall = cut
                    items.append((key, lane_key, lane, w))
        return items

    def _dispatch(self, items: list) -> None:
        backend = self.resolve_backend()
        dispatches: list = []

        def live_engine(_model, lane_dispatches):
            return engine_mod.check_batch(
                list(lane_dispatches), backend=backend,
                dispatches=dispatches)

        def live_host(_model, lane_dispatch, time_limit=None):
            return engine_mod.check_batch(
                [lane_dispatch], backend="host",
                dispatches=dispatches)[0]

        live_host.__name__ = "live-host"
        runner = ResilientRunner(engine=live_engine,
                                 cpu_fallback=live_host,
                                 deadline_s=self.deadline_s,
                                 max_group=64)
        verdicts = runner.check(None,
                                [w.dispatch for (_k, _lk, _ln, w)
                                 in items])

        # one global id per bucket dispatch; every participating
        # tenant journals it, so cross-tenant sharing is auditable
        ids = {}
        for di, d in enumerate(dispatches):
            self._dispatch_seq += 1
            d["id"] = ids[di] = f"d{self._dispatch_seq}"
            d["tenants"] = sorted({f"{k[0]}/{k[1]}"
                                   for (k, _lk, _ln, w), v
                                   in zip(items, verdicts)
                                   if isinstance(v, dict)
                                   and v.get("dispatch_index") == di})
            rec = telemetry.dispatch_record(
                d["engine"], why="live window micro-batch",
                cache=d["cache"], lanes=d["lanes"],
                bucket=d["bucket"], dispatch_id=d["id"],
                tenants=len(d["tenants"]))
            telemetry.attach_dispatch([], rec)
        seen_pairs = set()
        fenced_keys = set()
        now = self.clock()
        for (key, lane_key, lane, w), v in zip(items, verdicts):
            if not isinstance(v, dict) or key in fenced_keys:
                continue
            if v.get("quarantined"):
                lane.saturated = ("live checking quarantined: "
                                  + str(v.get("error") or v.get("why")
                                        or "engine failure"))
                self._emit(key, "live-quarantine", durable=True,
                           lane=repr(lane_key),
                           error=str(v.get("error"))[:200])
                continue
            di = v.get("dispatch_index", -1)
            disp = dispatches[di] if 0 <= di < len(dispatches) else {}
            if (key, di) not in seen_pairs and disp:
                seen_pairs.add((key, di))
                self._emit(key, "live-dispatch",
                           dispatch_id=disp.get("id"),
                           engine=disp.get("engine"),
                           cache=disp.get("cache"),
                           lanes=disp.get("lanes"),
                           tenants=disp.get("tenants"),
                           bucket=disp.get("bucket"),
                           seconds=disp.get("seconds"))
            flag = lane.apply_result(w, v)
            lag = (now - w.last_wall) if w.last_wall else None
            if lag is not None:
                telemetry.REGISTRY.histogram(
                    "live_window_lag_seconds",
                    buckets=LAG_BUCKETS_S).observe(lag)
            self._emit(key, "live-window",
                       lane=repr(lane_key), ops=w.n_ops,
                       events=int(w.dispatch.n_events),
                       valid=bool(v.get("valid?")),
                       lag_s=round(lag, 6) if lag is not None
                       else None)
            if flag is not None:
                # fleet discipline around the one emission that MUST
                # be exactly-once: a takeover replaying from the safe
                # cursor suppresses flags already journaled, and a
                # stale-epoch worker re-reads the lease (fresh, not
                # cached) and refuses to publish at all
                t = self.tenants.get(key)
                fkey = (repr(lane_key), flag.get("op_index"))
                if t is not None and fkey in t.flags_emitted:
                    telemetry.REGISTRY.counter(
                        "live_fleet_flags_suppressed_total").inc()
                    continue
                if self._fenced(key, fresh=True):
                    fenced_keys.add(key)
                    self._drop_fenced(key)
                    continue
                if t is not None:
                    t.flags_emitted.add(fkey)
                det = (now - flag["wall"]) if flag.get("wall") \
                    else lag
                self.flags_total += 1
                self.last_detection_lag_s = det
                telemetry.REGISTRY.counter("live_flags_total").inc()
                if det is not None:
                    telemetry.REGISTRY.gauge(
                        "live_detection_lag_seconds").set(det)
                    telemetry.REGISTRY.histogram(
                        "live_detection_lag_histogram_seconds",
                        buckets=LAG_BUCKETS_S).observe(det)
                try:
                    trace_id, dominant = self._trace_flag(
                        key, t, repr(lane_key), flag, det, now,
                        getattr(w, "cut_wall", None),
                        disp.get("seconds"), disp.get("id"),
                        v.get("engine"))
                except Exception:  # noqa: BLE001 - tracing is
                    trace_id = dominant = None   # advisory, the flag
                    log.debug("trace-flag failed",  # is not
                              exc_info=True)
                self._emit(key, "live-flag", durable=True,
                           lane=repr(lane_key),
                           op_index=flag.get("op_index"),
                           f=flag.get("f"),
                           value=flag.get("value"),
                           event=flag.get("event"),
                           detection_lag_s=round(det, 6)
                           if det is not None else None,
                           dispatch_id=disp.get("id"),
                           engine=v.get("engine"),
                           cache=v.get("cache"),
                           trace=trace_id,
                           lag_segment=dominant)

    # -- dispatch: transactional tenants (ISSUE 18) --------------------------

    def _txn_backlog(self, t) -> bool:
        try:
            return (t.run_dir / "history.wal").stat().st_size \
                > t.offset
        except OSError:
            return False

    def _dispatch_txn(self) -> int:
        """Advance every transactional tenant: feed buffered ops,
        drain edge deltas into the packed planes, update the closure
        warm, and publish NEW anomaly flags (same exactly-once
        discipline as window flags: journal de-dup + a fresh fence
        re-read before the durable emission).  Returns windows
        classified this tick."""
        nwin = 0
        for key, t in list(self.tenants.items()):
            if not getattr(t, "is_txn", False) or t.corrupt \
                    or key not in self.tenants:
                continue
            if not (t.pending_ops or t.need_classify):
                continue
            # classify every window_txns new txns under sustained
            # load; force at stream quiescence (WAL caught up or run
            # done) so the last partial window never waits
            force = t.done or not self._txn_backlog(t)
            now = self.clock()
            try:
                out = t.advance(now=now, force=force)
            except Exception as e:  # noqa: BLE001 - quarantine tenant
                t.corrupt = f"txn engine: {e}"
                self._emit(key, "live-corrupt", durable=True,
                           reason=t.corrupt[:200])
                continue
            win = out.get("window")
            if win:
                nwin += 1
                telemetry.REGISTRY.counter(
                    "live_txn_windows_total").inc()
                telemetry.REGISTRY.counter(
                    "live_txn_txns_total").inc(win["new_txns"])
                lag = (now - t.last_wall) if t.last_wall else None
                if lag is not None:
                    telemetry.REGISTRY.histogram(
                        "live_window_lag_seconds",
                        buckets=LAG_BUCKETS_S).observe(lag)
                self._emit(key, "live-txn-window",
                           txns=win["txns"], new_txns=win["new_txns"],
                           dirty_keys=win["dirty_keys"],
                           added=win["added"], removed=win["removed"],
                           rebuild=win["rebuild"],
                           rounds=win["rounds"], engine=win["engine"],
                           weakest=win["weakest"],
                           seconds=win["seconds"],
                           lag_s=round(lag, 6) if lag is not None
                           else None)
            for flag in out["flags"]:
                fkey = (flag["lane"], flag["op_index"])
                if fkey in t.flags_emitted:
                    telemetry.REGISTRY.counter(
                        "live_fleet_flags_suppressed_total").inc()
                    continue
                if self._fenced(key, fresh=True):
                    self._drop_fenced(key)
                    break
                t.flags_emitted.add(fkey)
                t.record_flag(flag)
                det = (now - flag["wall"]) if flag.get("wall") \
                    else None
                self.flags_total += 1
                telemetry.REGISTRY.counter("live_flags_total").inc()
                telemetry.REGISTRY.counter(
                    "live_txn_flags_total").inc()
                if flag.get("level"):
                    telemetry.REGISTRY.counter(
                        "live_txn_levels_total",
                        level=flag["level"]).inc()
                if det is not None:
                    self.last_detection_lag_s = det
                    telemetry.REGISTRY.gauge(
                        "live_detection_lag_seconds").set(det)
                    telemetry.REGISTRY.gauge(
                        "live_txn_detect_lag_seconds").set(det)
                    telemetry.REGISTRY.histogram(
                        "live_detection_lag_histogram_seconds",
                        buckets=LAG_BUCKETS_S).observe(det)
                try:
                    win_wall = (now - win["seconds"]) if win else None
                    trace_id, dominant = self._trace_flag(
                        key, t, flag["lane"], flag, det, now,
                        win_wall, win["seconds"] if win else None,
                        None, flag.get("engine"))
                except Exception:  # noqa: BLE001 - tracing is
                    trace_id = dominant = None   # advisory, the flag
                    log.debug("trace-flag failed",  # is not
                              exc_info=True)
                self._emit(key, "live-flag", durable=True,
                           lane=flag["lane"],
                           op_index=flag["op_index"],
                           f="txn", value=flag.get("value"),
                           event=flag.get("event"),
                           level=flag.get("level"),
                           detection_lag_s=round(det, 6)
                           if det is not None else None,
                           engine=flag.get("engine"),
                           trace=trace_id,
                           lag_segment=dominant)
        return nwin

    # -- snapshots -----------------------------------------------------------

    def _write_live_json(self, key, t: Tenant) -> None:
        stats = t.stats()
        stats.update({
            "backend": self.backend or self.backend_opt,
            "plan_cache": engine_mod.plan_cache_stats(),
            "budget_bytes": self.tenant_budget_bytes,
            "updated": round(self.clock(), 3),
        })
        if self.lease_ttl:
            with self._lease_lock:
                mine = self._leases.get(key)
            if mine is None:
                return                 # fenced (possibly mid-tick by
            stats["worker"] = self.worker_id  # the heartbeat thread)
            stats["epoch"] = mine.epoch
        # flags rendered with their journaled detection lag
        path = t.run_dir / "live.json"
        tmp = t.run_dir / ".live.json.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(stats, f, indent=2, default=repr)
            # lint: rename-ok(per-tick snapshot rewritten constantly; atomicity is the contract, and an fsync here would put a disk sync on the hot scan loop — durable state lives in live.jsonl/lease.json)
            os.replace(tmp, path)
        except OSError:
            log.debug("live.json write failed for %s", key,
                      exc_info=True)

    def _gauges(self) -> None:
        for (name, ts), t in self.tenants.items():
            label = f"{name}/{ts}"
            telemetry.REGISTRY.gauge("live_window_queue_depth",
                                     tenant=label).set(t.queue_depth)
            telemetry.REGISTRY.gauge("live_tenant_bytes",
                                     tenant=label).set(t.nbytes)
        if self.lease_ttl:
            telemetry.REGISTRY.gauge(
                "live_fleet_owned_tenants",
                worker=self.worker_id).set(len(self._leases))
            telemetry.REGISTRY.gauge(
                "live_fleet_owned_bytes",
                worker=self.worker_id).set(self._owned_bytes())

    # -- the tick ------------------------------------------------------------

    def tick(self) -> dict:
        due = self._tick_n % self.scan_every == 0
        # fleet mode: expiry is judged by observed silence, so the
        # scan cadence bounds takeover latency — rescan at least every
        # quarter-TTL of wall time regardless of tick count, keeping
        # "survivor takes over within ~one TTL" true even for an idle
        # worker whose ticks are slow
        if not due and self.lease_ttl \
                and self.mono() - self._last_discover \
                >= self.lease_ttl / 4:
            due = True
        if due:
            self.discover()
            self._last_discover = self.mono()
        self._tick_n += 1
        # fleet mode: verify ownership BEFORE touching a tenant's run
        # dir this tick — a fenced (stale-epoch) worker must refuse to
        # publish, not interleave with the new owner
        if self.lease_ttl:
            for key in list(self.tenants):
                if self._fenced(key):
                    self._drop_fenced(key)
        for key, t in list(self.tenants.items()):
            self._ingest(key, t)
        items = self._collect()
        if items:
            self._dispatch(items)
        txn_windows = self._dispatch_txn()
        # snapshot + finalize
        for key, t in list(self.tenants.items()):
            self._write_live_json(key, t)
            # advance the lease-recorded SAFE cursor only at fully
            # quiescent points: everything before it was checked and
            # published, so a takeover resuming here loses nothing
            # (re-checks between here and the dead worker's true
            # progress de-dup against live.jsonl)
            if not t.open_by_process and t.queue_depth == 0 \
                    and all(not ln.buffer for ln in t.lanes.values()):
                t.safe_offset, t.safe_seq = t.offset, t.seq
                if self.lease_ttl:
                    # the frontier capture pairs with THIS cursor: a
                    # successor restoring it resumes exactly here —
                    # and carries this worker's checkpoint span, so a
                    # takeover can mint the cross-worker span link
                    t.safe_state = self._wrap_trace_state(
                        key, t.frontier_state())
            if t.done and t.queue_depth == 0:
                self._emit(key, "live-done", durable=True,
                           **{"verdict-so-far":
                              t.stats()["verdict-so-far"]})
                self._release_lease(key, t, done=True)
                lg = self._logs.pop(key, None)
                if lg is not None:
                    lg.close()
                tlg = self._tracelogs.pop(key, None)
                if tlg is not None:
                    tlg.close()
                with self._transport_lock:
                    self._transport.pop(key, None)
                self.finished.add(key)
                del self.tenants[key]
        self.renew_leases()
        self._gauges()
        return {"tenants": len(self.tenants),
                "finished": len(self.finished),
                "windows": len(items) + txn_windows,
                "flags_total": self.flags_total}

    def drain(self, max_ticks: int = 10_000) -> int:
        """Tick until no new bytes, no ready windows, and no queued
        chunks remain (the `--once` path and the test harness).
        Returns ticks used."""
        for n in range(1, max_ticks + 1):
            stats = self.tick()
            busy = (stats["windows"] > 0 or self._has_new_bytes()
                    or any(t.queue_depth
                           for t in self.tenants.values()))
            if not busy:
                return n
        return max_ticks

    def _has_new_bytes(self) -> bool:
        for t in self.tenants.values():
            if t.corrupt or t.done:
                continue               # the cursor will never advance
            try:
                if (t.run_dir / "history.wal").stat().st_size \
                        > t.offset:
                    return True
            except OSError:
                continue
        return False

    def finalize_unadopted(self) -> int:
        """Write a final atomic `live.json` for every run this
        scheduler saw but never managed to adopt (foreign unexpired
        lease, a lost race, an adoption error over a mangled WAL), so
        `/fleet` and `/live` can show them as *visibly unowned* rather
        than absent — the `--once` drain-summary satellite.  Never
        clobbers a real owner's snapshot.  Returns summaries
        written."""
        written = 0
        for key, ts_dir in self._run_dirs():
            if key in self.tenants or key in self.finished:
                continue
            if (ts_dir / "live.json").exists():
                continue                # someone's snapshot: keep it
            why = self.unadopted.get(key, "never adopted")
            stats = {"verdict-so-far": "unknown", "unowned": True,
                     "reason": why, "flags": [],
                     "updated": round(self.clock(), 3)}
            tmp = ts_dir / ".live.json.tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump(stats, f, indent=2, default=repr)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, ts_dir / "live.json")
                written += 1
            except OSError:
                log.debug("unowned live.json write failed for %s",
                          key, exc_info=True)
        return written

    def close(self) -> None:
        # clean shutdown releases every owned lease so a peer can take
        # the tenants over immediately (no TTL wait)
        if self.lease_ttl:
            for key, t in list(self.tenants.items()):
                self._release_lease(key, t)
        for lg in self._logs.values():
            lg.close()
        self._logs.clear()
        for tlg in self._tracelogs.values():
            tlg.close()
        self._tracelogs.clear()
        if self._fleet_logger is not None:
            self._fleet_logger.close()
            self._fleet_logger = None


def _probe_lane():
    """A minimal one-event lane for the device probe."""
    import numpy as np
    from jepsen_tpu.live.engine import LaneDispatch
    plane = np.zeros((2, 2), bool)
    plane[0, 0] = True
    return LaneDispatch(
        plane=plane,
        slot_next=np.zeros((1, 2), np.int32),
        slot_legal=np.zeros((1, 2), bool),
        slot_open=np.zeros(1, bool),
        ev_kind=np.zeros(1, np.int32),
        ev_slot=np.zeros(1, np.int32),
        ev_next=np.zeros((1, 2), np.int32),
        ev_legal=np.zeros((1, 2), bool))
