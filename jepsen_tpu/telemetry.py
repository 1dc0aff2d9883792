"""Unified telemetry: metrics registry + crash-safe event log — the
observability layer spanning both halves of the system (ISSUE 4).

Jepsen's value is explaining *why* a run produced its verdict; this
module makes every verdict and every benchmark number carry its own
attribution.  Following the Dapper model (low-overhead, always-on for
named runs) and Prometheus-style pull metrics, it provides:

  * **MetricsRegistry** — thread-safe counters, gauges, and histograms
    with fixed bucket boundaries and label sets, rendered as Prometheus
    text exposition by `snapshot()` (scrape it from `web.py`'s
    `/metrics` endpoint or dump it programmatically).
  * **EventLog** — a crash-safe, append-only JSONL log written to
    `store/<name>/<ts>/telemetry.jsonl` with the same fsync/CRC
    discipline as the history WAL (history.HistoryWAL): every record
    carries a sequence number and a crc32 of its canonical payload, so
    a SIGKILLed run leaves at worst one torn trailing line and
    `read_events` recovers the intact prefix.  High-rate records (per-
    op latencies) are flushed but not fsynced; state-changing records
    (fault windows, breaker transitions) are fsynced — see
    docs/observability.md for the overhead accounting.
  * **Telemetry** — one per named test (core.run builds it via
    `for_test`), combining the process-global registry with the run's
    event log.  The disabled path is a single attribute check per
    call: telemetry must cost nothing when it is off.
  * **dispatch records** — the inspectable account of which engine
    checked which history and why (`engine`, `fallback_chain`, `why`,
    `R`, `crashes`, `batch`, `mesh`, and the `JEPSEN_TPU_*` env
    overrides in effect), attached to every verdict by the engine
    entry points (ops/wgl_seg, ops/wgl_deep, ops/wgl_batch,
    ops/runner) and emitted into the active run's event log.
  * **Stages** — one call's stage spans: `with stages.span("scan"):`
    adds the block's self seconds (its duration less the counted spans
    inside it) to the call's stage dict and writes a
    `jax.profiler.TraceAnnotation("jepsen.scan")` over the same
    interval, so stage counters and profiler spans cannot disagree and
    no interval is counted under two labels.  Work counts tallied on
    it reach the registry once per call (`flush`).

Event schema (telemetry.jsonl `ev` payloads; the envelope adds `i`
sequence, `t` wall-clock seconds, `crc`):

    {"type": "run-start", "name": ..., "start_time": ...}
    {"type": "op", "f": ..., "node": ..., "outcome": "ok|fail|info",
     "process": ..., "time": <rel ns>, "latency_ns": ...}
    {"type": "fault-start", "key": ..., "desc": ...}
    {"type": "fault-stop", "key": ..., "healed": <bool>}   # healed =
        reversed by the teardown ledger backstop, not its owner
    {"type": "breaker", "node": ..., "to": "open|half-open|closed",
     "failures": ...}
    {"type": "watchdog-stall", "process": ..., "why": ...}
    {"type": "nemesis", "f": ..., "outcome": ...}
    {"type": "dispatch", "record": {engine, why, fallback_chain, R,
     crashes, batch, mesh, env}, "stages": {stage: seconds}}
    {"type": "span", "span": {...}}                  # trace.py bridge
    {"type": "analyze", "seconds": ..., "valid": ...}
    {"type": "campaign-leak", "keys": [...]}   # a prior schedule's
        faults survived into the inter-schedule gap (campaign.py /
        nemesis.FaultLedger.assert_empty) — journaled, then healed
    {"type": "metrics", "snapshot": "<prometheus text>"}
    {"type": "run-end"}

Three consumption surfaces: `python -m jepsen_tpu.cli metrics
<store-dir>` summarizes a log (see `summarize`), `web.py` renders
`/telemetry` sparklines with nemesis windows shaded, and `snapshot()`
is the Prometheus exposition for scraping.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import zlib
from pathlib import Path
from typing import Optional

# Fixed histogram bucket boundaries (seconds) — Prometheus-style
# cumulative le= buckets; +Inf is implicit.
LATENCY_BUCKETS_S = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic counter (float-valued: stage-seconds accumulate too)."""

    __slots__ = ("_v", "_lock")

    def __init__(self):
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._v


class Gauge:
    __slots__ = ("_v", "_lock")

    def __init__(self):
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._v += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        with self._lock:
            return self._v


class Histogram:
    """Fixed-boundary histogram: cumulative bucket counts + sum + count
    (the Prometheus histogram data model)."""

    __slots__ = ("buckets", "counts", "sum", "count", "_lock")

    def __init__(self, buckets=LATENCY_BUCKETS_S):
        self.buckets = tuple(float(b) for b in buckets)
        assert list(self.buckets) == sorted(self.buckets)
        self.counts = [0] * (len(self.buckets) + 1)   # last = +Inf
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        i = 0
        for b in self.buckets:
            if v <= b:
                break
            i += 1
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def quantile(self, q: float) -> float:
        """Approximate quantile from the bucket boundaries (the upper
        edge of the bucket holding the q-th observation; +Inf bucket
        reports the last finite boundary)."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if not total:
            return 0.0
        rank = q * total
        acc = 0
        for i, c in enumerate(counts):
            acc += c
            if acc >= rank:
                return self.buckets[i] if i < len(self.buckets) \
                    else self.buckets[-1]
        return self.buckets[-1]


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _esc(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class MetricsRegistry:
    """Thread-safe named-metric registry.  Metrics are get-or-created by
    (name, label set); creation races resolve under one lock, and each
    metric guards its own mutation."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}      # name -> (kind, {labelkey: metric})
        self._by_items: dict = {}     # (name, label items) -> Counter

    def _get(self, kind, name: str, labels: dict, ctor):
        key = _label_key(labels)
        with self._lock:
            k, by_label = self._metrics.setdefault(name, (kind, {}))
            if k != kind:
                raise TypeError(f"metric {name!r} already registered "
                                f"as {k}, not {kind}")
            m = by_label.get(key)
            if m is None:
                m = by_label[key] = ctor()
            return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", name, labels, Counter)

    def counter_for(self, name: str, items: tuple) -> Counter:
        """`counter(name, **dict(items))` for a sorted tuple of label
        items, memoised: a per-call flush skips the label normalisation
        and the registry lock."""
        c = self._by_items.get((name, items))
        if c is None:
            c = self._by_items[(name, items)] = self.counter(
                name, **dict(items))
        return c

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", name, labels, Gauge)

    def histogram(self, name: str, buckets=LATENCY_BUCKETS_S,
                  **labels) -> Histogram:
        return self._get("histogram", name, labels,
                         lambda: Histogram(buckets))

    def collect(self) -> dict:
        """{name: (kind, {labelkey: metric})} snapshot (shallow)."""
        with self._lock:
            return {n: (k, dict(b)) for n, (k, b) in self._metrics.items()}

    def snapshot(self) -> str:
        """Prometheus text exposition format."""
        out = []
        for name, (kind, by_label) in sorted(self.collect().items()):
            out.append(f"# TYPE {name} {kind}")
            for key, m in sorted(by_label.items()):
                lab = ",".join(f'{k}="{_esc(v)}"' for k, v in key)
                if kind in ("counter", "gauge"):
                    out.append(f"{name}{{{lab}}} {m.value:g}" if lab
                               else f"{name} {m.value:g}")
                    continue
                with m._lock:
                    counts, s, c = list(m.counts), m.sum, m.count
                acc = 0
                for i, b in enumerate(m.buckets):
                    acc += counts[i]
                    le = f'le="{b:g}"'
                    sep = "," if lab else ""
                    out.append(f"{name}_bucket{{{lab}{sep}{le}}} {acc}")
                sep = "," if lab else ""
                out.append(f'{name}_bucket{{{lab}{sep}le="+Inf"}} {c}')
                out.append(f"{name}_sum{{{lab}}} {s:g}" if lab
                           else f"{name}_sum {s:g}")
                out.append(f"{name}_count{{{lab}}} {c}" if lab
                           else f"{name}_count {c}")
        return "\n".join(out) + ("\n" if out else "")

    def export(self) -> dict:
        """JSON-able dump of every metric — the worker-sidecar half of
        fleet federation (ISSUE 19): each fleet worker embeds this in
        its `store/fleet/<id>.json` status, and `federate()` re-renders
        the merged set as one exposition with `worker_id` labels."""
        out = {}
        for name, (kind, by_label) in sorted(self.collect().items()):
            samples = []
            for key, m in sorted(by_label.items()):
                labels = {k: v for k, v in key}
                if kind in ("counter", "gauge"):
                    samples.append({"labels": labels,
                                    "value": m.value})
                else:
                    with m._lock:
                        samples.append({"labels": labels,
                                        "buckets": list(m.buckets),
                                        "counts": list(m.counts),
                                        "sum": m.sum,
                                        "count": m.count})
            out[name] = {"kind": kind, "samples": samples}
        return out


# The process-global registry: engines, breakers, and the runner record
# into it without per-test plumbing (Prometheus semantics — counters
# are process-lifetime monotonic).  `snapshot()` renders it.
REGISTRY = MetricsRegistry()


def snapshot() -> str:
    """Prometheus text exposition of the process-global registry."""
    return REGISTRY.snapshot()


def federate(root, now: "float | None" = None,
             stale_after: "float | None" = None) -> str:
    """One Prometheus exposition for the whole fleet: merge every
    `store/fleet/<worker>.json` metrics snapshot, each sample labeled
    with its `worker_id`, NEVER summed across workers — two workers'
    counters are two time series, and collapsing them would silently
    launder a dead worker's last value into a live total.

    Staleness honesty: a worker whose snapshot is older than
    `stale_after` (default 3x its own lease TTL) contributes only
    `fleet_worker_stale{worker_id=...} 1` — its metrics are withheld,
    visibly, rather than served as if current."""
    root = Path(root)
    if now is None:
        now = time.time()  # lint: wall-ok(staleness display; ownership truth stays in lease epochs)
    merged: dict = {}      # name -> [kind, [(labels, sample), ...]]

    def add(name, kind, labels, sample):
        ent = merged.setdefault(name, [kind, []])
        if ent[0] == kind:
            ent[1].append((labels, sample))

    for p in sorted((root / "fleet").glob("*.json")):
        try:
            with open(p) as f:
                st = json.load(f)
        except Exception:  # noqa: BLE001 - a torn sidecar is skipped
            continue
        if not isinstance(st, dict) or not st.get("worker"):
            continue
        wid = str(st["worker"])
        age = max(now - float(st.get("updated") or 0.0), 0.0)
        ttl = float(st.get("lease_ttl") or 0.0)
        limit = stale_after if stale_after is not None \
            else (3.0 * ttl if ttl > 0 else 10.0)
        stale = age > limit
        add("fleet_worker_stale", "gauge", {"worker_id": wid},
            {"value": 1.0 if stale else 0.0})
        add("fleet_worker_age_seconds", "gauge", {"worker_id": wid},
            {"value": round(age, 3)})
        if stale:
            continue
        metrics = st.get("metrics")
        if not isinstance(metrics, dict):
            continue
        for name, spec in sorted(metrics.items()):
            if not isinstance(spec, dict):
                continue
            for s in spec.get("samples") or []:
                labels = dict(s.get("labels") or {})
                labels["worker_id"] = wid
                add(name, spec.get("kind"), labels, s)

    out = []
    for name, (kind, rows) in sorted(merged.items()):
        out.append(f"# TYPE {name} {kind}")
        for labels, s in rows:
            lab = ",".join(f'{k}="{_esc(str(v))}"'
                           for k, v in sorted(labels.items()))
            if kind in ("counter", "gauge"):
                v = s.get("value")
                v = float(v) if isinstance(v, (int, float)) else 0.0
                out.append(f"{name}{{{lab}}} {v:g}")
                continue
            buckets = s.get("buckets") or []
            counts = s.get("counts") or []
            acc = 0
            for i, b in enumerate(buckets):
                acc += counts[i] if i < len(counts) else 0
                out.append(
                    f'{name}_bucket{{{lab},le="{float(b):g}"}} {acc}')
            c = s.get("count") or 0
            out.append(f'{name}_bucket{{{lab},le="+Inf"}} {c}')
            out.append(f"{name}_sum{{{lab}}} "
                       f"{float(s.get('sum') or 0.0):g}")
            out.append(f"{name}_count{{{lab}}} {c}")
    return "\n".join(out) + ("\n" if out else "")


# ---------------------------------------------------------------------------
# Crash-safe event log (HistoryWAL framing discipline, store.py:223-273)
# ---------------------------------------------------------------------------

def _payload(ev: dict) -> str:
    return json.dumps(ev, sort_keys=True, separators=(",", ":"),
                      default=repr)


class EventLog:
    """Append-only, CRC-guarded JSONL event log.

    Record framing:  {"i": <seq>, "t": <wall s>, "crc": "<crc32>",
                      "ev": {...}}
    where crc guards the canonical `ev` payload (json, sorted keys,
    compact separators, default=repr) — a reader re-derives it from the
    parsed record alone, exactly like history.HistoryWAL.

    Durability tiers: every append is flushed (SIGKILL-safe — the
    kernel holds flushed bytes regardless of process death); appends
    with `durable=True` are also fsynced (power-loss-safe), reserved
    for state-changing events so the hot op path costs one buffered
    write, not one fsync (the <5% overhead bound, docs/observability.md).

    Never raises after construction: a write failure (disk full, fs
    gone) logs once and disables the log — telemetry must never fail a
    run.

    `resume=True` continues an existing log's sequence instead of
    restarting at 0 (which would break every follow_frames reader at
    the first new record): the intact prefix is scanned, a torn
    trailing line — a writer killed mid-append — is truncated away,
    and appends pick up at the next sequence number.  This is the
    fleet-takeover path: a new lease owner keeps the dead worker's
    live.jsonl timeline readable as ONE log.

    `epoch` (fleet tenant logs) stamps every record with the writer's
    lease epoch (`e` envelope field).  A SIGSTOP-paused worker can
    resume an in-flight append into a log a successor took over —
    after ANY writer-side fence check — so readers fence instead:
    follow_events skips lower-epoch intrusions rather than reading
    them as a tear (see history.follow_frames)."""

    def __init__(self, path, fsync: bool = True, resume: bool = False,
                 epoch: Optional[int] = None):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.fsync = fsync
        self.epoch = epoch
        self.lock = threading.Lock()
        self._n = 0
        self._dead = False
        if resume and self.path.exists() \
                and self.path.stat().st_size:
            try:
                from jepsen_tpu.history import follow_frames
                seg = follow_frames(self.path, key="ev",
                                    epoch_key="e")
                if seg.tail_bytes and not seg.corrupt:
                    with open(self.path, "r+b") as f:
                        f.truncate(seg.offset)
                # a corrupt COMPLETE record is left in place (readers
                # stop there); continuing the sequence past it keeps
                # appends harmless either way
                self._n = seg.seq
            except Exception:  # noqa: BLE001 - resume is best-effort
                pass
        self._f = open(self.path, "a")

    def append(self, ev: dict, durable: bool = False) -> None:
        with self.lock:
            if self._dead:
                return
            try:
                payload = _payload(ev)
                crc = zlib.crc32(payload.encode())
                e = f'"e":{self.epoch},' if self.epoch is not None \
                    else ""
                # lint: wall-ok(advisory envelope stamp; readers order by i/crc, never t)
                t = time.time()
                self._f.write(f'{{"i":{self._n},{e}"t":{t:.6f},'
                              f'"crc":"{crc:08x}","ev":{payload}}}\n')
                self._f.flush()
                if durable and self.fsync:
                    os.fsync(self._f.fileno())
                self._n += 1
            except Exception:
                self._dead = True
                import logging
                logging.getLogger("jepsen").warning(
                    "telemetry event log write failed; continuing "
                    "without telemetry", exc_info=True)

    def close(self) -> None:
        with self.lock:
            self._dead = True
            try:
                self._f.close()
            except Exception:
                pass


import dataclasses as _dataclasses


@_dataclasses.dataclass
class EventSegment:
    """One `follow_events` read: the validated new events plus the
    cursor state to resume from (history.FrameSegment semantics)."""

    events: list
    offset: int
    seq: int
    corrupt: bool = False
    stop_reason: Optional[str] = None
    tail_bytes: int = 0
    epoch: int = 0


def follow_events(path, offset: int = 0, seq: int = 0,
                  max_records: Optional[int] = None,
                  epoch: int = 0) -> EventSegment:
    """Resumable cursor over a (possibly still-being-written) event
    log — the streaming counterpart of `read_events`, sharing
    `history.follow_frames`'s torn-tail contract: only intact complete
    records since `offset` are returned; an incomplete trailing line is
    left unconsumed and re-read whole on the next call; a COMPLETE line
    failing a guard marks the stream `corrupt`.  Records are
    epoch-fenced (`e` envelope field, fleet tenant logs): a stale
    lower-epoch writer's intrusions are skipped and superseded, never
    a sequence break — pass the returned `epoch` back along with
    `offset`/`seq` when streaming.  Each event dict has `t` (wall
    seconds) and `i` (sequence) merged in, like `read_events`."""
    from jepsen_tpu.history import follow_frames
    seg = follow_frames(path, offset, seq, key="ev",
                        max_records=max_records,
                        epoch_key="e", epoch=epoch)
    events = []
    for rec in seg.records:
        ev = dict(rec["ev"])
        ev["t"] = rec.get("t")
        ev["i"] = rec["i"]
        events.append(ev)
    return EventSegment(events, seg.offset, seg.seq, seg.corrupt,
                        seg.stop_reason, seg.tail_bytes, seg.epoch)


def read_events(path) -> list[dict]:
    """Recover the intact prefix of an event log: records in order,
    stopping at the first torn/unparseable line, crc mismatch, or
    same-epoch sequence break (everything past a tear is
    unattributable; a fenced stale writer's epoch-stamped intrusions
    are skipped, not a tear).  Each returned dict is the event payload
    with `t` (wall seconds) and `i` (sequence) merged in.  One
    full-file `follow_events` read."""
    return follow_events(path).events


# ---------------------------------------------------------------------------
# Telemetry: the per-test bundle
# ---------------------------------------------------------------------------

class Telemetry:
    """Metrics + event log for one test (or the disabled no-op).

    The disabled path is one attribute check per call — cheap enough to
    leave the instrumentation unconditional in the worker loop."""

    def __init__(self, enabled: bool = False,
                 log: Optional[EventLog] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.enabled = enabled
        self.log = log
        self.registry = registry if registry is not None else REGISTRY

    # -- events -------------------------------------------------------------

    def event(self, type_: str, durable: bool = False, **fields) -> None:
        if not self.enabled or self.log is None:
            return
        self.log.append({"type": type_, **fields}, durable=durable)

    # -- run-phase instrumentation hooks ------------------------------------

    def record_op(self, f, node, outcome: str, t_invoke_ns,
                  t_complete_ns, process=None) -> None:
        """One completed client op: latency histogram keyed
        (f, node, outcome) + one non-durable event."""
        if not self.enabled:
            return
        lat_ns = (t_complete_ns - t_invoke_ns) \
            if (t_invoke_ns is not None and t_complete_ns is not None) \
            else None
        if lat_ns is not None:
            self.registry.histogram(
                "jepsen_op_latency_seconds",
                f=str(f), node=str(node), outcome=str(outcome),
            ).observe(lat_ns / 1e9)
        if self.log is not None:
            self.log.append({"type": "op", "f": str(f), "node": str(node),
                             "outcome": str(outcome), "process": process,
                             "time": t_invoke_ns, "latency_ns": lat_ns})

    def observe_wal_fsync(self, seconds: float) -> None:
        if not self.enabled:
            return
        self.registry.histogram("jepsen_wal_fsync_seconds").observe(
            seconds)

    def metrics_event(self) -> None:
        """Dump the registry into the event log (run save points), so
        the log alone carries the aggregate op-latency metrics even
        when nobody scrapes /metrics."""
        if not self.enabled or self.log is None:
            return
        self.log.append({"type": "metrics",
                         "snapshot": self.registry.snapshot()},
                        durable=True)

    def close(self) -> None:
        if self.log is not None:
            self.log.close()


NOOP = Telemetry(enabled=False)


def for_test(test) -> Telemetry:
    """Build the test's telemetry: enabled for named tests (the store
    dir anchors the event log) unless test['telemetry'] is False;
    disabled otherwise.  Always-on by design (Dapper): the enabled-path
    overhead is bounded and measured (tests/test_telemetry.py)."""
    if test.get("telemetry") is False:
        return NOOP
    if isinstance(test.get("telemetry"), Telemetry):
        return test["telemetry"]
    if not (test.get("name") and test.get("start-time")):
        return NOOP
    from jepsen_tpu import store
    return Telemetry(enabled=True,
                     log=EventLog(store.make_path(test,
                                                  "telemetry.jsonl")))


def of(test) -> Telemetry:
    """The test's telemetry if one is attached, else the no-op."""
    t = (test or {}).get("telemetry")
    return t if isinstance(t, Telemetry) else NOOP


# Active-run scope: code with no test in reach (circuit breakers,
# engine dispatch, the resilient runner) emits through the active
# telemetry, set by core.run for the duration of the run+analysis.
_active_lock = threading.Lock()
_active: Optional[Telemetry] = None


def set_active(t: Telemetry) -> None:
    global _active
    with _active_lock:
        _active = t if t is not None and t.enabled else None


def clear_active(t: Optional[Telemetry] = None) -> None:
    global _active
    with _active_lock:
        if t is None or _active is t:
            _active = None


def active() -> Optional[Telemetry]:
    return _active


def emit(type_: str, durable: bool = False, **fields) -> None:
    """Emit an event into the active run's log (no-op when no run is
    active — the cheap guard engines rely on)."""
    t = _active
    if t is not None:
        t.event(type_, durable=durable, **fields)


# ---------------------------------------------------------------------------
# Cross-cutting emitters (ledger, breaker, watchdog)
# ---------------------------------------------------------------------------

def fault_window(phase: str, key, desc=None, healed: bool = False,
                 tele: Optional[Telemetry] = None) -> None:
    """A fault-window edge: phase is 'start' or 'stop'.  Counted in the
    registry and journaled durably (checker timelines and the
    /telemetry dashboard overlay these windows on the op stream)."""
    t = tele if (tele is not None and tele.enabled) else _active
    REGISTRY.counter("jepsen_fault_windows_total", phase=phase).inc()
    if t is not None:
        ev = {"key": repr(key)}
        if phase == "start":
            ev["desc"] = desc if isinstance(
                desc, (str, int, float, list, dict, type(None))) \
                else repr(desc)
        else:
            ev["healed"] = bool(healed)
        t.event(f"fault-{phase}", durable=True, **ev)


def breaker_transition(node, to: str, failures: int) -> None:
    """A circuit-breaker state transition (reconnect.CircuitBreaker)."""
    REGISTRY.counter("jepsen_breaker_transitions_total",
                     node=str(node), to=to).inc()
    emit("breaker", durable=True, node=str(node), to=to,
         failures=failures)


def count_fallback(engine: str, reason: str = "unsupported") -> None:
    """A fallback-ladder rung was taken: a typed engine error was
    absorbed and a lower tier will produce the verdict.  The bare-
    fallback lint rule (ISSUE 15) requires every such handler to leave
    this trace (or re-raise) so silent degradation shows up in
    `jepsen_engine_fallback_total` instead of hiding in a green
    suite."""
    REGISTRY.counter("jepsen_engine_fallback_total",
                     engine=str(engine), reason=str(reason)).inc()


def count_lint(rule: str, kind: str = "finding") -> None:
    """One lint finding/waiver, counted per rule into
    `jepsen_lint_total{rule=,kind=}` (scraped at /metrics and rolled
    into the tier-1 CI artifact's lint row)."""
    REGISTRY.counter("jepsen_lint_total", rule=str(rule),
                     kind=str(kind)).inc()


# ---------------------------------------------------------------------------
# Dispatch records (analysis phase)
# ---------------------------------------------------------------------------

def env_overrides() -> dict:
    """The JEPSEN_TPU_* env knobs in effect — the 'why did dispatch go
    this way' record every verdict carries."""
    return {k: os.environ[k] for k in sorted(os.environ)
            if k.startswith("JEPSEN_TPU_")}


def dispatch_record(engine: str, *, why: Optional[str] = None,
                    fallback_chain=(), R=None, crashes=None,
                    batch=None, mesh=None, **extra) -> dict:
    """The inspectable dispatch record attached to verdict metadata:
    which engine, why, what it would fall back to, and the env knobs
    that steered it."""
    rec: dict = {"engine": engine, "env": env_overrides()}
    if why is not None:
        rec["why"] = why
    if fallback_chain:
        rec["fallback_chain"] = list(fallback_chain)
    if R is not None:
        rec["R"] = int(R)
    if crashes is not None:
        rec["crashes"] = int(crashes)
    if batch is not None:
        rec["batch"] = int(batch)
    if mesh is not None:
        rec["mesh"] = str(mesh)
    for k, v in extra.items():
        if v is not None:
            rec[k] = v
    return rec


def attach_dispatch(results, record: dict,
                    stages: Optional[dict] = None, *,
                    count_stages: bool = True) -> dict:
    """Attach one dispatch record (and optional per-stage host-second
    decomposition) to every verdict dict in `results` that lacks one,
    record the engine mix + stage seconds in the registry, and emit a
    `dispatch` event into the active run's log.  A call that attaches
    one stage dict to several engines' verdicts counts its seconds on
    the first attach only (`count_stages=False` on the rest).  Returns
    the record."""
    st = None
    _counts = ("wire_bytes", "overlap_chunks")   # ints, not seconds
    if stages:
        st = {k: round(float(v), 6) for k, v in stages.items()
              if isinstance(v, (int, float)) and k not in _counts}
        for k in _counts:
            if k in stages:
                st[k] = int(stages[k])
    n = 0
    for r in results if isinstance(results, (list, tuple)) else [results]:
        if isinstance(r, dict) and "dispatch" not in r:
            r["dispatch"] = record
            if st is not None and "stages" not in r:
                r["stages"] = st
            n += 1
    REGISTRY.counter("jepsen_engine_dispatch_total",
                     engine=record["engine"]).inc(max(n, 1))
    if st and count_stages:
        for k, v in st.items():
            if k not in _counts:
                REGISTRY.counter(STAGE_SECONDS,
                                 engine=record["engine"], stage=k).inc(v)
    if _active is not None:
        # the log's stage sums (summarize) count a call once too
        emit("dispatch", record=record,
             stages=st if count_stages else None, verdicts=n)
    return record


# ---------------------------------------------------------------------------
# Stage spans: one primitive for stage seconds and profiler annotations
# ---------------------------------------------------------------------------

STAGE_SECONDS = "jepsen_stage_seconds_total"      # {engine, stage}
CHECKER_SECONDS = "jepsen_checker_seconds_total"  # {checker, stage}
SCAN_KEYS = "jepsen_scan_keys_total"              # {path}
SPLIT_KEYS = "jepsen_split_keys_total"            # {path}
SPLIT_OPS_BUILT = "jepsen_split_ops_built_total"
DEVICE_CALLS = "jepsen_device_calls_total"        # {engine}
LANES = "jepsen_lanes_total"                      # {engine, kind}
WIRE_BYTES = "jepsen_wire_bytes_total"            # {engine, direction}
ELLE_ROUNDS = "jepsen_elle_closure_rounds_total"  # {n_pad}
ELLE_INFER_READS = "jepsen_elle_infer_reads_total"  # {path}

# the innermost open counted span of this thread
_open = threading.local()


def _annotation(name: str):
    """`jax.profiler.TraceAnnotation("jepsen.<name>")`, or None when JAX
    is not loaded (the run side imports this module without it)."""
    prof = sys.modules.get("jax.profiler")
    return None if prof is None else prof.TraceAnnotation("jepsen." + name)


class _Span:
    __slots__ = ("_seconds", "_name", "_ann", "_t0", "_child", "_parent")

    def __init__(self, seconds: dict, name: str):
        self._seconds = seconds
        self._name = name

    def __enter__(self):
        self._ann = _annotation(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        self._parent = getattr(_open, "span", None)
        _open.span = self
        self._child = 0.0
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        _open.span = self._parent
        if self._parent is not None:
            self._parent._child += dur
        self._seconds[self._name] = (self._seconds.get(self._name, 0.0)
                                     + dur - self._child)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Stages:
    """One call's stage seconds and work counts.

    `span(name)` times a block once on the monotonic clock and adds its
    SELF seconds to `seconds[name]`: the duration less every counted
    span that opened inside it on this thread, whichever Stages that
    one belongs to.  So no interval lands under two labels, and the
    labels of a call add up to at most its wall time.  The same
    interval is a `jepsen.<name>` annotation on the profiler's host
    plane, on the clock the device planes use.  `seconds` may be a
    dict the caller already holds (an engine's `stats`).

    `tally(metric, n, **labels)` adds to a local count; `flush` moves
    the tallies (and, given a metric name, the stage seconds) into the
    registry — once per call, never from a per-key or per-op loop."""

    __slots__ = ("seconds", "_counts")

    def __init__(self, seconds: Optional[dict] = None):
        self.seconds = {} if seconds is None else seconds
        self._counts: dict = {}

    def span(self, name: str) -> _Span:
        return _Span(self.seconds, name)

    def tally(self, metric: str, n: float = 1, **labels) -> None:
        key = (metric, tuple(sorted(labels.items())))
        self._counts[key] = self._counts.get(key, 0) + n

    def flush(self, metric: Optional[str] = None, **labels) -> None:
        for (name, key), n in self._counts.items():
            if n:
                REGISTRY.counter_for(name, key).inc(n)
        self._counts.clear()
        if metric is not None:
            for k, v in self.seconds.items():
                if isinstance(v, float):
                    REGISTRY.counter_for(metric, tuple(sorted(
                        {**labels, "stage": k}.items()))).inc(v)


def trace(name: str):
    """An annotation only (`jepsen.<name>`, no counter): a part of a
    counted span that the trace should show on its own, such as the
    put, call and fetch inside `kernel`.  Its time stays in the
    enclosing span's self seconds."""
    ann = _annotation(name)
    return contextlib.nullcontext() if ann is None else ann


@contextlib.contextmanager
def checker_stage(checker: str, stage: str):
    """One checker-front span counted on its own into
    `jepsen_checker_seconds_total{checker, stage}` as it closes (a
    witness replay inside an engine)."""
    st = Stages()
    with st.span(stage):
        yield
    st.flush(CHECKER_SECONDS, checker=checker)


# ---------------------------------------------------------------------------
# Log summarization (the `cli metrics` subcommand)
# ---------------------------------------------------------------------------

def _q(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(int(q * len(sorted_vals)),
                           len(sorted_vals) - 1)]


def pair_fault_windows(events: list[dict]) -> list[tuple]:
    """(key, t_start, t_stop|None) triples from fault-start/stop
    events, pairing each stop with the most recent open start of the
    same key."""
    open_: dict = {}
    out = []
    for ev in events:
        if ev.get("type") == "fault-start":
            open_.setdefault(ev.get("key"), []).append(ev)
        elif ev.get("type") == "fault-stop":
            starts = open_.get(ev.get("key"))
            if starts:
                s = starts.pop()
                out.append((ev.get("key"), s.get("t"), ev.get("t")))
            else:
                out.append((ev.get("key"), None, ev.get("t")))
    for key, starts in open_.items():
        for s in starts:
            out.append((key, s.get("t"), None))
    out.sort(key=lambda w: (w[1] if w[1] is not None else
                            (w[2] or 0.0)))
    return out


def summarize(events: list[dict]) -> str:
    """Human-readable summary of one telemetry log: op volume + top
    latencies, engine mix + stage decomposition, fault windows, breaker
    transitions, runner resilience counters."""
    ops = [e for e in events if e.get("type") == "op"]
    lines = [f"telemetry: {len(events)} events"]

    # -- ops ---------------------------------------------------------------
    by_key: dict = {}
    for e in ops:
        k = (e.get("f"), e.get("node"), e.get("outcome"))
        if e.get("latency_ns") is not None:
            by_key.setdefault(k, []).append(e["latency_ns"] / 1e6)
    lines.append(f"ops: {len(ops)} completed")
    rows = []
    for (f, node, outcome), lats in by_key.items():
        lats.sort()
        rows.append((f, node, outcome, len(lats), _q(lats, 0.5),
                     _q(lats, 0.95), lats[-1]))
    rows.sort(key=lambda r: -r[5])            # slowest p95 first
    for f, node, outcome, n, p50, p95, mx in rows[:12]:
        lines.append(f"  {f}@{node} {outcome}: n={n} "
                     f"p50={p50:.2f}ms p95={p95:.2f}ms max={mx:.2f}ms")
    if len(rows) > 12:
        lines.append(f"  ... {len(rows) - 12} more (f, node, outcome) "
                     "series")

    # -- engine mix --------------------------------------------------------
    dispatches = [e for e in events if e.get("type") == "dispatch"]
    mix: dict = {}
    stages_acc: dict = {}
    for e in dispatches:
        rec = e.get("record") or {}
        mix[rec.get("engine")] = mix.get(rec.get("engine"), 0) \
            + (e.get("verdicts") or 1)
        for k, v in (e.get("stages") or {}).items():
            if k not in ("wire_bytes", "overlap_chunks") \
                    and isinstance(v, (int, float)):
                stages_acc[k] = stages_acc.get(k, 0.0) + v
    if mix:
        lines.append("engine mix: " + ", ".join(
            f"{eng}={n}" for eng, n in
            sorted(mix.items(), key=lambda kv: -kv[1])))
    if stages_acc:
        lines.append("stage seconds: " + " ".join(
            f"{k}={v:.3f}" for k, v in sorted(stages_acc.items())))

    # -- dispatch plans (ISSUE 8): the planner-emitted why + fallback
    # chain behind each distinct routing decision, rendered verbatim —
    # not the opaque engine-name list the pre-planner records carried
    plans: dict = {}
    for e in dispatches:
        rec = e.get("record") or {}
        key = (rec.get("engine"), rec.get("why"),
               tuple(rec.get("fallback_chain") or ()))
        pl = dict(rec.get("plan") or {})
        # the record-level pack fields are what ACTUALLY ran (the plan
        # carries the intent) — surface the actual when present
        if rec.get("pack_backend") is not None:
            pl["pack_backend"] = rec["pack_backend"]
            pl["pack_threads"] = rec.get("pack_threads")
        plans.setdefault(key, pl)
    shown = [(k, v) for k, v in plans.items() if k[1] or k[2]]
    if shown:
        lines.append("dispatch plans:")
        for (eng, why, fb), pl in shown[:12]:
            chain = " -> ".join((eng,) + fb) if fb else (eng or "?")
            pack = ""
            if pl.get("pack_backend"):
                pack = (f" [pack={pl['pack_backend']}"
                        + (f" x{pl['pack_threads']}"
                           if pl.get("pack_threads") else "") + "]")
            deep = ""
            if pl.get("deep_variant"):
                # mask-plane provenance (ISSUE 10): which deep variant
                # and over how many shards / exchanges per round
                deep = (f" [{pl['deep_variant']}"
                        + (f" x{pl['shards']}" if pl.get("shards")
                           else "")
                        + (f" ex{pl['exchange_rounds']}"
                           if pl.get("exchange_rounds") else "") + "]")
            lines.append(f"  {chain}: {why or '?'}{pack}{deep}")
        if len(shown) > 12:
            lines.append(f"  ... {len(shown) - 12} more plans")

    # -- fault windows -----------------------------------------------------
    windows = pair_fault_windows(events)
    if windows:
        lines.append(f"fault windows: {len(windows)}")
        for key, t0, t1 in windows[:10]:
            dur = f"{t1 - t0:.2f}s" if (t0 is not None and
                                        t1 is not None) else "open"
            lines.append(f"  {key}: {dur}")

    # -- breakers / watchdog / runner --------------------------------------
    br = [e for e in events if e.get("type") == "breaker"]
    if br:
        lines.append("breaker transitions: " + ", ".join(
            f"{e.get('node')}->{e.get('to')}" for e in br[:10]))
    stalls = sum(1 for e in events if e.get("type") == "watchdog-stall")
    if stalls:
        lines.append(f"watchdog stalls: {stalls}")
    leaks = [e for e in events if e.get("type") == "campaign-leak"]
    if leaks:
        lines.append(
            f"campaign leaks: {len(leaks)} (faults that survived a "
            "schedule and were backstop-healed): "
            + "; ".join(", ".join(e.get("keys") or [])
                        for e in leaks[:5]))
    rn = [e for e in events if e.get("type") == "runner"]
    for e in rn:
        lines.append(
            "runner: "
            f"oom_bisections={e.get('oom_bisections', 0)} "
            f"retries={e.get('retries', 0)} "
            f"quarantines={e.get('quarantines', 0)} "
            f"cpu_fallbacks={e.get('cpu_fallbacks', 0)}")
    spans = sum(1 for e in events if e.get("type") == "span")
    if spans:
        lines.append(f"trace spans: {spans}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Time-series extraction (the /telemetry dashboard)
# ---------------------------------------------------------------------------

def op_series(events: list[dict], n_buckets: int = 100) -> dict:
    """Bucket op events over wall time for sparkline rendering:
    {"t0", "t1", "rate": [ops/s per bucket], "p95_ms": [...],
     "windows": [(frac_start, frac_stop), ...]}.  Fractions are
    positions in [0, 1] across the [t0, t1] span (None-edged windows
    clamp to the span)."""
    ops = [e for e in events if e.get("type") == "op"
           and e.get("t") is not None]
    if not ops:
        return {"t0": 0.0, "t1": 0.0, "rate": [], "p95_ms": [],
                "windows": []}
    ts = [e["t"] for e in ops]
    t0, t1 = min(ts), max(ts)
    span = max(t1 - t0, 1e-9)
    width = span / n_buckets
    counts = [0] * n_buckets
    lats: list = [[] for _ in range(n_buckets)]
    for e in ops:
        b = min(int((e["t"] - t0) / span * n_buckets), n_buckets - 1)
        counts[b] += 1
        if e.get("latency_ns") is not None:
            lats[b].append(e["latency_ns"] / 1e6)
    p95 = []
    for chunk in lats:
        chunk.sort()
        p95.append(_q(chunk, 0.95))
    windows = []
    for _key, ws, we in pair_fault_windows(events):
        a = 0.0 if ws is None else min(max((ws - t0) / span, 0.0), 1.0)
        b = 1.0 if we is None else min(max((we - t0) / span, 0.0), 1.0)
        if b > a:
            windows.append((a, b))
    return {"t0": t0, "t1": t1,
            "rate": [c / width for c in counts],
            "p95_ms": p95, "windows": windows}
