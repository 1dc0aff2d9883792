"""Checkers: validate that a history is correct.

Mirrors the reference's `jepsen/src/jepsen/checker.clj` — the `Checker`
protocol (:49-69), `check-safe` (:77), the `merge-valid` priority lattice
(:26-47), `compose` (:90), and all twelve built-in checkers — with the
heavy set algebra running as JAX kernels (`jepsen_tpu.ops.fold`) when
values are integers, and the linearizability checker delegating to the
TPU WGL frontier search (`jepsen_tpu.ops.wgl`) instead of knossos.

Every checker returns a dict with at least a `"valid?"` key whose value
is True, False, or "unknown".
"""

from __future__ import annotations

import threading
import traceback
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

from jepsen_tpu import telemetry
from jepsen_tpu.history import History, Op

UNKNOWN = "unknown"

# checker.clj:26-31 — larger numbers dominate when checkers compose.
VALID_PRIORITIES = {True: 0, False: 1, UNKNOWN: 0.5}


def merge_valid(valids) -> Any:
    """Merge n valid? values, yielding the highest-priority one
    (checker.clj:33-47)."""
    out = True
    for v in valids:
        if v not in VALID_PRIORITIES:
            raise ValueError(f"{v!r} is not a known valid? value")
        if VALID_PRIORITIES[out] < VALID_PRIORITIES[v]:
            out = v
    return out


class Checker:
    """checker.clj:49-69.  `test` is the test map (may be None for pure
    checkers); `opts` carries e.g. :subdirectory for artifact output."""

    def check(self, test, history, opts=None) -> dict:
        raise NotImplementedError


def check_safe(checker, test, history, opts=None) -> dict:
    """checker.clj:77-88: wrap checker exceptions into
    {'valid?': 'unknown', 'error': ...}."""
    try:
        return checker.check(test, history, opts or {})
    except Exception:
        return {"valid?": UNKNOWN, "error": traceback.format_exc()}


class Noop(Checker):
    def check(self, test, history, opts=None):
        return None


def noop():
    return Noop()


class UnbridledOptimism(Checker):
    """Everything is awesoooommmmme! (checker.clj:120-124)"""

    def check(self, test, history, opts=None):
        return {"valid?": True}


def unbridled_optimism():
    return UnbridledOptimism()


class Compose(Checker):
    """checker.clj:90-102: run a map of checkers in parallel; result map
    plus a merged top-level valid?."""

    def __init__(self, checker_map: dict):
        self.checker_map = dict(checker_map)

    def check(self, test, history, opts=None):
        if not self.checker_map:
            return {"valid?": True}
        with ThreadPoolExecutor(max_workers=len(self.checker_map)) as ex:
            futs = {k: ex.submit(check_safe, c, test, history, opts)
                    for k, c in self.checker_map.items()}
            results = {k: f.result() for k, f in futs.items()}
        out: dict = dict(results)
        out["valid?"] = merge_valid(
            r["valid?"] for r in results.values() if r is not None)
        return out


def compose(checker_map: dict) -> Checker:
    return Compose(checker_map)


class ConcurrencyLimit(Checker):
    """checker.clj:104-119: bound concurrent executions of a memory-heavy
    checker."""

    def __init__(self, limit: int, checker: Checker):
        self.sem = threading.Semaphore(limit)
        self.checker = checker

    def check(self, test, history, opts=None):
        with self.sem:
            return self.checker.check(test, history, opts)


def concurrency_limit(limit: int, checker: Checker) -> Checker:
    return ConcurrencyLimit(limit, checker)


# ---------------------------------------------------------------------------
# Linearizability — delegates to the TPU WGL kernel (ops/wgl.py) or the
# CPU oracle (ops/wgl_cpu.py); replaces knossos (checker.clj:127-158).
# ---------------------------------------------------------------------------

class Linearizable(Checker):
    """algorithm: 'auto' uses the device kernel when the model provides
    a DeviceSpec and falls back to the CPU oracle; 'device'/'cpu' force
    one; 'competition' races the device chain against the CPU oracle in
    parallel and takes the first finisher — the reference's default
    knossos mode (checker.clj:141-145 delegates to
    knossos.competition/analysis, which races :linear and :wgl the same
    way).  The losing CPU oracle is cancelled via an event, like
    knossos cancelling the losing future; a losing device kernel runs
    its (frontier-bounded) program to completion."""

    def __init__(self, model=None, algorithm: str = "auto", **kw):
        if model is None:
            raise ValueError(
                "The linearizable checker requires a model. It received: "
                "None instead.")
        self.model = model
        self.algorithm = algorithm
        self.kw = kw

    _SEG_KEYS = ("max_states", "max_open_bits", "localize",
                 "target_returns_per_segment")
    # Resilience options consumed by ops.runner.ResilientRunner on the
    # batched path (check_many); scalar check() ignores them.
    _RUNNER_KEYS = ("deadline_s", "max_retries", "checkpoint_dir")

    def _device_check(self, history):
        from jepsen_tpu.ops import wgl, wgl_seg

        seg_keys = self._SEG_KEYS
        ser_keys = ("frontier_sizes", "pad")
        unknown = (set(self.kw) - set(seg_keys) - set(ser_keys)
                   - set(self._CPU_KEYS) - set(self._RUNNER_KEYS))
        if unknown:
            raise TypeError(
                f"unknown linearizable checker option(s): "
                f"{sorted(unknown)}")
        seg_kw = {k: v for k, v in self.kw.items() if k in seg_keys}
        ser_kw = {k: v for k, v in self.kw.items() if k in ser_keys}
        # Fastest engine first: the segment-parallel transfer-matrix
        # kernel, then the serial frontier kernel for everything else.
        try:
            return wgl_seg.check(self.model, history, **seg_kw)
        except wgl_seg.Unsupported:
            from jepsen_tpu import telemetry
            telemetry.count_fallback("wgl_seg", "serial-frontier")
            return wgl.check(self.model, history, **ser_kw)

    _CPU_KEYS = ("max_configs", "time_limit")

    def _competition(self, history):
        """Race device vs CPU; first result wins (competition mode).
        The losing CPU oracle is cancelled via its `cancel` event (the
        device kernel cannot be interrupted mid-XLA-program, but its
        runtime is bounded by the frontier caps)."""
        import queue as queue_mod
        import threading

        from jepsen_tpu.ops import wgl_cpu

        out: queue_mod.Queue = queue_mod.Queue()
        cancel = threading.Event()
        cpu_kw = {k: v for k, v in self.kw.items() if k in self._CPU_KEYS}

        def run(name, f):
            try:
                out.put((name, f()))
            except Exception as e:  # noqa: BLE001 - loser may also fail
                out.put((name, e))

        racers = {
            "device": lambda: self._device_check(history),
            "cpu": lambda: wgl_cpu.check(self.model, history,
                                         cancel=cancel, **cpu_kw),
        }
        for name, f in racers.items():
            threading.Thread(target=run, args=(name, f),
                             daemon=True, name=f"linear-{name}").start()
        # Only a DEFINITIVE verdict (true/false) wins the race: an
        # :unknown from a racer that hit config-explosion or its
        # time_limit must not beat the still-running other racer, or
        # competition would be strictly worse than auto on exactly the
        # hard histories it targets.  Indefinite results and errors are
        # held as fallbacks until both racers have reported.
        indefinite = []
        errors = []
        for _ in racers:
            name, res = out.get()
            if isinstance(res, Exception):
                errors.append((name, res))
                continue
            if res.get("valid?") in ("cancelled", "unknown"):
                indefinite.append((name, res))
                continue
            winner = dict(res)
            winner["competition-winner"] = name
            cancel.set()
            return winner
        for name, res in indefinite:
            if res.get("valid?") == "unknown":
                winner = dict(res)
                winner["competition-winner"] = name
                return winner
        # Both racers failed: surface BOTH messages, chaining the first
        # failure as __cause__ so neither is silently dropped.
        (n1, e1), *rest = errors
        if rest:
            n2, e2 = rest[0]
            raise RuntimeError(
                f"both competition racers failed: {n1}: {e1!r}; "
                f"{n2}: {e2!r}") from e1
        raise e1

    def check_many(self, test, histories) -> list:
        """Batched re-check of MANY whole histories (the `analyze
        --all` path): device-eligible models ride ONE pipelined pass
        (wgl_seg.check_pipeline — grouped transfers, one verdict
        fetch, per-history fallbacks for out-of-scope entries),
        executed through ops.runner.ResilientRunner so a device OOM
        bisects instead of aborting, a corrupt history is quarantined
        with a structured verdict, `deadline_s` degrades the tail to
        the capped CPU oracle, and `checkpoint_dir` makes the sweep
        resumable.  Everything else loops the scalar check.
        Verdict-identical to per-history check() on healthy
        histories either way."""
        spec = self.model.device_spec()
        algo = self.algorithm
        if algo == "auto":
            algo = "device" if spec is not None else "cpu"
        runner_kw = {k: v for k, v in self.kw.items()
                     if k in self._RUNNER_KEYS}
        seg_kw = {k: v for k, v in self.kw.items()
                  if k in self._SEG_KEYS}
        if algo == "device" and spec is not None \
                and set(self.kw) <= (set(self._SEG_KEYS)
                                     | set(self._RUNNER_KEYS)):
            from jepsen_tpu.ops import runner as runner_mod
            return runner_mod.ResilientRunner(
                engine="seg_pipeline", engine_kwargs=seg_kw,
            ).check(self.model, histories, **runner_kw)
        return [self.check(test, h) for h in histories]

    def check(self, test, history, opts=None):
        """The verdict of the configured engine, then the front's own
        stages — the `final_paths` rebuild and `assemble` — as spans
        counted into
        `jepsen_checker_seconds_total{checker="linearizable"}`."""
        from jepsen_tpu.ops import wgl_cpu

        front = telemetry.Stages()
        algo = self.algorithm
        spec = self.model.device_spec()
        if algo == "auto":
            algo = "device" if spec is not None else "cpu"
        if algo == "competition":
            a = self._competition(history)
        elif algo == "device":
            a = self._device_check(history)
        elif algo == "cpu":
            a = wgl_cpu.check(self.model, history,
                              **{k: v for k, v in self.kw.items()
                                 if k not in self._RUNNER_KEYS})
        else:
            raise ValueError(f"unknown algorithm {algo!r}")
        if (a.get("valid?") is False and "final-paths" not in a
                and "final-paths-error" not in a
                and a.get("op_index") is not None):
            with front.span("final_paths"):
                # Analysis-artifact parity (checker.clj:155-158):
                # device verdicts localize a witness but carry no
                # configs or final-paths; reconstruct both from the CPU
                # oracle on the prefix through the witness, under the
                # artifact caps: the oracle is exponential in crashed
                # calls, and the verdict is already known invalid.  An
                # engine whose own capped attempt ran out says so in
                # final-paths-error.
                try:
                    # The prefix must include the witness's COMPLETION:
                    # cut at its invocation and prepare() treats it as
                    # crashed (linearizable by omission), yielding a
                    # bogus valid analysis (cf. wgl_seg's cutoff at
                    # completion.index).
                    hist = History(history)
                    wit = next((o for o in hist
                                if o.index == a["op_index"]), None)
                    cutoff = a["op_index"]
                    if wit is not None:
                        for o in hist:
                            if (o.index is not None
                                    and o.index > a["op_index"]
                                    and o.process == wit.process
                                    and not o.is_invoke):
                                cutoff = o.index
                                break
                    prefix = History(
                        [o for o in hist
                         if o.index is not None and o.index <= cutoff])
                    oracle = wgl_cpu.check(self.model, prefix,
                                           **wgl_cpu.ARTIFACT_CAPS)
                    if oracle.get("valid?") is not False:
                        a["final-paths-error"] = \
                            wgl_cpu.artifacts_error(oracle)
                    else:
                        for key in ("configs", "final-paths"):
                            if key in oracle and key not in a:
                                a[key] = oracle[key]
                except Exception as e:      # noqa: BLE001
                    a["final-paths-error"] = str(e)
        with front.span("assemble"):
            # Truncation parity (checker.clj:155-158): writing full
            # configs "can take *hours*".  The config-explosion verdict
            # sets 'configs' to a COUNT, not a list — only slice lists.
            if isinstance(a.get("configs"), list):
                a["configs"] = a["configs"][:10]
            if isinstance(a.get("final-paths"), list):
                a["final-paths"] = a["final-paths"][:10]
            if a.get("valid?") is False:
                # checker.clj:147-154: render the failing window as
                # linear.svg in the store dir.  Rendering must never
                # fail the check itself.
                try:
                    from jepsen_tpu.checker import linear_report
                    p = linear_report.write_to_store(test, history, a,
                                                     opts)
                    if p:
                        a["linear-svg"] = p
                except Exception as e:      # noqa: BLE001
                    a["linear-svg-error"] = str(e)
        front.flush(telemetry.CHECKER_SECONDS, checker="linearizable")
        return a


def linearizable(opts_or_model=None, **kw) -> Checker:
    """Accepts linearizable({'model': m, 'algorithm': ...}) like the
    reference (checker.clj:127), or linearizable(model, ...)."""
    if isinstance(opts_or_model, dict):
        o = dict(opts_or_model)
        return Linearizable(o.pop("model", None), o.pop("algorithm", "auto"),
                            **o, **kw)
    return Linearizable(opts_or_model, **kw)


# ---------------------------------------------------------------------------
# Queue (model-reduction) — checker.clj:160-180
# ---------------------------------------------------------------------------

class Queue(Checker):
    """Every dequeue must come from somewhere: assume every non-failing
    enqueue succeeded and only ok dequeues happened; reduce the model."""

    def __init__(self, model):
        self.model = model

    def check(self, test, history, opts=None):
        from jepsen_tpu.models import is_inconsistent

        m = self.model
        for o in History(history):
            if (o.f == "enqueue" and o.is_invoke) or \
                    (o.f == "dequeue" and o.is_ok):
                if m is None:
                    continue
                m = m.step(o)
                if is_inconsistent(m):
                    return {"valid?": False, "error": m.msg}
        return {"valid?": True, "final-queue": m}


def queue(model):
    return Queue(model)


# ---------------------------------------------------------------------------
# Set — checker.clj:182-233
# ---------------------------------------------------------------------------

def integer_interval_set_str(xs) -> str:
    """Compact sorted representation: #{1..3 5} (util.clj:528-553)."""
    xs = sorted(xs)
    if any(not isinstance(x, int) or isinstance(x, bool) for x in xs):
        return "#{" + " ".join(str(x) for x in xs) + "}"
    runs = []
    start = end = None
    for cur in xs:
        if start is None:
            start = end = cur
        elif cur == end + 1:
            end = cur
        else:
            runs.append((start, end))
            start = end = cur
    if start is not None:
        runs.append((start, end))
    return "#{" + " ".join(
        str(s) if s == e else f"{s}..{e}" for s, e in runs) + "}"


class Set(Checker):
    """Adds followed by a final read: every acknowledged add must be
    present, nothing unattempted may appear.  Large integer histories run
    the membership algebra on device (ops/fold.py)."""

    DEVICE_THRESHOLD = 4096

    def check(self, test, history, opts=None):
        attempts, adds, final_read = [], [], None
        for o in History(history):
            if o.f == "add" and o.is_invoke:
                attempts.append(o.value)
            elif o.f == "add" and o.is_ok:
                adds.append(o.value)
            elif o.f == "read" and o.is_ok:
                final_read = o.value
        if final_read is None:
            return {"valid?": UNKNOWN, "error": "Set was never read"}

        final_read = list(set(final_read))
        from jepsen_tpu.ops import fold

        if (fold.all_ints(attempts) and fold.all_ints(adds)
                and fold.all_ints(final_read)
                and len(attempts) + len(final_read) >= self.DEVICE_THRESHOLD):
            ok_m, unexpected_m, lost_m, recovered_m = fold.set_masks(
                attempts, adds, final_read)
            ok = {v for v, m in zip(final_read, ok_m) if m}
            unexpected = {v for v, m in zip(final_read, unexpected_m) if m}
            lost = {v for v, m in zip(adds, lost_m) if m}
            recovered = {v for v, m in zip(final_read, recovered_m) if m}
        else:
            attempts_s, adds_s, read_s = \
                set(attempts), set(adds), set(final_read)
            ok = read_s & attempts_s
            unexpected = read_s - attempts_s
            lost = adds_s - read_s
            recovered = ok - adds_s

        return {
            "valid?": not lost and not unexpected,
            "attempt-count": len(attempts),
            "acknowledged-count": len(adds),
            "ok-count": len(ok),
            "lost-count": len(lost),
            "recovered-count": len(recovered),
            "unexpected-count": len(unexpected),
            "ok": integer_interval_set_str(ok),
            "lost": integer_interval_set_str(lost),
            "unexpected": integer_interval_set_str(unexpected),
            "recovered": integer_interval_set_str(recovered),
        }


def set_checker():
    return Set()


# ---------------------------------------------------------------------------
# Set-full — checker.clj:364-533
# ---------------------------------------------------------------------------

class _SetFullElement:
    """Per-element timeline state (checker.clj SetFullElement :255-282)."""

    __slots__ = ("element", "known", "last_present", "last_absent")

    def __init__(self, element):
        self.element = element
        self.known: Optional[Op] = None
        self.last_present: Optional[Op] = None
        self.last_absent: Optional[Op] = None

    def add(self, op: Op):
        if op.is_ok and self.known is None:
            self.known = op

    def read_present(self, inv: Op, op: Op):
        if self.known is None:
            self.known = op
        if self.last_present is None or \
                self.last_present.index < inv.index:
            self.last_present = inv

    def read_absent(self, inv: Op, op: Op):
        if self.last_absent is None or self.last_absent.index < inv.index:
            self.last_absent = inv

    def results(self) -> dict:
        def idx(o, default=-1):
            return o.index if o is not None else default

        stable = self.last_present is not None and \
            idx(self.last_absent) < idx(self.last_present)
        lost = (self.known is not None and self.last_absent is not None
                and idx(self.last_present) < idx(self.last_absent)
                and idx(self.known) < idx(self.last_absent))
        never_read = not (stable or lost)
        known_time = self.known.time if self.known is not None else None
        stable_time = ((self.last_absent.time + 1)
                       if stable and self.last_absent is not None else
                       0 if stable else None)
        lost_time = ((self.last_present.time + 1)
                     if lost and self.last_present is not None else
                     0 if lost else None)
        stable_latency = (max(stable_time - known_time, 0) // 1_000_000
                          if stable and known_time is not None else None)
        lost_latency = (max(lost_time - known_time, 0) // 1_000_000
                        if lost and known_time is not None else None)
        return {"element": self.element,
                "outcome": ("stable" if stable else
                            "lost" if lost else "never-read"),
                "stable-latency": stable_latency,
                "lost-latency": lost_latency,
                "known": self.known,
                "last-absent": self.last_absent}


def frequency_distribution(points, xs):
    """Percentile map (0-1) of a collection (checker.clj:305-316)."""
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    return {p: xs[min(n - 1, int(n * p))] for p in points}


class SetFull(Checker):
    """Rigorous per-element stable/lost timeline analysis
    (checker.clj:364-533)."""

    def __init__(self, checker_opts=None):
        self.opts = {"linearizable?": False}
        self.opts.update(checker_opts or {})

    def check(self, test, history, opts=None):
        elements: dict = {}
        reads: dict = {}
        dups: dict = {}
        for o in History(history):
            if not isinstance(o.process, int) or isinstance(o.process, bool) \
                    or o.process < 0:
                continue
            if o.f == "add":
                if o.is_invoke:
                    elements.setdefault(o.value, _SetFullElement(o.value))
                elif o.value in elements:
                    elements[o.value].add(o)
            elif o.f == "read":
                if o.is_invoke:
                    reads[o.process] = o
                elif o.is_fail:
                    reads.pop(o.process, None)
                elif o.is_info:
                    pass
                elif o.is_ok:
                    inv = reads.get(o.process)
                    v = o.value or []
                    for el, n in Counter(v).items():
                        if n > 1:
                            dups[el] = max(dups.get(el, 0), n)
                    vs = set(v)
                    for el, state in elements.items():
                        if el in vs:
                            state.read_present(inv, o)
                        else:
                            state.read_absent(inv, o)

        rs = [e.results() for e in elements.values()]
        outcomes: dict = {}
        for r in rs:
            outcomes.setdefault(r["outcome"], []).append(r)
        stable = outcomes.get("stable", [])
        lost = outcomes.get("lost", [])
        never_read = outcomes.get("never-read", [])
        stale = [r for r in stable if r["stable-latency"]]
        worst_stale = sorted(stale, key=lambda r: r["stable-latency"],
                             reverse=True)[:8]
        stable_latencies = [r["stable-latency"] for r in rs
                            if r["stable-latency"] is not None]
        lost_latencies = [r["lost-latency"] for r in rs
                          if r["lost-latency"] is not None]
        if lost:
            valid: Any = False
        elif not stable:
            valid = UNKNOWN
        elif self.opts.get("linearizable?") and stale:
            valid = False
        else:
            valid = True
        out = {
            "valid?": valid if not dups else False,
            "attempt-count": len(rs),
            "stable-count": len(stable),
            "lost-count": len(lost),
            "lost": sorted(r["element"] for r in lost),
            "never-read-count": len(never_read),
            "never-read": sorted(r["element"] for r in never_read),
            "stale-count": len(stale),
            "stale": sorted(r["element"] for r in stale),
            "worst-stale": worst_stale,
            "duplicated-count": len(dups),
            "duplicated": dict(sorted(dups.items())),
        }
        points = (0, 0.5, 0.95, 0.99, 1)
        if stable_latencies:
            out["stable-latencies"] = frequency_distribution(
                points, stable_latencies)
        if lost_latencies:
            out["lost-latencies"] = frequency_distribution(
                points, lost_latencies)
        return out


def set_full(checker_opts=None):
    return SetFull(checker_opts)


# ---------------------------------------------------------------------------
# Total queue — checker.clj:534-628
# ---------------------------------------------------------------------------

def expand_queue_drain_ops(history) -> History:
    """Expand ok :drain ops into dequeue invoke/ok pairs
    (checker.clj:534-564)."""
    out = []
    for o in History(history):
        if o.f != "drain":
            out.append(o)
        elif o.is_invoke or o.is_fail:
            continue
        elif o.is_ok:
            for el in o.value or []:
                out.append(o.assoc(type="invoke", f="dequeue", value=None))
                out.append(o.assoc(type="ok", f="dequeue", value=el))
        else:
            raise ValueError(
                f"Not sure how to handle a crashed drain operation: {o}")
    return History(out)


class TotalQueue(Checker):
    """What goes in must come out (checker.clj:566-628).  Multiset algebra
    runs on device for large integer-valued histories."""

    DEVICE_THRESHOLD = 4096

    def check(self, test, history, opts=None):
        h = expand_queue_drain_ops(history)
        attempts: Counter = Counter()
        enqueues: Counter = Counter()
        dequeues: Counter = Counter()
        for o in h:
            if o.f == "enqueue" and o.is_invoke:
                attempts[o.value] += 1
            elif o.f == "enqueue" and o.is_ok:
                enqueues[o.value] += 1
            elif o.f == "dequeue" and o.is_ok:
                dequeues[o.value] += 1

        ok = dequeues & attempts
        unexpected = Counter({k: v for k, v in dequeues.items()
                              if k not in attempts})
        duplicated = dequeues - attempts - unexpected
        lost = enqueues - dequeues
        recovered = ok - enqueues

        def total(c):
            return sum(c.values())

        return {
            "valid?": not lost and not unexpected,
            "attempt-count": total(attempts),
            "acknowledged-count": total(enqueues),
            "ok-count": total(ok),
            "unexpected-count": total(unexpected),
            "duplicated-count": total(duplicated),
            "lost-count": total(lost),
            "recovered-count": total(recovered),
            "lost": dict(lost),
            "unexpected": dict(unexpected),
            "duplicated": dict(duplicated),
            "recovered": dict(recovered),
        }


def total_queue():
    return TotalQueue()


# ---------------------------------------------------------------------------
# Unique IDs — checker.clj:630-676
# ---------------------------------------------------------------------------

class UniqueIds(Checker):
    DEVICE_THRESHOLD = 4096

    def check(self, test, history, opts=None):
        attempted = 0
        acks = []
        for o in History(history):
            if o.f == "generate" and o.is_invoke:
                attempted += 1
            elif o.f == "generate" and o.is_ok:
                acks.append(o.value)

        from jepsen_tpu.ops import fold

        if fold.all_ints(acks) and len(acks) >= self.DEVICE_THRESHOLD:
            counts, mask = fold.duplicate_counts(acks)
            dups = {v: int(c) for v, c, m in zip(acks, counts, mask) if m}
        else:
            dups = {k: v for k, v in Counter(acks).items() if v > 1}
        rng = [min(acks), max(acks)] if acks else [None, None]
        return {
            "valid?": not dups,
            "attempted-count": attempted,
            "acknowledged-count": len(acks),
            "duplicated-count": len(dups),
            "duplicated": dict(sorted(dups.items(), key=lambda kv: -kv[1])[:48]),
            "range": rng,
        }


def unique_ids():
    return UniqueIds()


# ---------------------------------------------------------------------------
# Counter — checker.clj:678-755
# ---------------------------------------------------------------------------

class CounterChecker(Checker):
    """Interval-bound counter analysis (checker.clj:678-755): at each
    read, the value must lie within [lower, upper] where `lower` tracks
    ok'd increments + attempted decrements and `upper` attempted
    increments + ok'd decrements, unioned over the read's concurrency
    window — a read tuple is [min-lower-in-window, v,
    max-upper-in-window], matching the reference's golden fixtures
    (checker_test.clj:88-163).  Bounds are prefix sums; the device
    kernel ops/fold.counter_bounds computes them for long histories."""

    def check(self, test, history, opts=None):
        h = History(history)
        # Pair ops; drop failed pairs entirely (reference removes :fails?
        # invocations and fail completions, checker.clj:696-699).
        failed_inv = set()
        open_inv: dict = {}
        for pos, o in enumerate(h):
            if o.is_invoke:
                open_inv[o.process] = pos
            elif o.is_fail and o.process in open_inv:
                failed_inv.add(open_inv.pop(o.process))

        lower = upper = 0
        pending_reads: dict = {}  # process -> [min_lower, max_upper]
        reads = []
        for pos, o in enumerate(h):
            if pos in failed_inv or o.is_fail:
                continue
            if o.f == "read" and o.is_invoke:
                pending_reads[o.process] = [lower, upper]
            elif o.f == "read" and o.is_ok:
                lo, hi = pending_reads.pop(o.process, [lower, upper])
                reads.append((lo, o.value, hi))
            elif o.f == "add" and (o.is_invoke or o.is_ok):
                v = o.value
                if o.is_invoke:
                    lower, upper = ((lower, upper + v) if v > 0 else
                                    (lower + v, upper))
                else:
                    lower, upper = ((lower + v, upper) if v > 0 else
                                    (lower, upper + v))
                for rs in pending_reads.values():
                    rs[0] = min(rs[0], lower)
                    rs[1] = max(rs[1], upper)
        errors = [r for r in reads if not r[0] <= r[1] <= r[2]]
        return {"valid?": not errors,
                "reads": [list(r) for r in reads],
                "errors": [list(r) for r in errors]}


def counter():
    return CounterChecker()


# ---------------------------------------------------------------------------
# Graph checkers (latency/rate/clock plots) — wired to checker.perf
# ---------------------------------------------------------------------------

def _perf_mod():
    # NOT `from jepsen_tpu.checker import perf`: the factory function
    # `perf()` below shadows the submodule as a package attribute, and
    # importing the submodule in turn sets that attribute to the module
    # — so restore the factory afterwards or ck.perf() stops being
    # callable.
    import importlib
    import sys
    pkg = sys.modules[__name__]
    factory = getattr(pkg, "perf", None)
    mod = importlib.import_module("jepsen_tpu.checker.perf")
    if callable(factory) and getattr(pkg, "perf", None) is mod:
        setattr(pkg, "perf", factory)
    return mod


class LatencyGraph(Checker):
    def check(self, test, history, opts=None):
        perf_mod = _perf_mod()
        perf_mod.point_graph(test, history, opts or {})
        perf_mod.quantiles_graph(test, history, opts or {})
        return {"valid?": True}


class RateGraph(Checker):
    def check(self, test, history, opts=None):
        _perf_mod().rate_graph(test, history, opts or {})
        return {"valid?": True}


def latency_graph():
    return LatencyGraph()


def rate_graph():
    return RateGraph()


def perf():
    """Assorted performance statistics (checker.clj:774-778)."""
    return compose({"latency-graph": latency_graph(),
                    "rate-graph": rate_graph()})


class ClockPlot(Checker):
    def check(self, test, history, opts=None):
        from jepsen_tpu.checker import clock as clock_mod
        clock_mod.plot(test, history, opts or {})
        return {"valid?": True}


def clock_plot():
    return ClockPlot()
