"""Key-independent scaling: lift a single-key test to a map of keys
(reference: `jepsen/src/jepsen/independent.clj`).

Linearizability is expensive to check, so histories must be short — but
short histories can't reveal enough concurrency errors.  This layer
splits a test into independent per-key components: generators shard
threads into groups (one key per group), and the checker splits the
history into per-key subhistories.

This is the framework's **data-parallel axis**: `checker()` fans
per-key subhistories out host-side (bounded_pmap, like the reference's
independent.clj:247-298), and `batch_checker()` packs every per-key
history into one columnar device program — `vmap` of the WGL kernel over
keys, shardable over a TPU mesh (SURVEY.md §2.4, BASELINE config 3).
"""

from __future__ import annotations

import json
import logging
from typing import Callable, Iterable, Optional

import numpy as np

from jepsen_tpu import checker as ck
from jepsen_tpu import generator as gen
from jepsen_tpu import telemetry
from jepsen_tpu.history import KEY_NONE, KEY_UNHELD, KV, History, Op
from jepsen_tpu.util import bounded_pmap

log = logging.getLogger("jepsen")

DIR = "independent"


def tuple_(k, v) -> KV:
    return KV(k, v)


def is_tuple(v) -> bool:
    return isinstance(v, KV)


class SequentialGenerator(gen.Generator):
    """One key at a time: exhaust fgen(k1), move to k2, ...
    (independent.clj:31-64).  Op values are wrapped in KV tuples."""

    def __init__(self, keys: Iterable, fgen: Callable):
        import threading
        self.lock = threading.Lock()
        self.keys = list(keys)
        self.i = 0
        self.gen = fgen(self.keys[0]) if self.keys else None
        self.fgen = fgen

    def op(self, test, process):
        while True:
            with self.lock:
                i, g = self.i, self.gen
            if i >= len(self.keys):
                return None
            o = gen.op(g, test, process)
            if o is not None:
                k = self.keys[i]
                v = o.get("value") if isinstance(o, dict) else o.value
                return gen._op_assoc(o, value=KV(k, v))
            with self.lock:
                if self.i == i:  # we advance
                    self.i += 1
                    self.gen = (self.fgen(self.keys[self.i])
                                if self.i < len(self.keys) else None)


def sequential_generator(keys, fgen):
    return SequentialGenerator(keys, fgen)


class ConcurrentGenerator(gen.Generator):
    """n threads per key, running keys concurrently in disjoint thread
    groups; each group moves to a fresh key when its generator is
    exhausted (independent.clj:66-220).  The nemesis does not enter
    subgenerators."""

    def __init__(self, n: int, keys: Iterable, fgen: Callable):
        import threading
        assert isinstance(n, int) and n > 0
        self.n = n
        self.keys = iter(keys)
        self.fgen = fgen
        self.lock = threading.Lock()
        self.state: Optional[dict] = None

    def _init_state(self, test):
        threads = [t for t in gen.current_threads()
                   if isinstance(t, int) and not isinstance(t, bool)]
        thread_count = len(threads)
        assert sorted(threads) == list(range(thread_count)), \
            "concurrent-generator expects integer threads 0..n"
        assert test["concurrency"] == thread_count, (
            f"Expected test concurrency ({test['concurrency']}) to equal "
            f"the number of integer threads ({thread_count})")
        group_size = self.n
        group_count = thread_count // group_size
        assert group_size <= thread_count, (
            f"With {thread_count} worker threads, this concurrent-generator"
            f" cannot run a key with {group_size} threads concurrently."
            f" Consider raising your test's concurrency to at least"
            f" {group_size}.")
        assert thread_count == group_size * group_count, (
            f"This concurrent-generator has {thread_count} threads but can"
            f" only use {group_size * group_count} of them to run"
            f" {group_count} concurrent keys with {group_size} threads"
            f" apiece. Consider a concurrency that is a multiple of"
            f" {group_size}.")
        active = []
        for _ in range(group_count):
            k = next(self.keys, _DONE)
            active.append(None if k is _DONE else (k, self.fgen(k)))
        self.state = {
            "active": active,
            "group_threads": [tuple(threads[g * group_size:
                                            (g + 1) * group_size])
                              for g in range(group_count)],
            "group_size": group_size,
        }

    def op(self, test, process):
        with self.lock:
            if self.state is None:
                self._init_state(test)
            s = self.state
        thread = gen.process_to_thread(test, process)
        assert isinstance(thread, int), (
            f"Only worker threads with numeric ids can ask for operations"
            f" from concurrent-generator; got {thread!r}")
        group = thread // s["group_size"]
        while True:
            # An enclosing time-limit may expire while we rotate keys;
            # with an infinite key iterator every fresh subgenerator
            # then yields None immediately and this loop would spin
            # forever.  Re-check the deadline each turn.
            d = gen._deadline()
            if d is not None and gen._now() > d:
                return None
            with self.lock:
                pair = s["active"][group]
            if pair is None:
                return None
            k, g = pair
            threads2 = s["group_threads"][group]
            assert thread in threads2, (
                f"Probably a bug: thread {thread} in group {group} isn't in"
                f" that group's thread list {threads2}")
            with gen.with_threads(threads2):
                o = gen.op(g, test, process)
            if o is not None:
                v = o.get("value") if isinstance(o, dict) else o.value
                return gen._op_assoc(o, value=KV(k, v))
            with self.lock:
                if self.state["active"][group] is pair:
                    k2 = next(self.keys, _DONE)
                    self.state["active"][group] = \
                        None if k2 is _DONE else (k2, self.fgen(k2))


_DONE = object()


def concurrent_generator(n, keys, fgen):
    return ConcurrentGenerator(n, keys, fgen)


# ---------------------------------------------------------------------------
# History splitting (independent.clj:222-245)
# ---------------------------------------------------------------------------

def _split_columns(history):
    """The packed columns the split reads in place of the ops, or None:
    the history's own columns, with value kinds (so a key column where
    any record is keyed) and a code for every keyed record's key."""
    if not isinstance(history, History):
        return None
    cols = history.packed_columns()
    if cols is None or cols.vkind is None or len(cols) != len(history):
        return None
    if cols.key is not None and (cols.key == KEY_UNHELD).any():
        return None
    return cols


def history_keys(history) -> set:
    cols = _split_columns(history)
    if cols is None:
        return {o.value.key for o in History(history) if is_tuple(o.value)}
    if cols.key is None:
        return set()
    present = np.bincount(cols.key[cols.key >= 0],
                          minlength=len(cols.key_table))
    return {cols.key_table[c] for c in np.flatnonzero(present)}


def subhistory(k, history) -> History:
    """All ops without a differing key; KV values unwrapped.  Un-keyed
    ops (nemesis, info) appear in every subhistory."""
    out = []
    for o in History(history):
        v = o.value
        if not is_tuple(v):
            out.append(o)
        elif v.key == k:
            out.append(o.assoc(value=v.value))
    return History(out)


def subhistories(ks, history) -> list:
    """[subhistory(k, history) for k in ks] in ONE pass over the
    history: splitting a many-key run key by key rescans every op once
    per key.  A history with packed columns splits as columns
    (_KeyHistory: each key's rows, its ops built only when read); one
    without (a history loaded from the store) splits its ops."""
    cols = _split_columns(history)
    if cols is not None:
        return _split_cols(ks, history, cols)
    by_key: dict = {k: [] for k in ks}
    for o in History(history):
        v = o.value
        if not is_tuple(v):
            for sub in by_key.values():
                sub.append(o)
        elif v.key in by_key:
            by_key[v.key].append(o.assoc(value=v.value))
    return [History(by_key[k]) for k in ks]


class _KeyHistory(History):
    """One key's subhistory as rows of its parent: the rows' packed
    columns are attached (their values already the inner ones), and
    `ops` is built from the parent's ops on first access, exactly as
    `subhistory` builds them: keyed ops unwrapped through `assoc`,
    un-keyed ops shared."""

    def __init__(self, parent_ops: list, rows: np.ndarray,
                 keyed: np.ndarray, packed):
        self._parent_ops = parent_ops
        self._rows = rows
        self._keyed = keyed
        self._ops: Optional[list] = None
        self._packed = packed
        self._journal = None
        self.wal = None

    @property
    def ops(self) -> list:
        if self._ops is None:
            parent = self._parent_ops
            self._ops = [parent[r].assoc(value=parent[r].value.value)
                         if kd else parent[r]
                         for r, kd in zip(self._rows.tolist(),
                                          self._keyed.tolist())]
        return self._ops

    @ops.setter
    def ops(self, ops: list) -> None:
        self._ops = ops

    @property
    def ops_built(self) -> bool:
        return self._ops is not None

    def __len__(self):
        return len(self._rows) if self._ops is None else len(self._ops)


def _split_cols(ks, history, cols) -> list:
    """subhistories over packed columns: each key's rows are its own
    merged in order with the un-keyed ones; every column is gathered
    once in key order and each key takes a slice, the original `index`
    column included, so witnesses name the parent's op indices."""
    n = len(cols)
    key = cols.key if cols.key is not None \
        else np.full(n, KEY_NONE, np.int32)
    table = cols.key_table or []
    codes = {k: c for c, k in enumerate(table)}
    # rows grouped by code (KEY_NONE first), each group in history order
    order = np.argsort(key, kind="stable")
    starts = np.searchsorted(key[order], np.arange(KEY_NONE, len(table) + 1))
    unkeyed = order[:starts[1]]
    parts = []
    for k in ks:
        c = codes.get(k)
        own = order[starts[c + 1]:starts[c + 2]] if c is not None \
            else order[:0]
        parts.append(np.sort(np.concatenate([own, unkeyed]))
                     if len(unkeyed) else own)
    rows = np.concatenate(parts) if parts else order[:0]
    whole = cols.take(rows)
    whole.key = whole.key_table = None       # the values are the inner ones
    keyed = key[rows] >= 0
    out, lo = [], 0
    for part in parts:
        hi = lo + len(part)
        out.append(_KeyHistory(history.ops, part, keyed[lo:hi],
                               whole.take(slice(lo, hi))))
        lo = hi
    return out


# ---------------------------------------------------------------------------
# Checkers
# ---------------------------------------------------------------------------

class IndependentChecker(ck.Checker):
    """Host-parallel per-key checking (independent.clj:247-298): valid
    iff the underlying checker is valid for every subhistory; writes
    per-key artifacts under independent/<k>/."""

    def __init__(self, checker: ck.Checker):
        self.checker = checker

    def _check_key(self, test, history, opts, k):
        h = subhistory(k, history)
        subdir = list((opts or {}).get("subdirectory") or []) + [DIR, str(k)]
        results = ck.check_safe(self.checker, test, h,
                                {"subdirectory": subdir, "history-key": k})
        if test and test.get("name") and test.get("start-time"):
            from jepsen_tpu import store
            try:
                with open(store.make_path(test, *subdir, "results.json"),
                          "w") as f:
                    json.dump(store._jsonable_tree(results), f, indent=2,
                              default=repr)
                with open(store.make_path(test, *subdir, "history.jsonl"),
                          "w") as f:
                    f.write(h.to_jsonl())
            except OSError:
                log.warning("could not write independent results for %r", k)
        return k, results

    def check(self, test, history, opts=None):
        ks = sorted(history_keys(history), key=repr)
        results = dict(bounded_pmap(
            lambda k: self._check_key(test, history, opts, k), ks))
        failures = [k for k, r in results.items() if r["valid?"] is not True]
        return {"valid?": ck.merge_valid(r["valid?"]
                                         for r in results.values()),
                "results": results,
                "failures": failures}


def checker(sub_checker: ck.Checker) -> IndependentChecker:
    return IndependentChecker(sub_checker)


class BatchedLinearizableChecker(ck.Checker):
    """The TPU-native independent checker: every per-key subhistory is
    one lane of a single device program, shardable over a mesh.

    Engine order mirrors checker.Linearizable: the bitmap batch kernel
    first (ops/wgl_seg.check_many — dense configuration space, no
    sorting, exact; crash-free keys with small state spaces), whose
    per-key fallback escalates out-of-scope keys to the sorted frontier
    kernel (ops/wgl) and then the CPU oracle.  The batch dispatch runs
    through ops.runner.ResilientRunner, so a device OOM on a wide key
    axis bisects instead of aborting, one poisoned key is quarantined
    with a structured verdict, and — when the analysis phase provides
    opts['checkpoint_dir'] — completed per-key verdicts checkpoint to
    the store and a killed analysis resumes.  A model with no device
    spec at all degrades to the CPU oracle, key by key (the runner's
    BackendUnavailable path)."""

    def __init__(self, model, frontier_size: int = 256, mesh=None,
                 deadline_s: Optional[float] = None,
                 max_retries: int = 2):
        self.model = model
        self.frontier_size = frontier_size  # advisory; kept for API compat
        self.mesh = mesh
        self.deadline_s = deadline_s
        self.max_retries = max_retries

    def check(self, test, history, opts=None):
        """Split, one batched device call, assemble.  The front's own
        stages (`keys`, `split`, `assemble`) are spans counted into
        `jepsen_checker_seconds_total{checker="independent"}`; the
        engine counts its dispatch stages itself.  The split's keys
        count into `jepsen_split_keys_total{path}`, and the keys whose
        ops exist by the end into `jepsen_split_ops_built_total`."""
        front = telemetry.Stages()
        try:
            return self._check(test, history, opts, front)
        finally:
            front.flush(telemetry.CHECKER_SECONDS, checker="independent")

    def _check(self, test, history, opts, front):
        with front.span("keys"):
            ks = sorted(history_keys(history), key=repr)
        if not ks:
            return {"valid?": True, "results": {}, "failures": []}
        with front.span("split"):
            subs = subhistories(ks, history)
        columnar = sum(isinstance(s, _KeyHistory) for s in subs)
        front.tally(telemetry.SPLIT_KEYS, columnar, path="columnar")
        front.tally(telemetry.SPLIT_KEYS, len(subs) - columnar,
                    path="objects")
        try:
            return self._verdicts(test, opts, ks, subs, front)
        finally:
            front.tally(telemetry.SPLIT_OPS_BUILT,
                        sum(not isinstance(s, _KeyHistory) or s.ops_built
                            for s in subs))

    def _verdicts(self, test, opts, ks, subs, front):
        import os as _os

        from jepsen_tpu.ops import runner as runner_mod

        ckdir = (opts or {}).get("checkpoint_dir")
        per_key = runner_mod.ResilientRunner(
            engine="seg_many",
            engine_kwargs=dict(
                mesh=self.mesh,
                mesh_axis=(self.mesh.axis_names[0]
                           if self.mesh else None)),
            deadline_s=self.deadline_s,
            max_retries=self.max_retries,
            checkpoint_dir=(_os.path.join(str(ckdir), DIR)
                            if ckdir else None),
        ).check(self.model, subs)
        with front.span("assemble"):
            results = dict(zip(ks, per_key))
            failures = [k for k, r in results.items()
                        if r["valid?"] is not True]
            # Failing-window SVGs under independent/<k>/, matching the
            # host-parallel IndependentChecker path (checker.clj:147-154).
            for k, sub in zip(ks, subs):
                r = results[k]
                if r.get("valid?") is False \
                        and r.get("op_index") is not None:
                    try:
                        from jepsen_tpu.checker import linear_report
                        subdir = (list((opts or {}).get("subdirectory")
                                       or []) + [DIR, str(k)])
                        p = linear_report.write_to_store(
                            test, sub, r, {"subdirectory": subdir})
                        if p:
                            r["linear-svg"] = p
                    except Exception as e:          # noqa: BLE001
                        r["linear-svg-error"] = str(e)
            return {"valid?": ck.merge_valid(r["valid?"]
                                             for r in results.values()),
                    "results": results,
                    "failures": failures}


def batch_checker(model_or_checker, frontier_size: int = 256, mesh=None):
    """The TPU-native independent checker.  Handed a *model*, every
    per-key subhistory rides one lane of the batched WGL program
    (BatchedLinearizableChecker).  Handed a *Checker* that knows how
    to `check_many` (e.g. `checker.elle.Elle`), the same key-splitting
    shell batches through that checker's own device engine instead —
    txn isolation planes get the same one-program treatment as
    linearizability lanes."""
    if isinstance(model_or_checker, ck.Checker) \
            and callable(getattr(model_or_checker, "check_many", None)):
        from jepsen_tpu.checker.elle import BatchedElleChecker
        return BatchedElleChecker(model_or_checker)
    return BatchedLinearizableChecker(model_or_checker, frontier_size,
                                      mesh)
