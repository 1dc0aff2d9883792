"""Operation histories: the core data structure of the framework.

A history is an ordered sequence of *operations*.  Each op is a small
record — equivalent to the reference's Clojure maps
(`jepsen/src/jepsen/util.clj:146-206`, `jepsen/src/jepsen/core.clj:55-59`)
— with fields:

  index    monotone position in the history (knossos.history/index)
  process  logical single-threaded actor id (int), or NEMESIS
  type     one of invoke | ok | fail | info
  f        operation function tag (e.g. 'read, 'write, 'cas) — any hashable
  value    op payload; for reads the invoke carries None and the completion
           carries the observed value
  time     relative nanoseconds since test start
  error    optional error payload on non-ok completions

On the device side a history becomes a *columnar* struct-of-arrays
(`pack()`), replacing the map-per-op vectors: int32/int64 arrays that JAX
kernels consume directly.  See SURVEY.md §7 (history core + op codec).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Callable, Iterable, Iterator, Optional

import numpy as np

log = logging.getLogger("jepsen")

# Sentinel process id for the nemesis (the reference uses the keyword
# :nemesis; we reserve a negative int so process columns stay integral).
NEMESIS = -1

INVOKE, OK, FAIL, INFO = "invoke", "ok", "fail", "info"
TYPES = (INVOKE, OK, FAIL, INFO)
TYPE_CODE = {t: i for i, t in enumerate(TYPES)}
CODE_TYPE = {i: t for t, i in TYPE_CODE.items()}


class KV(tuple):
    """A key/value tuple marking an op value as belonging to an
    independent key (independent.clj tuple :21-29).  Public as
    `independent.KV`; defined here so the column journal can recognise
    keyed records."""

    def __new__(cls, k, v):
        return super().__new__(cls, (k, v))

    @property
    def key(self):
        return self[0]

    @property
    def value(self):
        return self[1]

    def __repr__(self):
        return f"[{self[0]!r} {self[1]!r}]"


@dataclasses.dataclass
class Op:
    """One operation record.  Mutable by design: the worker loop assigns
    :index/:time/:process as ops flow through it, like the reference's
    `assoc` chain (`core.clj:306-308`)."""

    process: Any = None
    type: str = INVOKE
    f: Any = None
    value: Any = None
    time: Optional[int] = None
    index: Optional[int] = None
    error: Any = None
    extra: dict = dataclasses.field(default_factory=dict)

    # -- dict-ish ergonomics -------------------------------------------------
    def __getitem__(self, k):
        if k in self.__dataclass_fields__ and k != "extra":
            return getattr(self, k)
        return self.extra[k]

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def __contains__(self, k):
        if k in ("process", "type", "f", "value", "time", "index", "error"):
            return getattr(self, k) is not None
        return k in self.extra

    def assoc(self, **kw) -> "Op":
        """Functional update: returns a copy with fields replaced.
        Hand-rolled rather than dataclasses.replace — this runs for
        every op in the worker loop and again per-op in history prep,
        and replace()'s re-init costs ~10x a plain copy."""
        out = object.__new__(Op)
        d = out.__dict__
        d.update(self.__dict__)
        extra = None
        for k, v in kw.items():
            if k in _OP_FIELDS:
                d[k] = v
            else:
                if extra is None:
                    extra = dict(self.extra)
                extra[k] = v
        d["extra"] = extra if extra is not None else dict(self.extra)
        return out

    # -- predicates (knossos.op parity: invoke? ok? fail? info?) -------------
    @property
    def is_invoke(self):
        return self.type == INVOKE

    @property
    def is_ok(self):
        return self.type == OK

    @property
    def is_fail(self):
        return self.type == FAIL

    @property
    def is_info(self):
        return self.type == INFO

    def to_dict(self) -> dict:
        v = self.value
        if isinstance(v, KV):
            # Tag independent-key tuples so they survive the JSON
            # round-trip — the reference registers a custom Fressian
            # handler for MapEntry for exactly this (store.clj:28-123);
            # without it, `analyze` on a stored keyed history finds no
            # keys and trivially passes.
            v = {"__kv__": [v[0], v[1]]}
        d = {"index": self.index, "process": self.process, "type": self.type,
             "f": self.f, "value": v, "time": self.time}
        if self.error is not None:
            d["error"] = self.error
        d.update(self.extra)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Op":
        d = dict(d)
        kw = {k: d.pop(k) for k in
              ("process", "type", "f", "value", "time", "index", "error")
              if k in d}
        v = kw.get("value")
        if isinstance(v, dict) and set(v) == {"__kv__"}:
            kw["value"] = KV(*v["__kv__"])
        return cls(extra=d, **kw)

    def __str__(self):
        err = f"\t{self.error}" if self.error is not None else ""
        return f"{self.process}\t{self.type}\t{self.f}\t{self.value}{err}"


_OP_FIELDS = frozenset(f for f in Op.__dataclass_fields__
                       if f != "extra")


# Convenience constructors (knossos.core/{invoke-op, ok-op, fail-op} parity —
# used heavily by the reference's checker tests, checker_test.clj:5-7).
def invoke_op(process, f, value, **kw):
    return Op(process=process, type=INVOKE, f=f, value=value, **kw)


def ok_op(process, f, value, **kw):
    return Op(process=process, type=OK, f=f, value=value, **kw)


def fail_op(process, f, value, **kw):
    return Op(process=process, type=FAIL, f=f, value=value, **kw)


def info_op(process, f, value, **kw):
    return Op(process=process, type=INFO, f=f, value=value, **kw)


def op(like: Any) -> Op:
    """Coerce a dict or Op to an Op."""
    if isinstance(like, Op):
        return like
    return Op.from_dict(like)


class History:
    """An indexed list of Ops with the analysis passes the reference gets
    from knossos.history: `index`, `complete`, `pairs`, `processes`."""

    def __init__(self, ops: Iterable[Any] = (), journal: bool = False,
                 wal: Optional["HistoryWAL"] = None):
        self.ops: list[Op] = [op(o) for o in ops]
        self._packed: Optional["PackedHistory"] = None
        # With journal=True (the run loop, core.py run_case), every
        # append also lands in an incremental ColumnJournal, so the
        # columnar representation exists the moment the run ends and
        # analysis never walks the Op objects (SURVEY.md §7).
        self._journal: Optional["ColumnJournal"] = None
        # With a wal, every append is also written through to the
        # fsynced on-disk write-ahead log, so a SIGKILLed run leaves a
        # recoverable op record (see HistoryWAL / recover).
        self.wal = wal
        if journal:
            self._journal = ColumnJournal()
            for o in self.ops:
                self._journal.append(o)
        if wal is not None:
            for o in self.ops:
                wal.append(o)

    def __len__(self):
        return len(self.ops)

    def __iter__(self) -> Iterator[Op]:
        return iter(self.ops)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return History(self.ops[i])
        return self.ops[i]

    def append(self, o: Any) -> "Op":
        o = op(o)
        self.ops.append(o)
        self._packed = None          # columnar cache is positional
        if self._journal is not None:
            self._journal.append(o)
        if self.wal is not None:
            self.wal.append(o)
        return o

    def invalidate_packed(self) -> None:
        """Drop the cached columnar representation.  MUST be called (or
        attach_packed(pack_history(h)) re-run) after mutating an op IN
        PLACE: append() invalidates automatically, but in-place edits
        (bench corruption planters, test fixtures) would otherwise
        feed stale columns to the native scanners while the Python
        oracle sees the new values — a verdict-divergence footgun
        (ADVICE r3).

        Also bumps the attached PackedHistory's `version` counter, so
        any alias still holding that instance (e.g. a scanner that
        cached its contiguous casts in `_scan_cols`) recomputes
        instead of reading stale derived arrays — see the
        PackedHistory docstring."""
        if self._packed is not None:
            self._packed.version += 1
        self._packed = None

    def packed_columns(self) -> Optional["PackedHistory"]:
        """The columnar representation if one already exists (attached
        or journal-built) — WITHOUT walking the ops.  None otherwise;
        callers that need columns unconditionally use pack().

        CONTRACT: the cache is invalidated by append() but NOT by
        in-place op mutation — mutators call invalidate_packed() or
        re-attach fresh columns (see its docstring)."""
        if self._packed is not None:
            return self._packed
        if self._journal is not None:
            return self._journal.packed()
        return None

    def attach_packed(self, packed: "PackedHistory") -> "History":
        """Attach a pre-built columnar representation (from a
        ColumnJournal maintained during the run).  pack() then returns
        it without walking the ops, and the native columnar scan path
        engages in the checkers."""
        assert len(packed) == len(self.ops), \
            (len(packed), len(self.ops))
        self._packed = packed
        return self

    # -- passes --------------------------------------------------------------
    def index(self) -> "History":
        """Assign sequential :index to every op (knossos.history/index,
        called at jepsen.core/analyze! core.clj:441)."""
        for i, o in enumerate(self.ops):
            o.index = i
        return self

    def processes(self) -> list:
        return sorted({o.process for o in self.ops},
                      key=lambda p: (isinstance(p, str), p))

    def pairs(self) -> list[tuple[Op, Optional[Op]]]:
        """Pair each invocation with its completion (timeline.clj:33-56).
        Completion is None for ops that never completed.  Nemesis ops
        (non-invoke-first) pair (op, None)."""
        out = []
        open_by_process: dict[Any, Op] = {}
        for o in self.ops:
            if o.is_invoke:
                if o.process in open_by_process:
                    raise ValueError(
                        f"process {o.process} invoked twice without completing: {o}")
                open_by_process[o.process] = o
            else:
                inv = open_by_process.pop(o.process, None)
                if inv is not None:
                    out.append((inv, o))
                else:
                    out.append((o, None))
        for inv in open_by_process.values():
            out.append((inv, None))
        out.sort(key=lambda p: (p[0].index if p[0].index is not None else 0))
        return out

    def complete(self) -> "History":
        """Fill in invocation values from completions (knossos.history/complete,
        used by checker/counter checker.clj:696): an ok completion of a read
        back-fills the invocation's observed value; invocations whose op
        crashed are marked info."""
        out = []
        open_by_process: dict[Any, Op] = {}
        for o in self.ops:
            o = dataclasses.replace(o, extra=dict(o.extra))
            if o.is_invoke:
                open_by_process[o.process] = o
            elif o.process in open_by_process:
                inv = open_by_process.pop(o.process)
                if o.is_ok and inv.value is None:
                    inv.value = o.value
                if o.is_info:
                    inv.type = INFO
            out.append(o)
        return History(out)

    # -- persistence ---------------------------------------------------------
    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(o.to_dict(), default=repr)
                         for o in self.ops) + ("\n" if self.ops else "")

    @classmethod
    def from_jsonl(cls, text: str) -> "History":
        return cls(json.loads(line) for line in text.splitlines() if line.strip())

    def to_dicts(self) -> list[dict]:
        return [o.to_dict() for o in self.ops]

    # -- device packing ------------------------------------------------------
    def pack(self, f_codes: Optional[dict] = None,
             value_encoder: Optional[Callable[[Op], tuple[int, int]]] = None,
             ) -> "PackedHistory":
        if f_codes is None and value_encoder is None:
            cols = self.packed_columns()
            if cols is not None:
                return cols
        return pack_history(self, f_codes, value_encoder)


@dataclasses.dataclass
class PackedHistory:
    """Columnar device representation of a history (SURVEY.md §2.5:
    "history transport to device").  Two int64 value slots cover every
    built-in workload (cas carries [old, new]); richer payloads stay
    host-side.  value_ok marks slots that held encodable (integer) values.

    Derived-cast caching: the native scanners cache their contiguous
    int32/uint8 casts of these columns on the instance (the
    `_scan_cols` attribute, built by `ops.wgl_seg._cols_args`), keyed
    by `(version, len)`.  Code that mutates the column arrays IN PLACE
    must bump `version` (History.invalidate_packed() does this for the
    attached instance) or the cached casts go stale while the Python
    oracle sees the new values — a verdict-divergence footgun.  A
    length change invalidates on its own.

    Keyed histories (values `KV(k, v)`, the independent layer's) carry
    a `key` column: the record's code into `key_table`, KEY_NONE for an
    un-keyed record, KEY_UNHELD for a key no table can hold
    (unhashable).  A held keyed record's value columns encode the inner
    `v`, so a key's rows are its subhistory's columns as they stand
    (`independent.subhistories`).  The whole history's value columns
    are then no single register's: the scanners decline columns that
    carry a key column.  `key` is None where no record is keyed.
    """

    index: np.ndarray       # int32 [n]
    process: np.ndarray     # int32 [n]  (NEMESIS == -1)
    type: np.ndarray        # uint8 [n]  TYPE_CODE
    f: np.ndarray           # int32 [n]  per-test f-code table
    value: np.ndarray       # int64 [n, 2]
    value_ok: np.ndarray    # bool  [n, 2]
    time: np.ndarray        # int64 [n]
    f_codes: dict           # f tag -> code
    # Value-shape discriminator for the native columnar scan: 0 = None,
    # 1 = int32-range int, 2 = int32-range [a, b] pair, 3 = other
    # (unencodable), 4 = int/pair outside int32.  None when the history
    # was packed with a custom value_encoder (the scan then falls back
    # to the Op-object walk, which sees the real values).
    vkind: Optional[np.ndarray] = None  # uint8 [n]
    # Mutation counter guarding derived-cast caches (see class
    # docstring): bump after any in-place column edit.
    version: int = 0
    key: Optional[np.ndarray] = None    # int32 [n] key code
    key_table: Optional[list] = None    # key code -> key

    def __len__(self):
        return len(self.index)

    def take(self, rows) -> "PackedHistory":
        """The records at `rows` as a new PackedHistory: positions (an
        int array, gathered in the order given) or a slice (views).
        Every per-record column is taken, the code tables copied, no
        derived casts carried over."""
        if isinstance(rows, slice):
            def get(a):
                return a[rows]
        else:
            def get(a):         # np.take: ~10x a[rows] on the 2-D columns
                return np.take(a, rows, axis=0)
        keyed = self.key is not None
        return PackedHistory(
            get(self.index), get(self.process), get(self.type),
            get(self.f), get(self.value), get(self.value_ok),
            get(self.time), dict(self.f_codes),
            vkind=None if self.vkind is None else get(self.vkind),
            key=get(self.key) if keyed else None,
            key_table=list(self.key_table) if keyed else None)

    def unpack_op(self, i: int) -> Op:
        codes_f = {v: k for k, v in self.f_codes.items()}
        val: Any = None
        if self.value_ok[i, 0] and self.value_ok[i, 1]:
            val = [int(self.value[i, 0]), int(self.value[i, 1])]
        elif self.value_ok[i, 0]:
            val = int(self.value[i, 0])
        proc = int(self.process[i])
        return Op(index=int(self.index[i]), process=proc,
                  type=CODE_TYPE[int(self.type[i])],
                  f=codes_f.get(int(self.f[i])), value=val,
                  time=int(self.time[i]))


_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1


_I32 = 2 ** 31

# Process-column sentinel for CLIENT ids outside int32 range (e.g.
# uuid-derived worker ids): the object-path scanners and the oracle see
# the real id and treat the op as a client call, so the columnar pack
# must NOT silently fold it into NEMESIS — that would drop the op from
# the columnar scan and break the pinned "columnar and object paths
# classify identically" invariant (ADVICE r4).  The native columnar
# ingests treat this sentinel as out-of-scope (whole history falls back
# to the object walk, which sees the true ids).
P_OUT_OF_RANGE = -2


def _i32_process(p) -> int:
    """Process column value: exact non-negative int in int32 range as
    itself; an exact int OUTSIDE int32 range -> P_OUT_OF_RANGE (the
    columnar scans then defer to the object paths); anything else
    (nemesis tags, bools, IntEnums, strings) -> NEMESIS.  Must never
    raise inside the run-loop journal append (ADVICE r3)."""
    if type(p) is int:
        return p if 0 <= p < _I32 else \
            (P_OUT_OF_RANGE if p >= _I32 else NEMESIS)
    return NEMESIS


def _i32_index(idx, fallback: int) -> int:
    """Index column value: positional fallback when the op's own index
    is missing OR outside int32 (the column is positional anyway for
    journaled runs)."""
    return idx if isinstance(idx, int) and not isinstance(idx, bool) \
        and -_I32 <= idx < _I32 else fallback


def _fits_i64(x: int) -> bool:
    return _I64_MIN <= x <= _I64_MAX


def default_value_encoder(o: Op) -> tuple[list[int], list[bool]]:
    """Encode an op value into two int64 slots.  ints -> slot 0;
    [a, b] pairs (cas) -> both slots; None/other -> marked not-ok.
    Ints beyond int64 are marked not-ok instead of overflowing the
    column store — the run loop journals every op through here
    (ColumnJournal), so this must never raise."""
    return _encode_value(o.value)


def _encode_value(v) -> tuple[list[int], list[bool]]:
    if isinstance(v, bool):  # bool is an int subclass; keep it encodable
        return [int(v), 0], [True, False]
    if isinstance(v, int):
        if not _fits_i64(v):
            return [0, 0], [False, False]
        return [v, 0], [True, False]
    if (isinstance(v, (list, tuple)) and len(v) == 2
            and all(isinstance(x, int) and not isinstance(x, bool) for x in v)):
        if not (_fits_i64(v[0]) and _fits_i64(v[1])):
            return [0, 0], [False, False]
        return [v[0], v[1]], [True, True]
    return [0, 0], [False, False]


_I32_MIN, _I32_MAX = -(1 << 31), (1 << 31) - 1


def _value_kind(v) -> int:
    """vkind discriminator (see PackedHistory.vkind)."""
    if v is None:
        return 0
    if isinstance(v, bool):
        return 1
    if isinstance(v, int):
        return 1 if _I32_MIN <= v <= _I32_MAX else 4
    if (isinstance(v, (list, tuple)) and len(v) == 2
            and all(isinstance(x, int) and not isinstance(x, bool)
                    for x in v)):
        return (2 if all(_I32_MIN <= x <= _I32_MAX for x in v) else 4)
    return 3


# Key column codes (see PackedHistory): a record whose value is no KV,
# and a KV whose key no table can hold.
KEY_NONE = -1
KEY_UNHELD = -2

# PackedHistory's per-record columns.
_RECORD_COLUMNS = ("index", "process", "type", "f", "value", "value_ok",
                   "time", "vkind", "key")


def _key_code(k, codes: dict, table: list) -> int:
    """`k`'s code in (codes, table), entered when new.  Keys group by
    dict equality, as the independent split groups them; a key no dict
    can hold (unhashable) is KEY_UNHELD, since the journal must never
    raise."""
    try:
        c = codes.get(k)
        if c is None:
            c = codes[k] = len(table)
            table.append(k)
        return c
    except TypeError:
        return KEY_UNHELD


def pack_history(h: History, f_codes: Optional[dict] = None,
                 value_encoder=None) -> PackedHistory:
    """The columns of `h` (see PackedHistory).  With a custom
    `value_encoder` the value columns are the encoder's, and neither
    `vkind` nor a key column is kept."""
    custom_encoder = value_encoder is not None
    if f_codes is None:
        f_codes = {}
        for o in h:
            if o.f not in f_codes:
                f_codes[o.f] = len(f_codes)
    n = len(h)
    index = np.zeros(n, np.int32)
    process = np.zeros(n, np.int32)
    typ = np.zeros(n, np.uint8)
    f = np.zeros(n, np.int32)
    value = np.zeros((n, 2), np.int64)
    value_ok = np.zeros((n, 2), bool)
    time = np.zeros(n, np.int64)
    vkind = None if custom_encoder else np.zeros(n, np.uint8)
    key = np.full(n, KEY_NONE, np.int32)
    key_codes: dict = {}
    key_table: list = []
    for i, o in enumerate(h):
        index[i] = _i32_index(o.index, i)
        p = o.process
        # `type(p) is int` (not isinstance): bools and int subclasses
        # (IntEnum, numpy ints) are NOT client processes, exactly as
        # the scan engines' PyLong_CheckExact treats them — the
        # columnar and object paths must classify identically; ints
        # past int32 are not batchable processes either (range guard)
        process[i] = _i32_process(p)
        typ[i] = TYPE_CODE[o.type]
        f[i] = f_codes.get(o.f, -1)
        time[i] = o.time if o.time is not None else 0
        if custom_encoder:
            (value[i, 0], value[i, 1]), \
                (value_ok[i, 0], value_ok[i, 1]) = value_encoder(o)
            continue
        v = o.value
        if isinstance(v, KV):
            key[i] = c = _key_code(v[0], key_codes, key_table)
            if c >= 0:
                v = v[1]
        (value[i, 0], value[i, 1]), (value_ok[i, 0], value_ok[i, 1]) = \
            _encode_value(v)
        vkind[i] = _value_kind(v)
    keyed = not custom_encoder and bool((key != KEY_NONE).any())
    return PackedHistory(index, process, typ, f, value, value_ok, time,
                         dict(f_codes), vkind=vkind,
                         key=key if keyed else None,
                         key_table=key_table if keyed else None)


class ColumnJournal:
    """Incremental columnar journal: the run loop appends each op as it
    is journaled (the conj-op! point, core.clj:334-336), so by analysis
    time the SURVEY.md §7 struct-of-arrays representation already
    exists and checkers never pay a per-op Python traversal.  Attach
    the result to a History with `attach_packed` (History.pack() then
    returns it for free and the native columnar scan engages)."""

    def __init__(self, cap: int = 1024):
        self._n = 0
        self._cap = cap
        self.f_codes: dict = {}
        # the key column appears with the first keyed record; a history
        # with none pays one isinstance check per record
        self.key: Optional[np.ndarray] = None
        self._key_codes: dict = {}
        self.key_table: list = []
        self._alloc(cap)

    def _alloc(self, cap):
        self.index = np.zeros(cap, np.int32)
        self.process = np.zeros(cap, np.int32)
        self.type = np.zeros(cap, np.uint8)
        self.f = np.zeros(cap, np.int32)
        self.value = np.zeros((cap, 2), np.int64)
        self.value_ok = np.zeros((cap, 2), bool)
        self.time = np.zeros(cap, np.int64)
        self.vkind = np.zeros(cap, np.uint8)

    def _grow(self):
        old = {name: getattr(self, name) for name in _RECORD_COLUMNS}
        self._cap *= 2
        self._alloc(self._cap)
        if old["key"] is not None:
            self.key = np.full(self._cap, KEY_NONE, np.int32)
        for name, o in old.items():
            if o is not None:
                getattr(self, name)[:len(o)] = o

    def append(self, o: Op) -> None:
        i = self._n
        if i == self._cap:
            self._grow()
        self.index[i] = _i32_index(o.index, i)
        p = o.process
        # match pack_history / the scanners: exact int only, int32
        # range-guarded — journal append must never raise (ADVICE r3)
        self.process[i] = _i32_process(p)
        self.type[i] = TYPE_CODE[o.type]
        fc = self.f_codes.get(o.f)
        if fc is None:
            fc = self.f_codes[o.f] = len(self.f_codes)
        self.f[i] = fc
        v = o.value
        if isinstance(v, KV):
            if self.key is None:
                self.key = np.full(self._cap, KEY_NONE, np.int32)
            self.key[i] = c = _key_code(v[0], self._key_codes,
                                        self.key_table)
            if c >= 0:
                v = v[1]
        (self.value[i, 0], self.value[i, 1]), \
            (self.value_ok[i, 0], self.value_ok[i, 1]) = _encode_value(v)
        self.time[i] = o.time if o.time is not None else 0
        self.vkind[i] = _value_kind(v)
        self._n = i + 1

    def packed(self) -> PackedHistory:
        n = self._n
        keyed = self.key is not None
        return PackedHistory(self.index[:n], self.process[:n],
                             self.type[:n], self.f[:n], self.value[:n],
                             self.value_ok[:n], self.time[:n],
                             dict(self.f_codes), vkind=self.vkind[:n],
                             key=self.key[:n] if keyed else None,
                             key_table=(list(self.key_table) if keyed
                                        else None))


# ---------------------------------------------------------------------------
# Crash-safe history WAL (ISSUE 2 tentpole; same framing discipline as
# the resilient runner's verdicts.jsonl checkpoints, store.py:223-273):
# one JSON record per journaled op, appended + flushed + fsynced as it
# lands, each record guarded by a crc32 digest of its canonical op
# payload.  A SIGKILLed run leaves at worst one torn trailing line;
# `recover` rebuilds a well-formed history from the intact prefix,
# closing open invocations as :info (indeterminate — exactly what the
# reference's checkers assume about ops whose process crashed).
#
# Record framing (history.wal):
#     {"i": <seq>, "w": <append wall-clock s>, "crc": "<crc32 of
#      canonical op json>", "op": {...}}
#
# The canonical payload is json.dumps(op_dict, sort_keys=True,
# separators=(",", ":"), default=repr) — deterministic across the
# write/read round trip, so a reader can re-derive and verify the crc
# from the parsed record alone.  The `w` append stamp rides OUTSIDE the
# crc-guarded payload (old readers ignore it; old WALs lack it): it is
# what lets the live checker service measure true op-append→flag
# detection latency (docs/live-checker.md).
# ---------------------------------------------------------------------------

def _wal_payload(op_dict: dict) -> str:
    return json.dumps(op_dict, sort_keys=True, separators=(",", ":"),
                      default=repr)


def frame_line(payload_dict: dict, seq: int,
               wall: Optional[float] = None, key: str = "op",
               ctx: Optional[str] = None) -> bytes:
    """Encode ONE frame line — the unit both the WAL and the ingest
    wire protocol (docs/remote-ingest.md) are made of.  With `wall`
    the bytes are exactly what HistoryWAL.append writes; without it,
    the no-stamp variant (campaign ledgers).  The `w` stamp rides
    outside the crc-guarded payload, as always; `ctx` is the trace
    context envelope field `c` (ISSUE 19) — uncrc'd beside `w`/`e`,
    so old readers skip it and a garbled context can never invalidate
    the record it annotates."""
    body = _wal_payload(payload_dict)
    crc = zlib.crc32(body.encode())
    w = "" if wall is None else f'"w":{wall:.6f},'
    c = "" if ctx is None else f'"c":{json.dumps(str(ctx))},'
    return f'{{"i":{seq},{w}{c}"crc":"{crc:08x}","{key}":{body}}}\n' \
        .encode()


def parse_frame_line(line, key: str = "op",
                     seq: Optional[int] = None):
    """Validate ONE complete frame line; `(record, None)` when it
    holds, `(None, reason)` when it doesn't.  The single definition of
    frame validity: `follow_frames` applies it per line with the
    running sequence, the ingest tier (live/ingest.py) applies it per
    wire frame with `seq=None` and classifies the sequence number
    itself (dup vs reorder).  Guard order is parse → envelope → seq →
    crc, matching the historical stop_reason strings byte-for-byte."""
    if isinstance(line, (bytes, bytearray)):
        line = bytes(line).decode("utf-8", errors="replace")
    line = line.strip()
    try:
        rec = json.loads(line)
    except json.JSONDecodeError:
        return None, "unparseable complete record"
    if not isinstance(rec, dict) or key not in rec:
        return None, f"not a {key!r} frame"
    if seq is not None and rec.get("i") != seq:
        return None, (f"sequence break (expected {seq}, got "
                      f"{rec.get('i')})")
    payload = _wal_payload(rec[key])
    if f"{zlib.crc32(payload.encode()):08x}" != rec.get("crc"):
        return None, "crc mismatch"
    return rec, None


@dataclasses.dataclass
class FrameSegment:
    """One `follow_frames` read: the validated records, plus the cursor
    state to resume from.  `offset` always points at the first byte NOT
    consumed (the start of the first incomplete or invalid line), so a
    torn tail is re-read — and picked up whole — on the next call."""

    records: list                       # validated envelope dicts
    offset: int                         # byte offset to resume from
    seq: int                            # next expected record seq
    corrupt: bool = False               # a COMPLETE line failed a guard
    stop_reason: Optional[str] = None
    tail_bytes: int = 0                 # unconsumed bytes past `offset`
    epoch: int = 0                      # highest writer epoch accepted


def follow_frames(path, offset: int = 0, seq: int = 0,
                  key: str = "op",
                  max_records: Optional[int] = None,
                  epoch_key: Optional[str] = None,
                  epoch: int = 0) -> FrameSegment:
    """Tail a crc/seq-framed JSONL log (history.wal, telemetry.jsonl —
    both use the same framing discipline) from a byte offset.

    Intact-prefix semantics, incrementally: every COMPLETE line from
    `offset` is validated (parses, is a dict carrying `key`, sequence
    number equals `seq`+position, crc re-derived from the canonical
    payload matches); validation failure of a complete line marks the
    stream `corrupt` — everything past it is unattributable, exactly as
    in `recover`.  An INCOMPLETE trailing line (no newline yet: the
    writer is mid-append, or died mid-write) is NOT consumed: `offset`
    stays at its first byte and the next call re-reads it, so a
    follower survives torn tails and resumes by offset alone.

    `max_records` bounds one read (backpressure: a tailer ingesting
    into bounded memory reads in slices); the returned offset/seq
    resume exactly after the last consumed record.

    **Epoch fencing** (`epoch_key`, fleet tenant logs): records may
    carry their writer's lease epoch in that envelope field.  A
    paused-then-resumed stale worker can finish an in-flight append
    into a log a successor already owns — no writer-side fence can
    close that window (the pause may land between the fence check and
    the write syscall), so the READER fences, Raft-style: a valid
    record whose epoch is BELOW the highest epoch seen is a stale
    intrusion — skipped, never a sequence break; a record RAISING the
    epoch is a takeover — it supersedes any lower-epoch records at or
    after its own sequence number (the new owner resumed there before
    the stale line landed) and the expected sequence continues from
    it; within one epoch the log is single-writer and a sequence
    break still means a real tear.  Records without the field are
    epoch 0 (legacy / non-fleet logs: behavior is unchanged)."""
    with open(path, "rb") as f:
        f.seek(offset)
        buf = f.read()
    records: list = []
    pos = 0
    corrupt, reason = False, None
    while max_records is None or len(records) < max_records:
        nl = buf.find(b"\n", pos)
        if nl < 0:
            break
        line = buf[pos:nl].decode("utf-8", errors="replace").strip()
        if not line:
            pos = nl + 1
            continue
        if epoch_key is None:
            rec, err = parse_frame_line(line, key=key, seq=seq)
            if err is not None:
                corrupt, reason = True, f"record {seq}: {err}"
                break
            records.append(rec)
            seq += 1
            pos = nl + 1
            continue
        rec, err = parse_frame_line(line, key=key, seq=None)
        if err is not None:
            corrupt, reason = True, f"record {seq}: {err}"
            break
        e = rec.get(epoch_key)
        e = e if isinstance(e, int) else 0
        if e < epoch:
            pos = nl + 1                # fenced stale writer: skip
            continue
        i = rec.get("i")
        if not isinstance(i, int):
            corrupt, reason = True, (f"record {seq}: sequence break "
                                     f"(expected {seq}, got {i})")
            break
        if e > epoch:
            # takeover: the new owner's timeline supersedes any
            # lower-epoch records at/after its resume point
            while records and records[-1].get("i", -1) >= i:
                records.pop()
            epoch = e
        elif i != seq:
            corrupt, reason = True, (f"record {seq}: sequence break "
                                     f"(expected {seq}, got {i})")
            break
        records.append(rec)
        seq = i + 1
        pos = nl + 1
    return FrameSegment(records, offset + pos, seq, corrupt, reason,
                        len(buf) - pos, epoch)


@dataclasses.dataclass
class WalSegment:
    """One `follow` read of a history WAL: new ops (in append order)
    with their append wall-clock stamps, plus resume cursor state."""

    ops: list                           # Op per intact new record
    walls: list                         # parallel wall s (None if old)
    offset: int
    seq: int
    corrupt: bool = False
    stop_reason: Optional[str] = None
    tail_bytes: int = 0
    ctxs: list = dataclasses.field(default_factory=list)
    # parallel trace contexts (`c` envelope field, None if untraced)
    seqs: list = dataclasses.field(default_factory=list)
    # parallel record sequence numbers (`i`) — the join key between a
    # surfaced op and the ingest tier's transport stamps (ISSUE 19)


def follow(path, offset: int = 0, seq: int = 0,
           max_records: Optional[int] = None) -> WalSegment:
    """Resumable cursor over a (possibly still-being-written) history
    WAL: the documented streaming alternative to `recover`'s full
    re-read.  Returns the ops appended since `offset` whose records are
    intact, and the (`offset`, `seq`) pair to pass to the next call.

    Contract (the live checker service is built on it):
      * records are validated exactly like `recover` — parse, seq,
        crc — and only the intact prefix of the new bytes is returned;
      * an incomplete trailing line is left unconsumed (`tail_bytes`),
        so a follower polls through torn tails and loses nothing;
      * a COMPLETE line failing validation sets `corrupt`: the stream
        is permanently damaged past `offset` and following further
        cannot be attributed (callers should fall back to `recover`
        semantics for the final verdict);
      * `walls[i]` is the writer's append wall-clock stamp (the `w`
        envelope field) when present — detection-latency measurements
        anchor on it — or None for WALs written before the field
        existed."""
    seg = follow_frames(path, offset, seq, key="op",
                        max_records=max_records)
    ops, walls, ctxs, seqs = [], [], [], []
    for rec in seg.records:
        ops.append(Op.from_dict(rec["op"]))
        w = rec.get("w")
        walls.append(float(w) if isinstance(w, (int, float)) else None)
        c = rec.get("c")
        ctxs.append(c if isinstance(c, str) else None)
        seqs.append(rec.get("i"))
    return WalSegment(ops, walls, seg.offset, seg.seq, seg.corrupt,
                      seg.stop_reason, seg.tail_bytes, ctxs, seqs)


class HistoryWAL:
    """Append-only, fsynced, digest-guarded op log.

    Thread-safe: the run loop appends from every worker (via
    History.append under the history lock) AND from the nemesis
    journal; the internal lock keeps records whole regardless.  Append
    failures (disk full, fs gone) are logged once and disable the WAL
    rather than crashing the run — a run without crash-safety beats no
    run."""

    def __init__(self, path, fsync: bool = True, telemetry=None):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.fsync = fsync
        # jepsen_tpu.telemetry.Telemetry (or None): fsync latency is
        # recorded per append into jepsen_wal_fsync_seconds — the WAL
        # is the run loop's one mandatory disk wait, so its latency
        # distribution is the first thing to check when op latencies
        # drift (docs/observability.md)
        self.telemetry = telemetry
        self.lock = threading.Lock()
        self._n = 0
        self._dead = False
        self._f = open(self.path, "ab")

    def _write_line(self, line: bytes) -> None:
        """The single write point for a framed line.  Subclass hook:
        the streaming client (live/client.py StreamingWAL) overrides
        this to tee the exact bytes onto the ingest wire — byte
        identity between the local WAL and the remote copy is the
        robustness contract, so there is exactly one encoder."""
        self._f.write(line)

    def append(self, o: "Op") -> None:
        # the appending thread's open span (core.run's client/invoke
        # wraps the completion append) becomes the record's `c`
        # envelope field — resolved OUTSIDE the WAL lock, it belongs
        # to this thread alone
        from jepsen_tpu import trace as trace_mod
        ctx = trace_mod.current_ctx()
        with self.lock:
            if self._dead:
                return
            try:
                # frame_line embeds the canonical payload verbatim (it
                # is itself JSON) — the reader re-derives the crc from
                # it alone.  `w` (append wall clock) rides outside the
                # guarded payload: follow()-based consumers measure
                # detection lag from it; recover() ignores it.
                self._write_line(frame_line(
                    o.to_dict(), self._n,
                    # lint: wall-ok(advisory envelope stamp; recovery orders by i/crc, never w)
                    wall=time.time(), ctx=ctx))
                self._f.flush()
                if self.fsync:
                    t0 = time.monotonic()
                    os.fsync(self._f.fileno())
                    if self.telemetry is not None:
                        self.telemetry.observe_wal_fsync(
                            time.monotonic() - t0)
                seq = self._n
                self._n += 1
            except Exception:
                self._dead = True
                log.warning("history WAL write failed; continuing "
                            "without crash-safety", exc_info=True)
                return
        self._post_sync(seq, ctx)

    def _post_sync(self, seq: int, ctx: Optional[str]) -> None:
        """Post-durability hook, called (outside the lock) after a
        record is flushed — and fsynced when fsync is on.  Default:
        nothing.  StreamingWAL overrides it to ship a `mark` control
        frame stamping when record `seq` became durable, the fsync
        segment of the detection-lag decomposition (ISSUE 19)."""

    def close(self) -> None:
        with self.lock:
            self._dead = True
            try:
                self._f.close()
            except Exception:
                pass

    # Resumable read cursor over a WAL file (typically someone ELSE's
    # WAL — the live checker tails runs it did not write).  Static:
    # the follower needs no handle on the writer.
    follow = staticmethod(follow)


def recover(path) -> History:
    """Rebuild a well-formed History from a (possibly truncated) WAL.

    Reads records in order, stopping at the first line that fails to
    parse, fails its crc check, or breaks the sequence — everything
    past a tear is unattributable, so recovery trusts exactly the
    intact prefix.  Invocations without a completion in that prefix are
    closed with synthesized `:info` completions (indeterminate: the op
    may or may not have taken effect), so `core.analyze` and the
    checkpointed checkers can verify the result directly.

    The returned History carries a `recovery` attribute:
        {"ops": <recovered op count>, "closed": <synthesized :info>,
         "torn": <True when the file ended mid-record or failed a
                  guard>, "stop_reason": <str or None>}
    """
    p = Path(path)
    seg = follow(p)                      # one full-file cursor read
    ops: list[Op] = list(seg.ops)
    stop_reason = seg.stop_reason
    if stop_reason is None and seg.tail_bytes:
        stop_reason = (f"incomplete trailing record "
                       f"({seg.tail_bytes} bytes)")

    # Close open invocations as :info (knossos treats such processes as
    # crashed; the invocation stays concurrent to everything after it).
    open_by_process: dict[Any, Op] = {}
    for o in ops:
        if o.is_invoke:
            open_by_process[o.process] = o
        else:
            open_by_process.pop(o.process, None)
    last_time = max((o.time for o in ops if o.time is not None), default=0)
    closed = 0
    for inv in sorted(open_by_process.values(),
                      key=lambda o: o.index if o.index is not None else 0):
        ops.append(inv.assoc(type=INFO, time=last_time,
                             error="wal-recover: open at crash"))
        closed += 1

    h = History(ops).index()
    h.recovery = {"ops": len(ops) - closed, "closed": closed,
                  "torn": stop_reason is not None,
                  "stop_reason": stop_reason}
    if stop_reason or closed:
        log.warning("WAL recovery %s: %d ops, %d open invocations "
                    "closed as :info%s", p, len(ops) - closed, closed,
                    f" ({stop_reason})" if stop_reason else "")
    return h


def history_latencies(h: History) -> list[tuple[Op, float]]:
    """(completed-invocation, latency-ns) pairs for client ops;
    util.clj:598-632."""
    out = []
    for inv, comp in History(h).pairs():
        if (comp is not None and inv.time is not None
                and comp.time is not None and isinstance(inv.process, int)
                and inv.process >= 0):
            out.append((inv.assoc(completion=comp), comp.time - inv.time))
    return out


def nemesis_intervals(h: History) -> list[tuple[Optional[Op], Optional[Op]]]:
    """Start/stop op pairs for nemesis activity windows (util.clj:634)."""
    out = []
    start = None
    for o in h:
        if o.process != NEMESIS and o.process != "nemesis":
            continue
        if o.f == "start" and o.is_invoke and start is None:
            start = o
        elif o.f == "stop" and not o.is_invoke and start is not None:
            out.append((start, o))
            start = None
    if start is not None:
        out.append((start, None))
    return out
