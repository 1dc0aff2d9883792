"""Dependency inference: observed txn history -> typed edge planes.

From the observed values of a *recoverable* transactional workload
(every write unique per key — `workloads/list_append.py`,
`workloads/rw_register.py`), derive per-key version orders and emit
one boolean adjacency plane per dependency type over committed
transactions:

    ww  write-write:  Tv installed a version, Tw installed a later one
    wr  write-read:   Tw installed the version Tr observed
    rw  anti-dep:     Tr observed a version preceding Tw's write
    po  process:      same worker process, consecutive txns
    rt  realtime:     Tw completed before Tr invoked

Soundness discipline (the property the whole subsystem leans on —
every reported cycle must exist in the real DSG):

  * list-append: the version order of key k is recovered from observed
    list states, which must form a prefix chain (longest read wins;
    a non-prefix read is itself an anomaly, `incompatible-order`).
  * rw-register: version order uses *evidence only* — the initial nil
    precedes everything, and a txn that read u before writing v
    proves u ≺ v (write-follows-read).  An emitted ww/rw edge over a
    non-adjacent version pair stands for a real edge followed by a
    ww-path, so cycle existence and rw-edge counts (what the Adya
    classification keys on) are preserved.
  * reads already condemned as G1a (aborted/garbage read) or G1b
    (intermediate read) contribute NO dependency edges: their version
    positions are unreliable, and the direct anomaly already carries
    the report.

G1a and G1b are detected inline during this pass; cycles are the
device kernels' job (`jepsen_tpu.ops.elle_graph`).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu import txn as mop
from jepsen_tpu.history import History

_MISS = object()

# Fixed plane order — ops/elle_graph.py indexes by position.
PLANES = ("ww", "wr", "rw", "po", "rt")
DEP_PLANES = ("ww", "wr", "rw")

LIST_APPEND = "list-append"
RW_REGISTER = "rw-register"


@dataclasses.dataclass(eq=False)
class Inference:
    """Everything the cycle kernels and the report need.

    The dependency and process edges are kept as per-plane edge lists
    (`lists`: ww, wr, rw, po) and the realtime order as the txns'
    invoke and completion indices (`order_idx`), from which
    `packed_stacked` builds the bit-packed planes directly: rt as
    nested rows (`_rt_pack_into`), the rest by sparse word insertion.
    The dense planes and their [P, n, n] stack are built on first
    access only, for the dense tiers and the callers that read them;
    the mesh tier never touches them."""

    txns: list                    # (invoke, ok) Op pairs, completion order
    direct: dict                  # anomaly name -> [witness dicts]
    workload: str
    lists: dict                   # LIST_PLANES name -> (src i64[], dst i64[])
    order_idx: tuple              # (invoke i64[n], ok i64[n]); -1 unknown
    meta: dict = dataclasses.field(default_factory=dict)
    predicate: Optional[dict] = None    # {"prw": (src, dst), "reads": n}
    _planes: Optional[dict] = dataclasses.field(
        default=None, init=False, repr=False)

    @property
    def n(self) -> int:
        return len(self.txns)

    @property
    def planes(self) -> dict:
        """plane name -> bool [n, n], built on first access."""
        if self._planes is None:
            n = self.n
            planes = {}
            for p in PLANES:
                if p == "rt":
                    planes[p] = _rt_rows(*self.order_idx, 0, n)
                    continue
                m = np.zeros((n, n), bool)
                src, dst = self.lists[p]
                if len(src):
                    m[src, dst] = True
                planes[p] = m
            self._planes = planes
        return self._planes

    def stacked(self) -> np.ndarray:
        """Planes as one [len(PLANES), n, n] bool array."""
        return np.stack([self.planes[p] for p in PLANES])

    def packed_stacked(self, n_pad: Optional[int] = None,
                       n_dev: int = 1) -> np.ndarray:
        """Planes as one bit-packed uint32 [len(PLANES), n_pad, W]
        stack, with no dense plane on the way: list planes by sparse
        word insertion (ops.elle_mesh.set_bits, on the native ingest
        layer), rt as nested rows (`_rt_pack_into`, under the
        trace-only `jepsen.pack.rt`).  Equal to
        elle_mesh.pack_planes(self.stacked())."""
        from jepsen_tpu.ops import elle_mesh
        if n_pad is None:
            n_pad = elle_mesh.pad_for_mesh(self.n, n_dev)
        out = np.zeros((len(PLANES), n_pad, n_pad // 32), np.uint32)
        for pi, p in enumerate(PLANES):
            if p == "rt":
                with telemetry.trace("pack.rt"):
                    _rt_pack_into(out[pi], *self.order_idx)
            else:
                elle_mesh.set_bits(out[pi], *self.lists[p])
        return out


# planes held as edge lists; rt is held as the order indices
LIST_PLANES = ("ww", "wr", "rw", "po")


def _rt_rows(inv: np.ndarray, ok: np.ndarray, r0: int,
             r1: int) -> np.ndarray:
    """Rows r0..r1 of the realtime plane, bool [r1 - r0, n]: a -> b iff
    a completed before b invoked, both indices known, a != b."""
    known = (inv >= 0) & (ok >= 0)
    inv_k = np.where(known, inv, -1)          # unknown b: no edge in
    rows = (ok[r0:r1, None] < inv_k[None, :]) & known[r0:r1, None]
    i = np.arange(r0, r1)
    rows[i - r0, i] = False
    return rows


def _rt_pack_into(plane: np.ndarray, inv: np.ndarray,
                  ok: np.ndarray) -> None:
    """The realtime plane packed into `plane` [n_pad, W] (bit b of word
    w is column 32w + b), rows 0..n written whole.

    a -> b iff ok[a] < inv[b], an interval order, so its rows nest:
    taken in descending ok, each known row is the one before it plus the
    columns whose invoke lies between the two completions.  Column b
    first appears at position k_b, the number of known rows with
    ok >= inv[b] (a searchsorted on the sorted ok; counting ties there
    is what keeps the < strict), and stays in every row after.  So one
    bit per column goes into a delta, a prefix OR down the delta gives
    every row, and the rows go back to txn order: a reversed slice
    where txns are in completion order (`collect_txns`), a scatter
    otherwise.  Unknown rows stay zero, unknown columns are never set,
    and the diagonal (a txn invoked after its own completion) is
    cleared, as in `_rt_rows`."""
    from jepsen_tpu.ops import elle_mesh
    n = len(inv)
    w = -(-n // 32)
    known = np.flatnonzero((inv >= 0) & (ok >= 0))
    rows = known[np.argsort(-ok[known], kind="stable")]
    k = len(rows) - np.searchsorted(ok[rows[::-1]], inv[known], "left")
    enter = k < len(rows)
    delta = np.zeros((len(rows), w), np.uint32)
    elle_mesh.set_bits(delta, k[enter], known[enter])
    np.bitwise_or.accumulate(delta, axis=0, out=delta)
    if len(rows) == n and (rows == np.arange(n - 1, -1, -1)).all():
        plane[:n, :w] = delta[::-1]
    else:
        plane[:n, :w] = 0
        plane[rows, :w] = delta
    own = known[ok[known] < inv[known]]
    elle_mesh.clear_bits(plane, own, own)


def _rt_count(inv: np.ndarray, ok: np.ndarray) -> int:
    """Edges of the realtime plane, without building it."""
    known = (inv >= 0) & (ok >= 0)
    inv_k, ok_k = inv[known], ok[known]
    later = np.sort(inv_k)
    n_after = len(later) - np.searchsorted(later, ok_k, side="right")
    return int(n_after.sum() - np.count_nonzero(ok_k < inv_k))


def _edge_counts(lists: dict, order_idx: tuple, n: int) -> dict:
    """Unique edges per plane: what the dense planes' sums read."""
    out = {}
    for p in PLANES:
        if p == "rt":
            out[p] = _rt_count(*order_idx)
        else:
            src, dst = lists[p]
            out[p] = int(np.unique(src * max(n, 1) + dst).size)
    return out


class _Edges:
    """Edge accumulator: per-plane (src, dst) edges of the LIST_PLANES,
    one at a time (`add`) or as whole arrays (`extend`), handed on in
    the order they came as int64 arrays (`arrays`)."""

    def __init__(self):
        self._src = {p: [] for p in LIST_PLANES}
        self._dst = {p: [] for p in LIST_PLANES}
        self._runs = {p: [] for p in LIST_PLANES}   # (src, dst) arrays

    def add(self, plane: str, a: int, b: int) -> None:
        if a == b or a is None or b is None:
            return
        self._src[plane].append(a)
        self._dst[plane].append(b)

    def extend(self, plane: str, src: np.ndarray, dst: np.ndarray) -> None:
        """Edges src[j] -> dst[j] in turn, after those so far, with
        `add`'s a != b filter."""
        self._close(plane)
        keep = src != dst
        self._runs[plane].append((src[keep], dst[keep]))

    def _close(self, plane: str) -> None:
        if self._src[plane]:
            self._runs[plane].append(
                (np.asarray(self._src[plane], np.int64),
                 np.asarray(self._dst[plane], np.int64)))
            self._src[plane], self._dst[plane] = [], []

    def arrays(self) -> dict:
        out = {}
        for p in LIST_PLANES:
            self._close(p)
            runs = self._runs[p] or [(np.zeros(0, np.int64),) * 2]
            out[p] = tuple(np.concatenate(side).astype(np.int64, copy=False)
                           for side in zip(*runs))
        return out


def txn_mops(okop) -> list:
    return [m for m in (okop.value or []) if mop.is_op(m)]


def detect_workload(history) -> str:
    """Sniff ALL ops (a failed append still marks the workload)."""
    for o in History(history):
        if isinstance(o.value, (list, tuple)):
            for m in o.value:
                if mop.is_op(m) and mop.is_append(m):
                    return LIST_APPEND
    return RW_REGISTER


def collect_txns(history):
    """(ok_pairs, failed_writes, indeterminate_writes): ok txns as
    (invoke, ok) pairs in completion order; the (k, v) write/append
    sets of failed txns (definitely didn't commit -> reading one is
    G1a) and of info txns (may have committed -> reading one is NOT an
    anomaly, but the writer isn't a graph node)."""
    hist = History(history)
    inv: dict = {}
    ok_pairs, failed, indet = [], set(), set()

    def writes_of(v):
        return {(mop.key(m), mop.value(m)) for m in (v or [])
                if mop.is_op(m) and (mop.is_write(m) or mop.is_append(m))
                and not isinstance(mop.value(m), (list, dict, set))}

    for o in hist:
        if not isinstance(o.value, (list, tuple)) or isinstance(
                o.value, str):
            continue
        if o.value and not all(mop.is_op(m) for m in o.value):
            continue
        if o.is_invoke:
            inv[o.process] = o
        elif o.process in inv:
            first = inv.pop(o.process)
            if o.is_ok:
                ok_pairs.append((first, o))
            elif o.is_fail:
                failed |= writes_of(first.value)
            else:                    # info: indeterminate
                indet |= writes_of(first.value)
    # invocations never completed are indeterminate too
    for o in inv.values():
        indet |= writes_of(o.value)
    return ok_pairs, failed, indet


def _order_planes(txns: list, edges: _Edges) -> tuple:
    """po: consecutive txns of one process, as edges.  rt (ok strictly
    before invoke) is returned as its source, the txns' (invoke, ok)
    history indices (-1 unknown): the plane itself is O(n^2)."""
    by_proc: dict = {}
    for i, (inv, _) in enumerate(txns):
        by_proc.setdefault(inv.process, []).append(i)
    for seq in by_proc.values():
        for a, b in zip(seq, seq[1:]):
            edges.add("po", a, b)
    inv_idx = np.array([inv.index if inv.index is not None else -1
                        for inv, _ in txns], np.int64)
    ok_idx = np.array([ok.index if ok.index is not None else -1
                       for _, ok in txns], np.int64)
    return inv_idx, ok_idx


# ---------------------------------------------------------------------------
# list-append
# ---------------------------------------------------------------------------

def _heads(x: np.ndarray) -> np.ndarray:
    """Mask of the first of each run of equal values in `x`."""
    h = np.ones(len(x), bool)
    h[1:] = x[1:] != x[:-1]
    return h


def _tails(x: np.ndarray) -> np.ndarray:
    """Mask of the last of each run of equal values in `x`."""
    t = np.ones(len(x), bool)
    t[:-1] = x[1:] != x[:-1]
    return t


def _segments(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """starts[i], starts[i] + 1, ... counts[i] of them, for each i in
    turn: the flat indices of a run of segments."""
    ends = np.cumsum(counts)
    return (np.repeat(starts - ends + counts, counts)
            + np.arange(int(ends[-1]) if len(ends) else 0))


# a read's classification, in the order of precedence
_CLEAN, _ABORTED, _GARBAGE, _G1B, _INCOMPATIBLE = range(5)


def _infer_list_append(txns, failed, indet, edges: _Edges):
    direct: dict = {}

    def flag(name, i, m, **kw):
        direct.setdefault(name, []).append(
            dict({"op": txns[i][1].to_dict(), "mop": list(m)}, **kw))

    # the walk: a code per key, and per key a code per version (a value
    # some committed txn appends); each read's txn, key and state, each
    # append's key, txn and version.  duplicate-elements is a read that
    # shows one element twice (Elle's definition; the read gives no
    # edge) or a value two committed txns append, flagged in txn order
    kcode: dict = {}              # key -> code
    vcode: list = []              # per key code: {value: version code}
    writer: list = []             # per version: its committed appender
    a_key, a_txn, a_ver = [], [], []
    r_txn, r_key, r_state, r_mop = [], [], [], []
    for i, (_, okop) in enumerate(txns):
        for m in txn_mops(okop):
            if mop.is_read(m):
                s = mop.value(m)
                if s is None:
                    s = []
                if not isinstance(s, (list, tuple)):
                    continue
                if len(set(s)) != len(s):
                    flag("duplicate-elements", i, m)
                    continue
                kc = kcode.setdefault(mop.key(m), len(kcode))
                if kc == len(vcode):
                    vcode.append({})
                r_txn.append(i)
                r_key.append(kc)
                r_state.append(s)
                r_mop.append(m)
            elif mop.is_append(m):
                kc = kcode.setdefault(mop.key(m), len(kcode))
                if kc == len(vcode):
                    vcode.append({})
                c = vcode[kc].setdefault(mop.value(m), len(writer))
                if c == len(writer):
                    writer.append(i)
                elif writer[c] != i:
                    flag("duplicate-elements", i, m,
                         other=txns[writer[c]][1].to_dict())
                    continue
                a_key.append(kc)
                a_txn.append(i)
                a_ver.append(c)

    # failed and indeterminate writes of the keys walked take codes too
    # (with no writer); an element with none (code -1) is garbage.  Code
    # -1 indexes the last slot of each per-version array, which no
    # version holds
    marks = []
    for pairs in (failed, indet):
        cs = []
        for k, v in pairs:
            kc = kcode.get(k)
            if kc is not None:
                c = vcode[kc].setdefault(v, len(writer))
                if c == len(writer):
                    writer.append(-1)
                cs.append(c)
        marks.append(cs)
    nv, nk, nr = len(writer), len(vcode), len(r_txn)
    v_writer = np.asarray(writer + [-1], np.int64)
    v_failed = np.zeros(nv + 1, bool)
    v_failed[marks[0]] = True
    v_indet = np.zeros(nv + 1, bool)
    v_indet[marks[1]] = True

    # the reads' elements, flat and coded
    r_txn = np.asarray(r_txn, np.int64)
    r_key = np.asarray(r_key, np.int64)
    r_len = np.fromiter(map(len, r_state), np.int64, nr)
    r_start = np.cumsum(r_len) - r_len
    n_el = int(r_len.sum())
    gets = [d.get for d in vcode]
    missing = itertools.repeat(-1)
    e_ver = np.fromiter(itertools.chain.from_iterable(
        map(gets[kc], s, missing) for kc, s in zip(r_key.tolist(), r_state)),
        np.int64, n_el)
    e_read = np.repeat(np.arange(nr), r_len)
    e_pos = np.arange(n_el) - r_start[e_read]
    e_txn = r_txn[e_read]
    e_w = v_writer[e_ver]

    # version order = longest prefix chain: each key's first longest
    # read (the order read), and each version's place in it
    ne = np.flatnonzero(r_len > 0)
    by_len = ne[np.lexsort((-r_len[ne], r_key[ne]))]
    best = by_len[_heads(r_key[by_len])]     # ascending key code
    order_read = np.full(nk, -1)
    order_read[r_key[best]] = best
    _, first = np.unique(r_key[ne], return_index=True)
    kseq = r_key[best][np.argsort(ne[first])]  # by first nonempty read
    big = int(r_len.max(initial=0)) + 1       # absent: after every read
    v_place = np.full(nv + 1, big)
    oe = _segments(r_start[best], r_len[best])
    coded = e_ver[oe] >= 0
    v_place[e_ver[oe][coded]] = e_pos[oe][coded]
    # a prefix read matches its key's order read at each position
    e_ord = e_ver[r_start[order_read[r_key[e_read]]] + e_pos]
    r_prefix = np.ones(nr, bool)
    r_prefix[e_read[e_ord != e_ver]] = False

    # each (key, txn)'s appends in mop order, grouped in first-append
    # order (within a key, txn order: the walk is in txn order); its
    # tail version and the last place its appends take (absent last);
    # each key's appenders by (last place, txn).  A clean read of the
    # first L versions has seen exactly the appenders placed below L
    a_key = np.asarray(a_key, np.int64)
    by_key = np.argsort(a_key, kind="stable")
    a_key = a_key[by_key]
    a_txn = np.asarray(a_txn, np.int64)[by_key]
    a_ver = np.asarray(a_ver, np.int64)[by_key]
    g_head = _heads(a_key * len(txns) + a_txn)
    g_key, g_txn = a_key[g_head], a_txn[g_head]
    v_tail = np.full(nv + 1, -1)                # its appender's tail
    v_tail[a_ver] = a_ver[_tails(a_key * len(txns) + a_txn)][
        np.cumsum(g_head) - 1]
    g_last = (np.maximum.reduceat(v_place[a_ver], np.flatnonzero(g_head))
              if len(a_ver) else np.zeros(0, np.int64))
    by_last = np.lexsort((g_txn, g_last, g_key))
    s_txn = g_txn[by_last]
    s_at = g_key[by_last] * (big + 1) + g_last[by_last]
    k_end = np.searchsorted(g_key[by_last], np.arange(nk), "right")

    def unseen(kc, length):
        """(start, count) in s_txn of the appenders of keys `kc` that a
        prefix of `length` versions has not seen."""
        start = np.searchsorted(s_at, kc * (big + 1) + length)
        return start, k_end[kc] - start

    # classify each read; only clean prefix reads contribute edges.
    # G1a: its first element that a failed txn appended (aborted) or no
    # txn may have (garbage)
    r_kind = np.zeros(nr, np.int8)
    bad = np.flatnonzero(v_failed[e_ver] | ((e_w < 0) & ~v_indet[e_ver]))
    bad = bad[_heads(e_read[bad])]
    r_kind[e_read[bad]] = np.where(v_failed[e_ver[bad]], _ABORTED, _GARBAGE)
    # G1b: an appender whose earlier append the read saw and whose last
    # it did not (the first such in first-append order, so the least
    # txn); membership by place in a prefix read, else by the read's
    # own elements
    cand = np.flatnonzero((e_w >= 0) & (e_w != e_txn)
                          & (r_kind[e_read] == _CLEAN))
    tail = v_tail[e_ver[cand]]
    c_read = e_read[cand]
    has_tail = v_place[tail] < r_len[c_read]
    off = ~r_prefix[c_read]
    if off.any():
        el = np.flatnonzero(~r_prefix[e_read])
        has_tail[off] = np.isin(c_read[off] * (nv + 1) + tail[off],
                                e_read[el] * (nv + 1) + e_ver[el])
    g1b = cand[~has_tail]
    g1b = g1b[np.lexsort((e_w[g1b], e_read[g1b]))]
    g1b = g1b[_heads(e_read[g1b])]
    r_kind[e_read[g1b]] = _G1B
    r_g1b = np.full(nr, -1)
    r_g1b[e_read[g1b]] = e_w[g1b]
    # incompatible-order: off its key's order
    r_kind[(r_kind == _CLEAN) & ~r_prefix] = _INCOMPATIBLE
    for r in np.flatnonzero(r_kind).tolist():
        i, m, kind = int(r_txn[r]), r_mop[r], r_kind[r]
        if kind == _ABORTED:
            flag("G1a", i, m, kind="aborted")
        elif kind == _GARBAGE:
            flag("G1a", i, m, kind="garbage")
        elif kind == _G1B:
            flag("G1b", i, m, writer=txns[r_g1b[r]][1].to_dict())
        else:
            flag("incompatible-order", i, m,
                 longest=list(r_state[order_read[r_key[r]]]))
    telemetry.REGISTRY.counter(telemetry.ELLE_INFER_READS,
                               path="array").inc(nr)

    # wr: the last element whose writer is a committed node other than
    # the reader itself (read-your-own-write is not an external
    # observation; the one before it is)
    clean = np.flatnonzero(r_kind == _CLEAN)
    wr = np.flatnonzero((r_kind[e_read] == _CLEAN) & (e_w >= 0)
                        & (e_w != e_txn))
    wr = wr[_tails(e_read[wr])]
    edges.extend("wr", e_w[wr], e_txn[wr])
    # rw: lists grow monotonically, so ANY committed append not in the
    # observed state was installed after it — the next observed version
    # plus every unobserved committed append (sound: the emitted edge
    # stands for rw + a ww-path)
    start, count = unseen(r_key[clean], r_len[clean])
    edges.extend("rw", np.repeat(r_txn[clean], count),
                 s_txn[_segments(start, count)])

    # ww: consecutive committed writers along each key's version order,
    # then order-tail -> unobserved appends (same monotonicity argument:
    # absent from the longest observed state => later); key by key, in
    # the order of their first nonempty read
    ob = order_read[kseq]
    oe = _segments(r_start[ob], r_len[ob])
    w, rank = e_w[oe], np.repeat(np.arange(len(kseq)), r_len[ob])
    w, rank = w[w >= 0], rank[w >= 0]
    step = np.flatnonzero(rank[1:] == rank[:-1])      # w[j] -> w[j + 1]
    last = np.flatnonzero(_tails(rank))
    start, count = unseen(kseq[rank[last]], r_len[ob[rank[last]]])
    src = np.concatenate([w[step], np.repeat(w[last], count)])
    dst = np.concatenate([w[step + 1], s_txn[_segments(start, count)]])
    at = np.argsort(np.concatenate([2 * rank[step],       # chain, then tail
                                    2 * np.repeat(rank[last], count) + 1]),
                    kind="stable")
    edges.extend("ww", src[at], dst[at])

    # bounded: results.json must not scale with history size
    keys = list(kcode)
    orders = {keys[k]: r_state[order_read[k]] for k in kseq.tolist()}
    seen_key = np.zeros(nk, bool)
    seen_key[a_key] = True
    seen_key[kseq] = True
    meta = {"keys": int(seen_key.sum()), "version-orders": {
        repr(k): (list(v[:32]) + ["..."] if len(v) > 32 else list(v))
        for k, v in sorted(orders.items(),
                           key=lambda kv: repr(kv[0]))[:8]}}
    return direct, meta


# ---------------------------------------------------------------------------
# rw-register
# ---------------------------------------------------------------------------

def _infer_rw_register(txns, failed, indet, edges: _Edges):
    direct: dict = {}
    meta: dict = {}

    def flag(name, i, m, **kw):
        direct.setdefault(name, []).append(
            dict({"op": txns[i][1].to_dict(), "mop": list(m)}, **kw))

    writer_of: dict = {}          # (k, v) -> txn of the FINAL write of v
    intermediate: dict = {}       # (k, v) -> txn whose non-final write v was
    finals_by_txn: list = []      # per txn: {k: final value written}
    for i, (_, okop) in enumerate(txns):
        last: dict = {}
        for m in txn_mops(okop):
            if mop.is_write(m):
                k = mop.key(m)
                if k in last:
                    intermediate[(k, last[k])] = i
                last[k] = mop.value(m)
        for k, v in list(last.items()):
            if (k, v) in writer_of and writer_of[(k, v)] != i:
                flag("duplicate-elements", i, ["w", k, v],
                     other=txns[writer_of[(k, v)]][1].to_dict())
                del last[k]
                continue
            writer_of[(k, v)] = i
        finals_by_txn.append(last)

    # clean reads + version-order evidence (write-follows-read).  A
    # read AFTER the txn's own write to the key observes itself; only
    # pre-write reads are external observations.
    clean_reads: list = []        # (txn, key, value read)
    evidence: dict = {}           # key -> {u: set of direct successors v}
    for i, (_, okop) in enumerate(txns):
        wrote: set = set()
        pre_read: dict = {}
        for m in txn_mops(okop):
            k = mop.key(m)
            if mop.is_write(m):
                wrote.add(k)
                continue
            if not mop.is_read(m) or k in wrote:
                continue
            v = mop.value(m)
            if isinstance(v, (list, dict, set)):
                continue             # not a register observation
            if v is not None:
                if (k, v) in failed:
                    flag("G1a", i, m, kind="aborted")
                    continue
                if (k, v) in intermediate:
                    t = intermediate[(k, v)]
                    if t != i:
                        flag("G1b", i, m, writer=txns[t][1].to_dict())
                        continue
                if writer_of.get((k, v)) is None:
                    if (k, v) not in indet:
                        flag("G1a", i, m, kind="garbage")
                    continue          # indeterminate writer: no edges
            clean_reads.append((i, k, v))
            pre_read.setdefault(k, v)
        for k, v in finals_by_txn[i].items():
            if k in pre_read:
                evidence.setdefault(k, {}).setdefault(
                    pre_read[k], set()).add(v)

    # per-key evidence DAG sanity: a cycle means the observations are
    # not explainable by ANY version order.  Iterative coloring — the
    # write-follows-read chain of a counter-shaped key is as long as
    # the history.
    for k, succ in evidence.items():
        color: dict = {}
        bad = False
        for root in list(succ):
            if color.get(root, 0):
                continue
            stack = [(root, iter(succ.get(root, ())))]
            color[root] = 1
            while stack and not bad:
                u, it = stack[-1]
                v = next(it, None)
                if v is None:
                    color[u] = 2
                    stack.pop()
                elif color.get(v, 0) == 1:
                    bad = True
                elif color.get(v, 0) == 0:
                    color[v] = 1
                    stack.append((v, iter(succ.get(v, ()))))
            if bad:
                break
        if bad:
            flag("cyclic-version-order", 0, ["r", k, None], key=repr(k))
            evidence[k] = {}

    # ww + wr + rw from evidence
    for k, succ in evidence.items():
        for u, vs in succ.items():
            wu = writer_of.get((k, u)) if u is not None else None
            for v in vs:
                wv = writer_of.get((k, v))
                if wu is not None and wv is not None:
                    edges.add("ww", wu, wv)
    for i, k, v in clean_reads:
        if v is not None:
            w = writer_of.get((k, v))
            if w is not None:
                edges.add("wr", w, i)
        for nxt in evidence.get(k, {}).get(v, ()):
            wv = writer_of.get((k, nxt))
            if wv is not None:
                edges.add("rw", i, wv)

    meta["evidence-keys"] = len(evidence)
    return direct, meta


# ---------------------------------------------------------------------------
# predicate reads (ISSUE 20): phantom evidence for G1/G2-predicate
# ---------------------------------------------------------------------------

def _infer_predicate(txns, failed, indet, edges: _Edges):
    """Evidence from ["rp", pred, observed] micro-ops, workload-
    independent (runs after either item pass; zero rp mops => no-op).

      * an observed (k, v) whose writer failed (or doesn't exist and
        isn't indeterminate) is a DIRECT G1-predicate flag — a dirty/
        garbage predicate read breaks read-committed on its own;
      * an observed (k, v) with a committed writer is an ordinary wr
        observation (the predicate read read that version);
      * a committed final write to a key INSIDE the predicate's match
        set (`txn.predicate_keys`) that the read observed NOTHING for
        is a phantom: the write can only have been installed after
        the read's snapshot (nil-first version order), so it emits a
        predicate anti-dependency `prw` read -> writer.  Non-nil
        mismatches get no edge (conservative: without a version-order
        witness the unseen version could be older).

    Returns (direct, (prw_src, prw_dst)); prw is NOT one of PLANES —
    the lattice engine carries it as its own packed plane.
    """
    direct: dict = {}

    def flag(name, i, m, **kw):
        direct.setdefault(name, []).append(
            dict({"op": txns[i][1].to_dict(), "mop": list(m)}, **kw))

    any_rp = any(mop.is_predicate_read(m)
                 for _, okop in txns for m in txn_mops(okop))
    if not any_rp:
        return direct, ([], [])

    writer_of: dict = {}          # (k, v) -> committed writer txn
    finals: dict = {}             # key -> {txn: final value written}
    for i, (_, okop) in enumerate(txns):
        last: dict = {}
        for m in txn_mops(okop):
            if mop.is_write(m):
                last[mop.key(m)] = mop.value(m)
            elif mop.is_append(m):
                k, v = mop.key(m), mop.value(m)
                writer_of.setdefault((k, v), i)
                finals.setdefault(k, {})[i] = v
        for k, v in last.items():
            writer_of.setdefault((k, v), i)
            finals.setdefault(k, {})[i] = v

    prw_src: list = []
    prw_dst: list = []
    for i, (_, okop) in enumerate(txns):
        for m in txn_mops(okop):
            if not mop.is_predicate_read(m):
                continue
            observed = mop.value(m)
            if not isinstance(observed, dict):
                observed = {}
            for k, v in observed.items():
                if v is None:
                    continue
                if (k, v) in failed:
                    flag("G1-predicate", i, m, kind="aborted",
                         key=repr(k))
                    continue
                w = writer_of.get((k, v))
                if w is None:
                    if (k, v) not in indet:
                        flag("G1-predicate", i, m, kind="garbage",
                             key=repr(k))
                    continue
                if w != i:
                    edges.add("wr", w, i)
            for k in mop.predicate_keys(m):
                if observed.get(k) is not None:
                    continue       # saw a version; no phantom for k
                for t in finals.get(k, ()):
                    if t != i:
                        prw_src.append(i)
                        prw_dst.append(t)
    return direct, (prw_src, prw_dst)


# ---------------------------------------------------------------------------
# session-order plane families (ISSUE 20)
# ---------------------------------------------------------------------------

SESSION_PLANES = ("so_ww", "so_wr", "so_rw", "so_rr")


def txn_roles(txns) -> tuple:
    """(wrote, read) bool indicator vectors over committed txns — a
    predicate read counts as a read."""
    n = len(txns)
    wrote = np.zeros(n, bool)
    read = np.zeros(n, bool)
    for i, (_, okop) in enumerate(txns):
        for m in txn_mops(okop):
            if mop.is_write(m) or mop.is_append(m):
                wrote[i] = True
            elif mop.is_read(m) or mop.is_predicate_read(m):
                read[i] = True
    return wrote, read


def session_planes(txns) -> dict:
    """The transitively-closed session order (every ordered pair of
    one process's committed txns — `po`'s closure, built closed by
    construction) split into endpoint-role families:

        so_ww  writer -> writer     (monotonic-writes' defining edges)
        so_wr  writer -> reader     (read-your-writes')
        so_rw  reader -> writer     (writes-follow-reads')
        so_rr  reader -> reader     (monotonic-reads')

    A txn that both reads and writes puts its edges in every matching
    family; the lattice masks' priority chain disambiguates.  Returns
    {"planes": {name: bool [n, n]}, "edge_lists": {name: (src, dst)},
    "wrote": bool [n], "read": bool [n]}.
    """
    n = len(txns)
    wrote, read = txn_roles(txns)
    so = np.zeros((n, n), bool)
    by_proc: dict = {}
    for i, (inv, _) in enumerate(txns):
        by_proc.setdefault(inv.process, []).append(i)
    for seq in by_proc.values():
        for ai, a in enumerate(seq):
            for b in seq[ai + 1:]:
                so[a, b] = True
    fams = {"so_ww": so & np.outer(wrote, wrote),
            "so_wr": so & np.outer(wrote, read),
            "so_rw": so & np.outer(read, wrote),
            "so_rr": so & np.outer(read, read)}
    lists = {}
    for name, plane in fams.items():
        s, d = np.nonzero(plane)
        lists[name] = (s.astype(np.int64), d.astype(np.int64))
    return {"planes": fams, "edge_lists": lists,
            "wrote": wrote, "read": read}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def infer(history, workload: str = "auto") -> Inference:
    """Infer dependency planes + direct anomalies from a history.
    `workload`: "list-append", "rw-register", or "auto" (sniff for
    append micro-ops)."""
    if workload == "auto":
        workload = detect_workload(history)
    txns, failed, indet = collect_txns(history)
    edges = _Edges()
    if workload == LIST_APPEND:
        direct, meta = _infer_list_append(txns, failed, indet, edges)
    elif workload == RW_REGISTER:
        direct, meta = _infer_rw_register(txns, failed, indet, edges)
    else:
        raise ValueError(f"unknown elle workload {workload!r}")
    pred_direct, (prw_src, prw_dst) = _infer_predicate(
        txns, failed, indet, edges)
    for name, flags in pred_direct.items():
        direct.setdefault(name, []).extend(flags)
    order_idx = _order_planes(txns, edges)
    lists = edges.arrays()
    meta["txn-count"] = len(txns)
    meta["edge-counts"] = _edge_counts(lists, order_idx, len(txns))
    predicate = None
    if prw_src or "G1-predicate" in pred_direct:
        predicate = {"prw": (np.asarray(prw_src, np.int64),
                             np.asarray(prw_dst, np.int64)),
                     "reads": sum(
                         1 for _, okop in txns
                         for m in txn_mops(okop)
                         if mop.is_predicate_read(m))}
        meta["predicate-reads"] = predicate["reads"]
    return Inference(txns=txns, direct=direct, workload=workload,
                     lists=lists, order_idx=order_idx, meta=meta,
                     predicate=predicate)


# ---------------------------------------------------------------------------
# Incremental mode (ISSUE 18): streaming ops -> edge DELTAS
# ---------------------------------------------------------------------------
#
# Both one-shot passes above are *key-separable*: every flag and every
# dependency edge of key k is a pure function of (the ordered committed
# txns touching k, the failed set, the indeterminate set) — no
# cross-key coupling anywhere.  The incremental engine exploits that:
# it keeps per-key touch lists, marks a key dirty whenever any op
# could change its classification (a commit touching it, a fail/abort
# of one of its writers, a new in-flight write to it), and on drain()
# recomputes each dirty key's COMPLETE flag+edge contribution with a
# faithful single-key transcription of the one-shot logic, diffing it
# against the cached previous contribution.  Exactness is therefore by
# construction (pinned window-by-window by tests/test_live_txn.py's
# differential sweep), and the work per drain is proportional to the
# dirty keys, not the history.
#
# The diff is emitted as per-plane edge ADDS and REMOVES (an edge is
# shared by however many keys derive it — a refcount decides when a
# bit actually sets or clears).  Removals are classified for the warm
# closure downstream (ops/elle_mesh.classify_packed_warm):
#
#   * a removal is COVERED when the key's new edge set implies it
#     transitively (ww tail supersession w1->T becoming w1->w2->T, and
#     the wr last-writer analogue).  A covered edge stays inside the
#     closure of the exact set, so a warm-started closure that never
#     un-learns it is still the exact closure — by induction over
#     removal events, as long as every removal since the last cold
#     rebuild was covered at its removal time.
#   * anything else (a read condemned by a late G1a/G1b/
#     incompatible-order, an evidence wipe after cyclic-version-order)
#     is UNCOVERED: drain() raises `rebuild`, and the consumer must
#     rebuild closure cold from the (exact, bit-cleared) direct
#     planes.  Uncovered removals coincide with freshly-found direct
#     anomalies, so rebuilds are rare on clean streams.
#
# po is monotone (a process's next txn only appends to its chain); rt
# is handled per new txn in both directions, so no order edge is ever
# retracted.


def _writes_of(value):
    """(k, v) write/append pairs of one mop list — the collect_txns
    inner helper, shared with the incremental feed."""
    return {(mop.key(m), mop.value(m)) for m in (value or [])
            if mop.is_op(m) and (mop.is_write(m) or mop.is_append(m))
            and not isinstance(mop.value(m), (list, dict, set))}


class IncrementalInference:
    """Streaming twin of `infer()`: feed ops in WAL order, drain edge
    deltas + the current direct-anomaly map.  The whole state
    serializes to JSON (`to_state`/`from_state`) so a fleet takeover
    resumes mid-stream from a lease checkpoint."""

    # txn record layout: (process, inv_index, ok_index, value, ok_dict)
    _P, _INV, _OK, _VAL, _DICT = range(5)

    def __init__(self, workload: str):
        if workload not in (LIST_APPEND, RW_REGISTER):
            raise ValueError(f"unknown elle workload {workload!r}")
        self.workload = workload
        self.txns: list = []           # committed, completion order
        self.inflight: dict = {}       # process -> (inv_index, value)
        self.failed: set = set()       # (k, v) of failed writes
        self.indet_done: set = set()   # (k, v) of info-txn writes
        self.touch: dict = {}          # key -> [txn indices, ascending]
        self._inv_idx: list = []       # per txn, -1 when unknown
        self._ok_idx: list = []
        self._last_by_proc: dict = {}  # process -> last txn index (po)
        self._dirty: set = set()
        self._key_cache: dict = {}     # key -> (flags, frozenset edges)
        self._edge_ref: dict = {}      # (plane, a, b) -> key refcount
        self._ordered = 0              # txns already po/rt-emitted
        self._pending_po: list = []    # (a, b) awaiting drain

    @property
    def n(self) -> int:
        return len(self.txns)

    # -- feed ---------------------------------------------------------------

    def feed(self, op) -> None:
        """One history Op, in WAL order (gating and pairing mirror
        collect_txns exactly, including dangling-invoke-as-indet)."""
        v = op.value
        if not isinstance(v, (list, tuple)) or isinstance(v, str):
            return
        if v and not all(mop.is_op(m) for m in v):
            return
        if op.is_invoke:
            old = self.inflight.pop(op.process, None)
            if old is not None:
                # a re-invoke on a busy process drops the dangling
                # txn from the indeterminate set (collect_txns
                # overwrites inv[p]) — its write keys reclassify
                self._mark_writes_dirty(old[1])
            idx = op.index if isinstance(op.index, int) else -1
            self.inflight[op.process] = (idx, list(v))
            self._mark_writes_dirty(v)
            return
        got = self.inflight.pop(op.process, None)
        if got is None:
            return
        inv_index, inv_value = got
        if op.is_ok:
            i = len(self.txns)
            self.txns.append((op.process, inv_index,
                              op.index if isinstance(op.index, int)
                              else -1, list(v), op.to_dict()))
            self._inv_idx.append(inv_index)
            self._ok_idx.append(self.txns[i][self._OK])
            for m in v:
                if mop.is_predicate_read(m):
                    # predicate descriptors are list-shaped (not
                    # hashable keys) and their phantom evidence is a
                    # one-shot pass (`_infer_predicate`); the live
                    # tier's lattice classes come from the session
                    # planes, which rp mops don't touch
                    continue
                k = mop.key(m)
                seq = self.touch.setdefault(k, [])
                if not seq or seq[-1] != i:
                    seq.append(i)
                self._dirty.add(k)
            prev = self._last_by_proc.get(op.process)
            if prev is not None:
                self._pending_po.append((prev, i))
            self._last_by_proc[op.process] = i
        elif op.is_fail:
            w = _writes_of(inv_value)
            self.failed |= w
            self._dirty.update(k for k, _ in w)
        else:                          # info: indeterminate
            # membership in the effective indet set is unchanged (the
            # writes were already indeterminate while in flight)
            self.indet_done |= _writes_of(inv_value)

    def _mark_writes_dirty(self, value) -> None:
        self._dirty.update(k for k, _ in _writes_of(value))

    def _indet(self) -> set:
        out = set(self.indet_done)
        for _idx, v in self.inflight.values():
            out |= _writes_of(v)
        return out

    def _mops(self, i: int) -> list:
        return [m for m in self.txns[i][self._VAL] if mop.is_op(m)]

    # -- drain --------------------------------------------------------------

    def drain(self) -> dict:
        """Recompute dirty keys, diff, and return the delta:

            {"added":   [(plane, a, b), ...],
             "removed": [(plane, a, b), ...],   # already bit-clearable
             "rebuild": bool,   # an uncovered removal happened
             "n": txn count, "dirty_keys": recomputed key count}
        """
        indet = self._indet()
        added: list = []
        removed: list = []
        rebuild = False
        recompute = (self._recompute_append_key
                     if self.workload == LIST_APPEND
                     else self._recompute_register_key)
        ndirty = len(self._dirty)
        for k in list(self._dirty):
            flags, edges = recompute(k, indet)
            _old_flags, old_edges = self._key_cache.get(
                k, ((), frozenset()))
            for e in edges - old_edges:
                r = self._edge_ref.get(e, 0)
                if r == 0:
                    added.append(e)
                self._edge_ref[e] = r + 1
            for e in old_edges - edges:
                r = self._edge_ref.get(e, 0) - 1
                if r <= 0:
                    self._edge_ref.pop(e, None)
                    removed.append(e)
                    if not self._covered(e, edges):
                        rebuild = True
                else:
                    self._edge_ref[e] = r
            self._key_cache[k] = (tuple(flags), edges)
        self._dirty.clear()
        self._order_delta(added)
        return {"added": added, "removed": removed,
                "rebuild": rebuild, "n": self.n,
                "dirty_keys": ndirty}

    @staticmethod
    def _covered(e, new_edges: frozenset) -> bool:
        """True when the key's new edge set transitively implies the
        removed edge (so a warm closure keeping it stays exact)."""
        p, a, b = e
        if p == "ww":
            return any(q == "ww" and x == a
                       and ("ww", y, b) in new_edges
                       for q, x, y in new_edges)
        if p == "wr":
            return any(q == "ww" and x == a
                       and ("wr", y, b) in new_edges
                       for q, x, y in new_edges)
        return False                   # rw retractions always rebuild

    def _order_delta(self, added: list) -> None:
        """po/rt edges for txns committed since the last drain — both
        directions per new txn, so monotonicity is unconditional."""
        n = self.n
        start = self._ordered
        if start >= n:
            return
        for a, b in self._pending_po:
            added.append(("po", a, b))
        self._pending_po.clear()
        inv = np.asarray(self._inv_idx, np.int64)
        ok = np.asarray(self._ok_idx, np.int64)
        known = (inv >= 0) & (ok >= 0)
        idx = np.arange(n)
        for j in range(start, n):
            if known[j]:
                # incoming rt: every txn that completed before j
                # invoked (covers pairs among the new txns too)
                for i in np.nonzero((ok < inv[j]) & known
                                    & (idx != j))[0]:
                    added.append(("rt", int(i), j))
                # outgoing rt toward PRE-EXISTING txns (ok_j < inv_i
                # cannot hold under WAL-ordered indices, but indices
                # are caller-supplied — stay exact, not clever)
                if start:
                    for i in np.nonzero(
                            (ok[j] < inv[:start]) & known[:start])[0]:
                        added.append(("rt", j, int(i)))
        self._ordered = n

    # -- verdict inputs -----------------------------------------------------

    def direct(self) -> dict:
        """Current direct-anomaly map, exact for the fed prefix
        (payloads match the one-shot `infer().direct` witnesses)."""
        out: dict = {}
        for k in sorted(self._key_cache, key=repr):
            for name, payload in self._key_cache[k][0]:
                out.setdefault(name, []).append(payload)
        return out

    def meta(self) -> dict:
        return {"txn-count": self.n, "keys": len(self.touch),
                "inflight": len(self.inflight),
                "edges-live": len(self._edge_ref)}

    # -- per-key recomputes (single-key transcriptions of the one-shot
    #    passes; every flag payload is byte-compatible) ----------------------

    def _recompute_append_key(self, k, indet):
        flags: list = []
        edges: set = set()
        txns = self.txns

        def flag(name, i, m, **kw):
            flags.append((name, dict({"op": txns[i][self._DICT],
                                      "mop": list(m)}, **kw)))

        writer_of: dict = {}           # v -> txn index
        appends: dict = {}             # txn index -> [v, ...] mop order
        seq = self.touch.get(k, ())
        reads: list = []
        for i in seq:
            for m in self._mops(i):
                if mop.key(m) != k:
                    continue
                if mop.is_read(m):
                    s = mop.value(m)
                    if s is None:
                        s = []
                    if not isinstance(s, (list, tuple)):
                        continue
                    if len(set(s)) != len(s):
                        flag("duplicate-elements", i, m)
                        continue
                    reads.append((i, tuple(s), m))
                elif mop.is_append(m):
                    v = mop.value(m)
                    if v in writer_of and writer_of[v] != i:
                        flag("duplicate-elements", i, m,
                             other=txns[writer_of[v]][self._DICT])
                        continue
                    writer_of[v] = i
                    appends.setdefault(i, []).append(v)
        order: tuple = ()
        for i, s, m in reads:
            if len(s) > len(order):
                order = s
        for i, s, m in reads:
            bad = False
            for v in s:
                if (k, v) in self.failed:
                    flag("G1a", i, m, kind="aborted")
                    bad = True
                    break
                if writer_of.get(v) is None and (k, v) not in indet:
                    flag("G1a", i, m, kind="garbage")
                    bad = True
                    break
            if bad:
                continue
            seen = set(s)
            for t, vs in appends.items():
                if t == i or len(vs) < 2:
                    continue
                if any(v in seen for v in vs[:-1]) \
                        and vs[-1] not in seen:
                    flag("G1b", i, m, writer=txns[t][self._DICT])
                    bad = True
                    break
            if bad:
                continue
            if tuple(order[:len(s)]) != tuple(s):
                flag("incompatible-order", i, m, longest=list(order))
                continue
            for v in reversed(s):
                w = writer_of.get(v)
                if w is not None and w != i:
                    edges.add(("wr", w, i))
                    break
            seen2 = set(s)
            for t, vs in appends.items():
                if t != i and not seen2.issuperset(vs):
                    edges.add(("rw", i, t))
        prev = None
        for v in order:
            w = writer_of.get(v)
            if w is None:
                continue
            if prev is not None and prev != w:
                edges.add(("ww", prev, w))
            prev = w
        if prev is not None:
            observed = set(order)
            for t, vs in appends.items():
                if t != prev and not observed.issuperset(vs):
                    edges.add(("ww", prev, t))
        return flags, frozenset(edges)

    def _recompute_register_key(self, k, indet):
        flags: list = []
        edges: set = set()
        txns = self.txns

        def flag(name, i, m, **kw):
            flags.append((name, dict({"op": txns[i][self._DICT],
                                      "mop": list(m)}, **kw)))

        writer_of: dict = {}           # v -> txn of the FINAL write
        intermediate: dict = {}        # v -> txn whose non-final write
        finals: dict = {}              # txn index -> final value
        seq = self.touch.get(k, ())
        for i in seq:
            last = _MISS
            for m in self._mops(i):
                if mop.is_write(m) and mop.key(m) == k:
                    if last is not _MISS:
                        intermediate[last] = i
                    last = mop.value(m)
            if last is _MISS:
                continue
            if last in writer_of and writer_of[last] != i:
                flag("duplicate-elements", i, ["w", k, last],
                     other=txns[writer_of[last]][self._DICT])
                continue
            writer_of[last] = i
            finals[i] = last
        clean_reads: list = []         # (txn, value read)
        evidence: dict = {}            # u -> set of successor finals
        for i in seq:
            wrote = False
            pre_read = _MISS
            for m in self._mops(i):
                if mop.key(m) != k:
                    continue
                if mop.is_write(m):
                    wrote = True
                    continue
                if not mop.is_read(m) or wrote:
                    continue
                v = mop.value(m)
                if isinstance(v, (list, dict, set)):
                    continue
                if v is not None:
                    if (k, v) in self.failed:
                        flag("G1a", i, m, kind="aborted")
                        continue
                    if v in intermediate:
                        t = intermediate[v]
                        if t != i:
                            flag("G1b", i, m,
                                 writer=txns[t][self._DICT])
                            continue
                    if writer_of.get(v) is None:
                        if (k, v) not in indet:
                            flag("G1a", i, m, kind="garbage")
                        continue
                clean_reads.append((i, v))
                if pre_read is _MISS:
                    pre_read = v
            if i in finals and pre_read is not _MISS:
                evidence.setdefault(pre_read, set()).add(finals[i])
        succ = evidence
        color: dict = {}
        bad = False
        for root in list(succ):
            if color.get(root, 0):
                continue
            stack = [(root, iter(succ.get(root, ())))]
            color[root] = 1
            while stack and not bad:
                u, it = stack[-1]
                v = next(it, None)
                if v is None:
                    color[u] = 2
                    stack.pop()
                elif color.get(v, 0) == 1:
                    bad = True
                elif color.get(v, 0) == 0:
                    color[v] = 1
                    stack.append((v, iter(succ.get(v, ()))))
            if bad:
                break
        if bad:
            flag("cyclic-version-order", 0, ["r", k, None],
                 key=repr(k))
            succ = {}
        for u, vs in succ.items():
            wu = writer_of.get(u) if u is not None else None
            for v in vs:
                wv = writer_of.get(v)
                if wu is not None and wv is not None and wu != wv:
                    edges.add(("ww", wu, wv))
        for i, v in clean_reads:
            if v is not None:
                w = writer_of.get(v)
                if w is not None and w != i:
                    edges.add(("wr", w, i))
            for nxt in succ.get(v, ()):
                wv = writer_of.get(nxt)
                if wv is not None and wv != i:
                    edges.add(("rw", i, wv))
        return flags, frozenset(edges)

    # -- checkpoint serialization (lease sidecar payload) --------------------

    def to_state(self) -> dict:
        """JSON-able checkpoint of the WHOLE incremental state —
        caches and planes are derivable, so only the core facts ship:
        committed txns, in-flight invokes, failed/indet write sets.
        Raises TypeError/ValueError on non-JSON-able keys/values (the
        caller skips the checkpoint; full replay stays correct)."""
        import json
        state = {"workload": self.workload, "v": 1,
                 "txns": [[t[self._P], t[self._INV], t[self._OK],
                           t[self._VAL], t[self._DICT]]
                          for t in self.txns],
                 "inflight": [[p, idx, val] for p, (idx, val)
                              in self.inflight.items()],
                 "failed": [list(kv) for kv in sorted(
                     self.failed, key=repr)],
                 "indet": [list(kv) for kv in sorted(
                     self.indet_done, key=repr)]}
        json.dumps(state)              # fail fast, not at write time
        return state

    @classmethod
    def from_state(cls, state: dict) -> "IncrementalInference":
        """Rebuild from a checkpoint: bookkeeping is reconstructed,
        every key marked dirty — the first drain() re-emits the full
        edge set, from which the consumer rebuilds planes + closure
        cold (O(state), not O(WAL))."""
        inc = cls(state["workload"])
        for p, inv_i, ok_i, val, okd in state.get("txns") or []:
            i = len(inc.txns)
            inc.txns.append((p, int(inv_i), int(ok_i),
                             list(val), okd))
            inc._inv_idx.append(int(inv_i))
            inc._ok_idx.append(int(ok_i))
            for m in val:
                if not mop.is_op(m):
                    continue
                k = mop.key(m)
                seq = inc.touch.setdefault(k, [])
                if not seq or seq[-1] != i:
                    seq.append(i)
            inc._last_by_proc[p] = i
        # po chains replay from the rebuilt per-process order
        by_proc: dict = {}
        for i, t in enumerate(inc.txns):
            by_proc.setdefault(t[cls._P], []).append(i)
        for chain in by_proc.values():
            inc._pending_po.extend(zip(chain, chain[1:]))
        for p, idx, val in state.get("inflight") or []:
            inc.inflight[p] = (int(idx), list(val))
        inc.failed = {tuple(kv) for kv in state.get("failed") or []}
        inc.indet_done = {tuple(kv)
                          for kv in state.get("indet") or []}
        inc._dirty = set(inc.touch)
        return inc
