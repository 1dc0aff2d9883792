"""Elle list-append at the benchmark's shape: the checker against the
plain reference (`benchmark/references/elle_list_append.py`) on seeded
simulated runs, clean and with each anomaly class planted, on the host,
dense device and mesh tiers; the packed planes built from the
inference's edge lists against the dense stack, and the realtime
plane's nested-row pack against the dense rows; a mesh-tier check that
builds no dense plane, at a full run's size too; the padding rule; the
stage seconds and counters of one call; the array inference against
the loop it replaced, edge for edge and in order; and inference linear
in the history."""

import bisect
import gc
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import faults_list_append
from benchmark.entries import elle_list_append as entry
from benchmark.references import elle_list_append as ref
from benchmark.traffic import append_sim
from jepsen_tpu import telemetry
from jepsen_tpu import txn as mop
from jepsen_tpu.checker import elle as elle_ck
from jepsen_tpu.elle import infer as infer_mod
from jepsen_tpu.elle.infer import _Edges, txn_mops
from jepsen_tpu.history import History, fail_op, info_op, invoke_op, ok_op
from jepsen_tpu.ops import elle_mesh

# the cell's traffic for 2 simulated seconds, partitions every half
# second: ~450 txns, a few :info txns among them
SMALL = {"nodes": 5, "threads": 10, "run_s": 2, "key_count": 3,
         "min_txn_length": 1, "max_txn_length": 4, "max_writes_per_key": 32,
         "read_ratio": 0.5, "stagger_s": 1 / 30, "latency_median_s": 0.002,
         "latency_sigma": 0.5, "nemesis": "partition-random-halves",
         "healed_s": 0.5, "partitioned_s": 0.5, "timeout_s": 1.0}


def base_ops(seed: int) -> list:
    h, _ = append_sim.generate(SMALL, seed, False)
    return list(h.ops)


# Each plant: (planted txns as (invoke value, completion type, completion
# value) in completion order, the position among them of the txn whose
# :ok op is the witness).  x and y are fresh keys; the planted txns are
# concurrent with each other, each on a process of its own.
X, Y = "x", "y"
PLANTS = {
    "G0": ([([["append", X, 1], ["append", Y, 1]], "ok", None),
            ([["append", X, 2], ["append", Y, 2]], "ok", None),
            ([["r", X, None], ["r", Y, None]], "ok",
             [["r", X, [1, 2]], ["r", Y, [2, 1]]])], 0),
    "G1a": ([([["append", X, 9]], "fail", None),
             ([["r", X, None]], "ok", [["r", X, [9]]])], 1),
    "G1b": ([([["append", X, 1], ["append", X, 2]], "ok", None),
             ([["r", X, None]], "ok", [["r", X, [1]]])], 1),
    "G1c": ([([["append", X, 1], ["append", Y, 2]], "ok", None),
             ([["r", X, None], ["append", Y, 1]], "ok",
              [["r", X, [1]], ["append", Y, 1]]),
             ([["r", Y, None]], "ok", [["r", Y, [1, 2]]])], 0),
    "G-single": ([([["r", Y, None], ["r", X, None]], "ok",
                   [["r", Y, [1]], ["r", X, []]]),
                  ([["append", X, 1], ["append", Y, 1]], "ok", None)], 0),
    "G2-item": ([([["r", X, None], ["append", Y, 1]], "ok",
                  [["r", X, []], ["append", Y, 1]]),
                 ([["r", Y, None], ["append", X, 1]], "ok",
                  [["r", Y, []], ["append", X, 1]])], 0),
    "incompatible-order": ([([["append", X, 1]], "ok", None),
                            ([["append", X, 2]], "ok", None),
                            ([["r", X, None]], "ok", [["r", X, [1, 2]]]),
                            ([["r", X, None]], "ok", [["r", X, [2]]])], 3),
    "duplicate-elements": ([([["append", X, 1]], "ok", None),
                            ([["append", X, 1]], "ok", None)], 1),
    # Elle's own duplicate-elements: a read that shows one element twice
    "duplicate-read": ([([["append", X, 1]], "ok", None),
                        ([["r", X, None]], "ok", [["r", X, [1, 1]]])], 1),
}
# the class each plant is an instance of, where it is not the plant's name
CLASS_OF = {"duplicate-read": "duplicate-elements"}


def planted(seed: int, cls: str):
    """(history, witness index): the run of `seed` with the plant of
    `cls` inserted at 90% depth."""
    ops = base_ops(seed)
    txns, who = PLANTS[cls]
    at = int(len(ops) * 0.9)
    ts = ops[at - 1].time
    p0 = max(o.process for o in ops if type(o.process) is int) + 1
    invs = [invoke_op(p0 + i, "txn", v, time=ts)
            for i, (v, _, _) in enumerate(txns)]
    done = []
    for i, (v, typ, out) in enumerate(txns):
        mk = ok_op if typ == "ok" else fail_op
        done.append(mk(p0 + i, "txn", out if out is not None else v,
                       time=ts))
    h = History(ops[:at] + invs + done + ops[at:]).index()
    return h, at + len(invs) + who


CASES = ["clean"] + list(PLANTS)


def history_of(case: str, i: int):
    seed = 2**31 + 101 * i
    if case == "clean":
        return History(base_ops(seed)).index(), None
    return planted(seed, case)


# each tier and the engine and backend its verdicts name
TIERS = {"host": ("elle-host", None), "device": ("elle-device", "cpu"),
         "mesh": ("elle-mesh", "cpu")}


@pytest.mark.parametrize("algorithm", sorted(TIERS))
@pytest.mark.parametrize("case", CASES)
def test_checker_agrees_with_the_reference(case, algorithm):
    h, witness = history_of(case, CASES.index(case))
    assert any(o.type == "info" and type(o.process) is int for o in h.ops)
    want = ref.answers(h.ops)
    res = elle_ck.checker(workload="list-append",
                          algorithm=algorithm).check(None, h)
    got = entry.verdicts(res)
    assert set(got) == set(want)
    for cls, a in want.items():
        assert got[cls]["valid?"] is a["valid"], (cls, res["anomaly-types"])
        assert got[cls]["op_index"] == a["op_index"], cls
    invalid = {cls for cls, a in want.items() if not a["valid"]}
    if case == "clean":
        assert not invalid
    else:
        cls = CLASS_OF.get(case, case)
        assert cls in invalid
        assert want[cls]["op_index"] == witness
    assert (res["engine"], res["backend"]) == TIERS[algorithm]


def loop_inference(h) -> tuple:
    """({plane: {(a, b)}} for ww, wr, rw, and the G1b writers' `:ok`
    indices): the list-append inference as plain loops over every
    appender of a read's key, the form the indexed one replaced."""
    txns, failed, indet = infer_mod.collect_txns(h)
    writer, appends, reads = {}, {}, []
    for i, (_, ok) in enumerate(txns):
        for f, k, v in infer_mod.txn_mops(ok):
            if f == "append" and writer.setdefault((k, v), i) == i:
                appends.setdefault((k, i), []).append(v)
            elif f == "r" and len(set(v or ())) == len(v or ()):
                reads.append((i, k, tuple(v or ())))
    orders = {}
    for i, k, s in reads:
        if len(s) > len(orders.get(k, ())):
            orders[k] = s
    edges = {"ww": set(), "wr": set(), "rw": set()}
    g1b = []
    for i, k, s in reads:
        if any((k, v) in failed or ((k, v) not in writer
                                    and (k, v) not in indet) for v in s):
            continue
        seen = set(s)
        bad = [t for (k2, t), vs in appends.items()
               if k2 == k and t != i and len(vs) >= 2
               and any(v in seen for v in vs[:-1]) and vs[-1] not in seen]
        if bad:
            g1b.append(txns[bad[0]][1].index)
            continue
        if orders.get(k, ())[:len(s)] != s:
            continue
        w = next((writer[(k, v)] for v in reversed(s)
                  if writer.get((k, v), i) != i), None)
        if w is not None:
            edges["wr"].add((w, i))
        edges["rw"] |= {(i, t) for (k2, t), vs in appends.items()
                        if k2 == k and t != i and not seen.issuperset(vs)}
    for k, order in orders.items():
        ws = [writer[(k, v)] for v in order if (k, v) in writer]
        edges["ww"] |= {(a, b) for a, b in zip(ws, ws[1:]) if a != b}
        if ws:
            edges["ww"] |= {(ws[-1], t) for (k2, t), vs in appends.items()
                            if k2 == k and t != ws[-1]
                            and not set(order).issuperset(vs)}
    return edges, g1b


@pytest.mark.parametrize("case", CASES)
def test_indexed_inference_equals_the_loops(case):
    h, _ = history_of(case, CASES.index(case))
    inf = infer_mod.infer(h, workload="list-append")
    edges, g1b = loop_inference(h)
    for p, want in edges.items():
        assert set(zip(*map(list, inf.lists[p]))) == want, p
    assert [e["writer"]["index"] for e in inf.direct.get("G1b", [])] == g1b


# The loop over reads and their elements that the array pass replaced,
# kept unchanged as the oracle: the array pass must give its edge lists
# in the same order, its direct anomalies and its meta.
def _infer_list_append(txns, failed, indet, edges: _Edges):
    direct: dict = {}
    meta: dict = {"keys": 0}

    def flag(name, i, m, **kw):
        direct.setdefault(name, []).append(
            dict({"op": txns[i][1].to_dict(), "mop": list(m)}, **kw))

    # per-key append bookkeeping and the observed states over committed
    # txns; duplicate-elements is a read that shows one element twice
    # (Elle's definition; the read gives no edge) or a value two
    # committed txns append, flagged in txn order
    writer_of: dict = {}          # (k, v) -> txn index
    appends_by_txn: dict = {}     # (k, txn) -> [v, ...] in mop order
    reads: list = []              # (txn index, key, state tuple, mop)
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
                reads.append((i, mop.key(m), tuple(s), m))
            elif mop.is_append(m):
                k, v = mop.key(m), mop.value(m)
                if (k, v) in writer_of and writer_of[(k, v)] != i:
                    flag("duplicate-elements", i, m,
                         other=txns[writer_of[(k, v)]][1].to_dict())
                    continue
                writer_of[(k, v)] = i
                appends_by_txn.setdefault((k, i), []).append(v)

    # version order = longest prefix chain
    orders: dict = {}             # key -> tuple of values, longest observed
    for i, k, s, m in reads:
        if len(s) > len(orders.get(k, ())):
            orders[k] = s
    meta["keys"] = len({k for k, _ in writer_of} | set(orders))

    # indexed once, so that a read costs its own length (plus the rw
    # edges it draws): each key's appenders in first-append order
    # (`rank`), and in the order of the last place their appends take
    # in the key's version order (`unseen_from`; absent appends place
    # last).  A clean read of the first L versions has seen exactly the
    # appenders whose last place is below L.
    by_key_appends: dict = {}     # key -> [(txn, [v, ...]), ...]
    rank: dict = {}               # (key, txn) -> place in that list
    for (k, t), vs in appends_by_txn.items():
        lst = by_key_appends.setdefault(k, [])
        rank[(k, t)] = len(lst)
        lst.append((t, vs))
    absent = float("inf")
    unseen_from: dict = {}        # key -> ([last place, ...], [txn, ...])
    for k, lst in by_key_appends.items():
        place: dict = {}
        for j, v in enumerate(orders.get(k, ())):
            place.setdefault(v, j)
        last = sorted((max(place.get(v, absent) for v in vs), t)
                      for t, vs in lst)
        unseen_from[k] = ([p for p, _ in last], [t for _, t in last])

    # classify each read; only clean prefix reads contribute edges
    for i, k, s, m in reads:
        order = orders.get(k, ())
        bad = False
        for v in s:
            if (k, v) in failed:
                flag("G1a", i, m, kind="aborted")
                bad = True
                break
            if (writer_of.get((k, v)) is None and (k, v) not in indet):
                flag("G1a", i, m, kind="garbage")
                bad = True
                break
        if bad:
            continue
        seen = set(s)
        # G1b: an appender whose earlier append the read saw and whose
        # last it did not (the first such in first-append order)
        g1b = None
        for v in s:
            t = writer_of.get((k, v))
            if t is not None and t != i:
                tail = appends_by_txn[(k, t)][-1]
                if tail not in seen and (
                        g1b is None or rank[(k, t)] < rank[(k, g1b)]):
                    g1b = t
        if g1b is not None:
            flag("G1b", i, m, writer=txns[g1b][1].to_dict())
            continue
        if tuple(order[:len(s)]) != tuple(s):
            flag("incompatible-order", i, m, longest=list(order))
            continue
        # wr: the last element whose writer is a committed node other
        # than the reader itself (read-your-own-write is not an
        # external observation; the one before it is)
        for v in reversed(s):
            w = writer_of.get((k, v))
            if w is not None and w != i:
                edges.add("wr", w, i)
                break
        # rw: lists grow monotonically, so ANY committed append not in
        # the observed state was installed after it — the next observed
        # version plus every unobserved committed append (sound: the
        # emitted edge stands for rw + a ww-path)
        if k in unseen_from:
            places, txs = unseen_from[k]
            for t in txs[bisect.bisect_left(places, len(s)):]:
                if t != i:
                    edges.add("rw", i, t)

    # ww: consecutive committed writers along each key's version
    # order, then order-tail -> unobserved appends (same monotonicity
    # argument: absent from the longest observed state => later)
    for k, order in orders.items():
        prev = None
        for v in order:
            w = writer_of.get((k, v))
            if w is None:
                continue
            if prev is not None and prev != w:
                edges.add("ww", prev, w)
            prev = w
        if prev is not None and k in unseen_from:
            places, txs = unseen_from[k]
            for t in txs[bisect.bisect_left(places, len(order)):]:
                if t != prev:
                    edges.add("ww", prev, t)

    # bounded: results.json must not scale with history size
    meta["version-orders"] = {
        repr(k): (list(v[:32]) + ["..."] if len(v) > 32 else list(v))
        for k, v in sorted(orders.items(),
                           key=lambda kv: repr(kv[0]))[:8]}
    return direct, meta


# Hand-made histories, txn after txn, each (invoke value, completion
# type, completion value): the shapes the simulated runs rarely or never
# reach.
HAND = {
    "mixed-keys": [
        ([["append", X, 1], ["append", 1, 1], ["append", Y, 1]], "ok", None),
        ([["append", 1, 2], ["r", X, None], ["append", X, 2]], "ok",
         [["append", 1, 2], ["r", X, [1]], ["append", X, 2]]),
        ([["r", 1, None], ["r", X, None], ["r", Y, None], ["r", 2, None]],
         "ok", [["r", 1, [1, 2]], ["r", X, [1, 2]], ["r", Y, [1]],
                ["r", 2, []]]),
        ([["append", 2, 5], ["rp", ["keys", [X, Y]], {}]], "ok",
         [["append", 2, 5], ["rp", ["keys", [X, Y]], {X: 1}]]),
        ([["r", 2, None], ["append", Y, 2]], "ok",
         [["r", 2, [5]], ["append", Y, 2]]),
    ],
    # an aborted element after a garbage one, and a value a failed txn
    # and a committed one both append (aborted, as the failed set wins)
    "G1a-aborted": [
        ([["append", X, 1]], "fail", None),
        ([["append", X, 2]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [2, 1]]]),
        ([["r", X, None]], "ok", [["r", X, [2, 7, 1]]]),
        ([["append", X, 3]], "fail", None),
        ([["append", X, 3]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [2, 3]]]),
        ([["r", X, None]], "ok", [["r", X, [2]]]),
    ],
    # an :info txn's value is no garbage, and an invocation that never
    # completes is indeterminate too
    "G1a-garbage": [
        ([["append", X, 1]], "info", None),
        ([["append", X, 2]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [1, 2]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 2, 9]]]),
        ([["r", X, None]], "ok", [["r", X, [8, 1]]]),
        ([["append", Y, 4]], None, None),
        ([["r", Y, None]], "ok", [["r", Y, [4]]]),
    ],
    # the read [3, 1] sees the heads of both txns' appends and neither's
    # tail: the lower-rank writer (txn 0) is named, though its element
    # comes later in the read, off the order; [1] and [1, 2, 3] are
    # prefixes of it
    "G1b-rank": [
        ([["append", X, 1], ["append", X, 2]], "ok", None),
        ([["append", X, 3], ["append", X, 4]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [1, 2, 3, 4]]]),
        ([["r", X, None]], "ok", [["r", X, [3, 1]]]),
        ([["r", X, None]], "ok", [["r", X, [1]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 2, 3]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 2, 3, 4]]]),
    ],
    # reads off the first longest read, one of them as long as it; on y
    # the longest read holds a garbage element
    "incompatible-order": [
        ([["append", X, 1]], "ok", None),
        ([["append", X, 2]], "ok", None),
        ([["append", X, 3]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [1, 2, 3]]]),
        ([["r", X, None]], "ok", [["r", X, [2]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 3]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 3, 2]]]),
        ([["append", Y, 1], ["append", Y, 2]], "ok", None),
        ([["r", Y, None]], "ok", [["r", Y, [1, 2, 99]]]),
        ([["r", Y, None]], "ok", [["r", Y, [1, 2]]]),
        ([["append", Y, 3]], "ok", None),
        ([["r", Y, None]], "ok", [["r", Y, [1, 2, 3]]]),
    ],
    # a value two committed txns append, a read that shows one element
    # twice, and a txn that appends one value twice (no anomaly)
    "duplicate-elements": [
        ([["append", X, 1]], "ok", None),
        ([["append", X, 1], ["append", X, 2]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, [1, 1]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 2]]]),
        ([["append", Y, 5], ["append", Y, 5]], "ok", None),
        ([["r", Y, None]], "ok", [["r", Y, [5]]]),
    ],
    "read-your-own-write": [
        ([["append", X, 1]], "ok", None),
        ([["append", X, 2], ["r", X, None]], "ok",
         [["append", X, 2], ["r", X, [1, 2]]]),
        ([["r", X, None]], "ok", [["r", X, [1, 2]]]),
        ([["append", X, 3], ["r", X, None]], "ok",
         [["append", X, 3], ["r", X, [1, 2, 3]]]),
        ([["append", Y, 1], ["r", Y, None]], "ok",
         [["append", Y, 1], ["r", Y, [1]]]),
    ],
    # y is appended and read, but never seen: it has no version order
    "none-and-empty": [
        ([["r", X, None]], "ok", [["r", X, None]]),
        ([["r", X, None]], "ok", [["r", X, []]]),
        ([["append", X, 1], ["append", Y, 1]], "ok", None),
        ([["r", X, None], ["r", Y, None]], "ok",
         [["r", X, None], ["r", Y, []]]),
        ([["r", X, None]], "ok", [["r", X, [1]]]),
        ([["append", Y, 2]], "ok", None),
    ],
    # z has no appends at all, w only an :info txn's
    "no-appenders": [
        ([["r", "z", None]], "ok", [["r", "z", []]]),
        ([["r", "z", None]], "ok", [["r", "z", [5]]]),
        ([["append", "w", 1]], "info", None),
        ([["r", "w", None]], "ok", [["r", "w", [1]]]),
        ([["append", X, 1]], "ok", None),
        ([["r", "w", None], ["r", X, None]], "ok",
         [["r", "w", []], ["r", X, [1]]]),
    ],
    # a number and a string are no list states and are passed over; a
    # tuple is one
    "non-list-state": [
        ([["append", X, 1]], "ok", None),
        ([["r", X, None]], "ok", [["r", X, 5]]),
        ([["r", X, None]], "ok", [["r", X, "ab"]]),
        ([["r", X, None]], "ok", [["r", X, (1,)]]),
        ([["append", X, 2]], "ok", None),
    ],
}


def hand_history(txns):
    ops = []
    for p, (v, typ, out) in enumerate(txns):
        ops.append(invoke_op(p, "txn", v))
        if typ is not None:
            mk = {"ok": ok_op, "fail": fail_op, "info": info_op}[typ]
            ops.append(mk(p, "txn", out if out is not None else v))
    return History(ops).index()


ORACLE_CASES = ([("case", c) for c in CASES]
                + [("sim", plant) for plant in (False, True)]
                + [("hand", c) for c in HAND])


def oracle_history(kind, case):
    if kind == "case":
        return history_of(case, CASES.index(case))[0]
    if kind == "sim":
        return append_sim.generate(SMALL, 2**31 + 23, case)[0]
    return hand_history(HAND[case])


def classified_reads(h) -> int:
    """The reads the loop classifies: list states (None reads as []),
    less those that show one element twice."""
    txns, _, _ = infer_mod.collect_txns(h)
    states = [[] if m[2] is None else m[2] for _, ok in txns
              for m in txn_mops(ok) if mop.is_read(m)]
    return sum(1 for s in states if isinstance(s, (list, tuple))
               and len(set(s)) == len(s))


@pytest.mark.parametrize("kind,case", ORACLE_CASES,
                         ids=[f"{k}-{c}" for k, c in ORACLE_CASES])
def test_inference_equals_the_loop(kind, case, monkeypatch):
    """The array pass gives the loop's edge lists element for element
    and in order (ww, wr, rw, po), its direct anomalies in order, dict
    order included, and its meta; and counts every read it classifies
    on the array path."""
    h = oracle_history(kind, case)
    before = counters(telemetry.ELLE_INFER_READS)
    got = infer_mod.infer(h, workload="list-append")
    after = counters(telemetry.ELLE_INFER_READS)
    monkeypatch.setattr(infer_mod, "_infer_list_append", _infer_list_append)
    want = infer_mod.infer(h, workload="list-append")
    for p in infer_mod.LIST_PLANES:
        for g, w in zip(got.lists[p], want.lists[p]):
            assert g.dtype == w.dtype == np.int64, p
            assert g.tolist() == w.tolist(), p
    assert list(got.direct) == list(want.direct)
    assert got.direct == want.direct
    assert got.meta == want.meta
    n = classified_reads(h)
    assert {k: x - before.get(k, 0.0) for k, x in after.items()
            if x != before.get(k, 0.0)} == (
        {(("path", "array"),): float(n)} if n else {})
    if kind == "hand":
        assert n and any(got.lists[p][0].size for p in ("ww", "wr", "rw"))


def test_rw_extension_keeps_the_tail():
    """The program draws rw edges to every unobserved appender, the
    reference to the next version's writer only: on a read skew whose
    defining rw edge the program could draw to a later writer, both
    name the same tail."""
    # T0, T1, T2 append x 1, 2, 3 (T2 y 2 too), T3 reads x [1] and
    # y [2], T4 reads x [1, 2, 3]: T3 -rw-> T1 (the next version) and
    # T3 -rw-> T2 (the program only); T1 -ww-> T2 -wr-> T3 closes both
    t = [
        ([["append", X, 1]], [["append", X, 1]]),
        ([["append", X, 2]], [["append", X, 2]]),
        ([["append", X, 3], ["append", Y, 2]],
         [["append", X, 3], ["append", Y, 2]]),
        ([["r", X, None], ["r", Y, None]], [["r", X, [1]], ["r", Y, [2]]]),
        ([["r", X, None]], [["r", X, [1, 2, 3]]]),
    ]
    ops = [invoke_op(i, "txn", v) for i, (v, _) in enumerate(t)]
    ops += [ok_op(i, "txn", out) for i, (_, out) in enumerate(t)]
    h = History(ops).index()
    inf = infer_mod.infer(h, workload="list-append")
    rw = set(zip(*map(list, inf.lists["rw"])))
    assert {(3, 1), (3, 2)} <= rw           # reader 3 -> writers 1, 2
    g, _ = ref.infer(*ref.committed_txns(h.ops))
    assert g.adj["rw"][3] == {1}
    want = ref.answers(h.ops)
    got = entry.verdicts(elle_ck.checker(
        workload="list-append", algorithm="mesh").check(None, h))
    assert want["G-single"]["valid"] is False
    assert got["G-single"]["op_index"] == want["G-single"]["op_index"] \
        == h.ops[8].index


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_packed_planes_equal_the_dense_pack(seed, n_dev):
    h, _ = planted(2**31 + seed, "G-single")
    inf = infer_mod.infer(h, workload="list-append")
    packed = inf.packed_stacked(n_dev=n_dev)
    assert inf._planes is None
    dense = elle_mesh.pack_planes(inf.stacked(), n_dev=n_dev)
    assert packed.shape == dense.shape
    assert (packed == dense).all()
    assert inf.meta["edge-counts"] == {
        p: int(inf.planes[p].sum()) for p in infer_mod.PLANES}


@pytest.mark.parametrize("rounds", [1, 2])
def test_the_cells_plant_needs_two_closure_rounds(rounds, monkeypatch):
    """The benchmark's planted G-single has a way back of three edges
    and no realtime shortcut: a closure cut to one squaring round (the
    `closure_one_round` fault) misses it, two find it at the plant's
    witness."""
    if rounds == 1:
        faults_list_append.closure_one_round(monkeypatch.setattr)
    else:
        monkeypatch.setattr(elle_mesh, "closure_steps", lambda n_pad: 2)
        monkeypatch.setattr(elle_mesh, "_PLAN_CACHE", {})
    h, plants = append_sim.generate(SMALL, 2**31 + 5, True)
    (plant,) = [p for p in plants if p["key"] == "G-single"]
    assert ref.answers(h.ops)["G-single"]["op_index"] == plant["witness"]
    got = entry.verdicts(elle_ck.checker(
        workload="list-append", algorithm="mesh").check(None, h))
    assert got["G-single"]["valid?"] is (rounds == 1)
    if rounds == 2:
        assert got["G-single"]["op_index"] == plant["witness"]


@pytest.mark.parametrize("order", [True, False])
def test_the_cells_realtime_plant_needs_order(order, monkeypatch):
    """The benchmark's realtime G1c closes only through realtime order:
    the checker finds it at the plant's witness with order, and passes
    it without (the `order_dropped` fault), as the reference under
    plain serializability (the control) does; the G-single plant is
    found either way."""
    if not order:
        faults_list_append.order_dropped(monkeypatch.setattr)
    h, plants = append_sim.generate(SMALL, 2**31 + 5, True)
    w = {p["key"]: p["witness"] for p in plants}
    assert set(w) == {"G1c", "G-single"} and w["G1c"] < w["G-single"]
    want = ref.answers(h.ops)
    assert {c for c, a in want.items() if not a["valid"]} == set(w)
    assert all(want[c]["op_index"] == w[c] for c in w)
    serializable = ref.answers(h.ops, control=True)
    assert serializable["G1c"]["valid"] is True
    assert serializable["G-single"]["op_index"] == w["G-single"]
    got = entry.verdicts(elle_ck.checker(
        workload="list-append", algorithm="mesh").check(None, h))
    assert got["G1c"]["valid?"] is (not order)
    assert got["G1c"]["op_index"] == (w["G1c"] if order else None)
    assert got["G-single"]["op_index"] == w["G-single"]


def test_realtime_plane_with_unknown_indices():
    """Txns whose ops carry no index have no realtime edges, in the
    packed plane, its edge count and the dense plane alike."""
    inv = np.array([0, 2, -1, 5, 9, 11], np.int64)
    ok = np.array([1, 4, 7, -1, 10, 12], np.int64)
    dense = infer_mod._rt_rows(inv, ok, 0, len(inv))
    want = np.array([[ok[a] >= 0 and inv[b] >= 0 and inv[a] >= 0
                      and ok[b] >= 0 and ok[a] < inv[b] and a != b
                      for b in range(6)] for a in range(6)])
    assert (dense == want).all()
    plane = np.zeros((128, 4), np.uint32)
    infer_mod._rt_pack_into(plane, inv, ok)
    assert (plane == elle_mesh.pack_planes(dense[None], n_pad=128)[0]).all()
    assert infer_mod._rt_count(inv, ok) == int(dense.sum())


def _order_vectors(kind: str, n: int, seed: int) -> tuple:
    """(inv, ok) index vectors of one shape: `completion` in completion
    order (`collect_txns`' case), `shuffled` the same out of order,
    `unknown` with -1 on either side, `inverted` with invokes after
    completions, `ties` drawn from a few values so completions equal
    other txns' invokes."""
    rng = np.random.default_rng(seed)
    inv = np.sort(rng.integers(0, 3 * n + 1, n))
    ok = inv + rng.integers(1, 8, n)
    order = np.argsort(ok, kind="stable")
    inv, ok = inv[order], ok[order]
    if kind == "shuffled":
        perm = rng.permutation(n)
        inv, ok = inv[perm], ok[perm]
    elif kind == "unknown":
        inv = np.where(rng.random(n) < 0.2, -1, inv)
        ok = np.where(rng.random(n) < 0.2, -1, ok)
    elif kind == "inverted":
        flip = rng.random(n) < 0.3
        inv, ok = np.where(flip, ok, inv), np.where(flip, inv, ok)
    elif kind == "ties":
        inv = rng.integers(-1, 6, n)
        ok = rng.integers(-1, 6, n)
    return inv.astype(np.int64), ok.astype(np.int64)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("n", [0, 1, 31, 32, 33, 1000])
@pytest.mark.parametrize(
    "kind", ["completion", "shuffled", "unknown", "inverted", "ties"])
def test_realtime_pack_equals_the_dense_rows(kind, n, n_dev):
    """The nested-row rt pack is the dense `_rt_rows` plane packed, bit
    for bit, padding included."""
    inv, ok = _order_vectors(kind, n, 2**31 + 17 * n + n_dev)
    n_pad = elle_mesh.pad_for_mesh(n, n_dev)
    plane = np.zeros((n_pad, n_pad // 32), np.uint32)
    infer_mod._rt_pack_into(plane, inv, ok)
    dense = infer_mod._rt_rows(inv, ok, 0, n)
    assert (plane == elle_mesh.pack_planes(dense[None], n_pad=n_pad)[0]).all()
    assert infer_mod._rt_count(inv, ok) == int(dense.sum())


@pytest.mark.parametrize("plant", [False, True])
def test_realtime_pack_of_a_simulated_run(plant):
    """On a simulated run of the cell's traffic the rt pack equals the
    dense rows, and is equal again with the order reversed in time (the
    `realtime_reversed` fault's call, which takes the scatter branch)."""
    h, _ = append_sim.generate(SMALL, 2**31 + 11, plant)
    inv, ok = infer_mod.infer(h, workload="list-append").order_idx
    n = len(inv)
    assert n > 300 and (np.diff(ok) > 0).all()
    top = int(max(inv.max(), ok.max())) + 1
    for a, b in ((inv, ok), (top - ok, top - inv)):
        n_pad = elle_mesh.pad_for_mesh(n, 1)
        plane = np.zeros((n_pad, n_pad // 32), np.uint32)
        infer_mod._rt_pack_into(plane, a, b)
        dense = infer_mod._rt_rows(a, b, 0, n)
        assert dense.any()
        want = elle_mesh.pack_planes(dense[None], n_pad=n_pad)[0]
        assert (plane == want).all()


def test_packed_stack_builds_no_dense_realtime_rows(monkeypatch):
    """`packed_stacked` never builds an n x n bool: with `_rt_rows`
    refusing, the stack still equals the dense pack taken before."""
    h, _ = planted(2**31 + 9, "G1c")
    inf = infer_mod.infer(h, workload="list-append")
    want = elle_mesh.pack_planes(inf.stacked())
    inf = infer_mod.infer(h, workload="list-append")

    def refuse(*a, **kw):
        raise AssertionError("dense realtime rows built")
    monkeypatch.setattr(infer_mod, "_rt_rows", refuse)
    got = inf.packed_stacked()
    assert got.shape[0] == len(infer_mod.PLANES)
    assert (got == want).all()


def test_mesh_check_builds_no_dense_plane(monkeypatch):
    seen = []
    real = infer_mod.infer

    def keep(*a, **kw):
        inf = real(*a, **kw)
        seen.append(inf)
        return inf
    monkeypatch.setattr(infer_mod, "infer", keep)
    h, witness = planted(2**31 + 7, "G-single")
    v = elle_ck.checker(workload="list-append",
                        algorithm="mesh").check(None, h)
    assert v["engine"] == "elle-mesh" and v["valid?"] is False
    assert v["anomalies"]["G-single"][0]["cycle"][0]["index"] == witness
    assert v["anomalies"]["G-single"][0]["edges"][0] == "rw"
    (inf,) = seen
    assert inf._planes is None


def test_full_size_history_routes_to_the_mesh_tier(monkeypatch):
    """A history of the cell's size (~13.4k txns) goes to `elle-mesh`
    on `auto` with packed planes only.  The closure itself is stubbed:
    on the CPU it would take minutes."""
    from benchmark import harness

    seen, shapes = [], []
    real = infer_mod.infer

    def keep(*a, **kw):
        inf = real(*a, **kw)
        seen.append(inf)
        return inf

    def closure(packed, ns, devices=None, **kw):
        shapes.extend(p.shape for p in packed)
        return [{"anomalies": {}, "n": n, "n_pad": p.shape[-2], "rounds": 3,
                 "shards": 1, "backend": devices[0].platform}
                for p, n in zip(packed, ns)]
    monkeypatch.setattr(infer_mod, "infer", keep)
    monkeypatch.setattr(elle_mesh, "classify_packed", closure)
    cell = harness.load_cell(Path(__file__).resolve().parents[1],
                             "list-append.campaign")
    h, _ = append_sim.generate(cell.params, 2**31 + 17, False)
    v = elle_ck.checker(workload="list-append").check(None, h)
    (inf,) = seen
    assert 13_000 < inf.n < 13_800
    assert (v["engine"], v["backend"], v["valid?"]) == ("elle-mesh", "cpu",
                                                        True)
    n_pad = elle_mesh.pad_for_mesh(inf.n, len(elle_mesh.mesh_devices()))
    assert shapes == [(len(infer_mod.PLANES), n_pad, n_pad // 32)]
    assert inf._planes is None


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_neighbouring_lengths_share_one_block(n_dev):
    """From the mesh threshold on, histories a few txns apart pad to
    the same n_pad (a 1,024 step), so they share a block and a compile;
    below it the 128-row tile stands as it was; n_pad never shrinks as
    n grows."""
    assert {elle_mesh.pad_for_mesh(n, n_dev)
            for n in range(13_313, 14_337, 17)} == {14_336}
    assert elle_mesh._block_for(14_336) == 2048
    assert elle_mesh._block_for(13_312) == 1024
    assert {elle_mesh._block_for(elle_mesh.pad_for_mesh(n, n_dev))
            for n in range(16_385, 17_409, 31)} == {1024}
    tile = elle_mesh.mesh_tile(n_dev)
    for n in range(0, elle_mesh._MESH_STEP_FROM, 61):
        assert elle_mesh.pad_for_mesh(n, n_dev) == max(
            tile, tile * -(-n // tile)), n
    last = tile
    for n in (1, 100, 4_095, 4_096, 8_191, 8_192, 8_193, 16_384, 20_000,
              100_000):
        p = elle_mesh.pad_for_mesh(n, n_dev)
        step = tile if n < elle_mesh._MESH_STEP_FROM \
            else int(np.lcm(tile, 1024))
        assert p >= n and p % step == 0 and p - n < step, n
        assert p >= last
        last = p
    assert elle_mesh.pad_for_mesh(8191, 1) == 8192
    assert elle_mesh.pad_for_mesh(8193, 1) == 9216


def counters(name):
    _, by = telemetry.REGISTRY.collect().get(name, ("counter", {}))
    return {k: float(m.value) for k, m in by.items()}


def test_stage_seconds_and_counters_of_one_call():
    h, _ = planted(2**31 + 3, "G-single")
    names = (telemetry.STAGE_SECONDS, telemetry.DEVICE_CALLS,
             telemetry.WIRE_BYTES, telemetry.ELLE_ROUNDS)
    before = {n: counters(n) for n in names}
    chk = elle_ck.checker(workload="list-append", algorithm="mesh")
    t0 = time.monotonic()
    v = chk.check(None, h)
    wall = time.monotonic() - t0
    after = {n: counters(n) for n in names}
    delta = {n: {k: x - before[n].get(k, 0.0) for k, x in after[n].items()
                 if x - before[n].get(k, 0.0)} for n in names}
    st = v["stages"]
    assert set(st) == {"infer", "pack", "kernel", "witness"}
    assert sum(st.values()) <= wall
    stage = {dict(k)["stage"]: x for k, x in
             delta[telemetry.STAGE_SECONDS].items()
             if dict(k)["engine"] == "elle-mesh"}
    assert stage.keys() == st.keys()
    assert sum(stage.values()) <= wall
    assert list(delta[telemetry.DEVICE_CALLS].values()) == [1.0]
    n_pad = elle_mesh.pad_for_mesh(v["txn-count"], 8)
    assert delta[telemetry.ELLE_ROUNDS] == {
        (("n_pad", str(n_pad)),): float(v["rounds"])}
    wire = {dict(k)["direction"]: x
            for k, x in delta[telemetry.WIRE_BYTES].items()}
    assert wire["h2d"] == 4 * n_pad * n_pad // 8


def test_inference_is_linear_in_the_history():
    """2x the txns costs at most 2.5x the inference, at 4k -> 8k txns:
    the calling thread's CPU seconds (the process's would count the
    other threads of the worker too), the best of five alternating
    tries each, since the test shares its cores with other workers,
    with the cyclic collector held off (its passes grow with every live
    object, not with the inference)."""
    hs = [append_sim.generate(dict(SMALL, run_s=run_s, nemesis=None),
                              2**31 + 11, False)[0] for run_s in (14, 28)]
    best = [float("inf")] * 2
    gc.collect()
    gc.disable()
    try:
        for _ in range(5):
            for j, h in enumerate(hs):
                t0 = time.thread_time()
                inf = infer_mod.infer(h, workload="list-append")
                best[j] = min(best[j], time.thread_time() - t0)
                if j == 0:
                    n1 = inf.n
                else:
                    n2 = inf.n
                del inf
    finally:
        gc.enable()
    assert 3_500 < n1 < 4_500 and 1.9 < n2 / n1 < 2.1
    assert best[1] / best[0] <= 2.5, (n1, n2, best)
