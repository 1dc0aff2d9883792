"""Independent-layer tests — ports
`jepsen/test/jepsen/independent_test.clj` (sequential/concurrent
generator key-sharding incl. the 1000-key concurrency test :34-40, the
lifted checker :76-97) and adds device coverage: the batched
vmap-over-keys WGL checker, sharded over an 8-device CPU mesh."""

import random

import pytest

from jepsen_tpu import checker as ck
from jepsen_tpu import generator as gen
from jepsen_tpu import independent as ind
from jepsen_tpu import models
from jepsen_tpu.history import History, invoke_op, ok_op, fail_op, info_op
from tests.test_generator import ops


@pytest.fixture(autouse=True)
def store_tmpdir(tmp_path, monkeypatch):
    from jepsen_tpu import store
    monkeypatch.setattr(store, "BASE", tmp_path / "store")
    yield


def values(os_):
    return [o["value"] for o in os_]


class TestSequentialGenerator:
    def test_empty_keys(self):
        assert ops(("a", "b"), ind.sequential_generator([], lambda k: "x")) \
            == []

    def test_one_key(self):
        got = ops(("a",), ind.sequential_generator(
            ["k1"], lambda k: gen.gseq([{"value": "ashley"},
                                        {"value": "katchadourian"}])))
        assert values(got) == [ind.KV("k1", "ashley"),
                               ind.KV("k1", "katchadourian")]

    def test_n_keys(self):
        got = ops(("a",), ind.sequential_generator(
            [1, 2, 3],
            lambda k: gen.gseq([{"value": v} for v in range(k)])))
        assert values(got) == [ind.KV(1, 0),
                               ind.KV(2, 0), ind.KV(2, 1),
                               ind.KV(3, 0), ind.KV(3, 1), ind.KV(3, 2)]

    def test_concurrency(self):
        kmax, vmax = 1000, 10
        got = ops(tuple(range(10)), ind.sequential_generator(
            range(kmax),
            lambda k: gen.gseq([{"value": v} for v in range(vmax)])))
        assert set(map(tuple, values(got))) == \
            {(k, v) for k in range(kmax) for v in range(vmax)}


class TestConcurrentGenerator:
    def test_empty_keys(self):
        assert ops(tuple(range(10)),
                   ind.concurrent_generator(1, [], lambda k: k)) == []

    def test_too_few_threads(self):
        with pytest.raises(AssertionError, match="at least 12"):
            ops(tuple(range(10)),
                ind.concurrent_generator(12, [], lambda k: k))

    def test_uneven_threads(self):
        with pytest.raises(AssertionError, match="multiple of 2"):
            ops(tuple(range(11)),
                ind.concurrent_generator(2, [], lambda k: k))

    def test_fully_concurrent(self):
        kmax, vmax, n, threads = 10, 5, 5, 100
        got = ops(tuple(range(threads)), ind.concurrent_generator(
            n, range(kmax),
            lambda k: gen.gseq([{"value": v} for v in range(vmax)])))
        assert set(map(tuple, values(got))) == \
            {(k, v) for k in range(kmax) for v in range(vmax)}


def test_history_keys_and_subhistory():
    h = History([
        invoke_op(0, "read", ind.KV(1, None)),
        ok_op(0, "read", ind.KV(1, 5)),
        info_op("nemesis", "start", None),
        invoke_op(1, "write", ind.KV(2, 7)),
        ok_op(1, "write", ind.KV(2, 7)),
    ]).index()
    assert ind.history_keys(h) == {1, 2}
    sub1 = ind.subhistory(1, h)
    assert [o.value for o in sub1] == [None, 5, None]
    assert sub1[2].f == "start"  # un-keyed nemesis ops appear everywhere
    # the one-pass split is the per-key split, key for key
    both = ind.subhistories([1, 2], h)
    assert [[(o.f, o.value) for o in s] for s in both] == [
        [(o.f, o.value) for o in ind.subhistory(k, h)] for k in (1, 2)]


def test_checker():
    """independent_test.clj:76-97: even-length subhistories are valid."""

    class EvenChecker(ck.Checker):
        def check(self, test, history, opts=None):
            return {"valid?": len(history) % 2 == 0}

    history = ops(("a", "b", "c"), ind.sequential_generator(
        [0, 1, 2, 3],
        lambda k: gen.gseq([{"value": v} for v in range(k)])))
    history = [{"value": "not-sharded"}] + history
    r = ind.checker(EvenChecker()).check(
        {"name": "independent-checker-test", "start-time": "0"},
        History(history), {})
    assert r == {"valid?": False,
                 "results": {1: {"valid?": True},
                             2: {"valid?": False},
                             3: {"valid?": True}},
                 "failures": [2]}


def test_checker_writes_artifacts(tmp_path):
    from jepsen_tpu import store

    class TinyChecker(ck.Checker):
        def check(self, test, history, opts=None):
            return {"valid?": True}

    h = History([invoke_op(0, "read", ind.KV(1, None)),
                 ok_op(0, "read", ind.KV(1, None))]).index()
    test = {"name": "indep-artifacts", "start-time": "t0"}
    ind.checker(TinyChecker()).check(test, h, {})
    assert (store.BASE / "indep-artifacts" / "t0" / "independent" / "1" /
            "results.json").exists()


# ---------------------------------------------------------------------------
# Batched device checking
# ---------------------------------------------------------------------------

def make_register_history(key, n_ops, seed, bad=False):
    """A linearizable single-register history from a sequential run with
    concurrency-2 interleaving; optionally corrupted."""
    rng = random.Random(seed)
    ops_, value = [], None
    for i in range(n_ops):
        p = rng.randint(0, 1)
        f = rng.choice(["read", "write", "cas"])
        if f == "read":
            ops_.append(invoke_op(p, "read", None))
            ops_.append(ok_op(p, "read", value))
        elif f == "write":
            v = rng.randint(0, 4)
            ops_.append(invoke_op(p, "write", v))
            value = v
            ops_.append(ok_op(p, "write", v))
        else:
            old, new = rng.randint(0, 4), rng.randint(0, 4)
            ops_.append(invoke_op(p, "cas", [old, new]))
            if value == old:
                value = new
                ops_.append(ok_op(p, "cas", [old, new]))
            elif i % 7 == 3:
                # occasional crashed op: stays concurrent forever —
                # frequent crashes explode the search (06-refining.md:12-19)
                ops_.append(info_op(p, "cas", [old, new]))
            else:
                ops_.append(fail_op(p, "cas", [old, new]))
    if bad:
        ops_.append(invoke_op(7, "read", None))
        ops_.append(ok_op(7, "read", 99))
    return History(ops_).index()


def test_check_many_matches_cpu_oracle():
    from jepsen_tpu.ops import wgl_batch, wgl_cpu

    hists = [make_register_history(k, 30, seed=k, bad=(k % 3 == 2))
             for k in range(9)]
    model = models.CASRegister()
    batch = wgl_batch.check_many(model, hists, frontier_size=128)
    for k, (h, r) in enumerate(zip(hists, batch)):
        expected = wgl_cpu.check(models.CASRegister(), h)
        assert r["valid?"] == expected["valid?"], f"key {k}"
        assert r["valid?"] == (k % 3 != 2)


def test_check_many_on_mesh():
    import jax
    from jax.sharding import Mesh
    from jepsen_tpu.ops import wgl_batch

    devices = jax.devices()
    assert len(devices) == 8, "conftest should provide 8 virtual devices"
    mesh = Mesh(devices, ("keys",))
    hists = [make_register_history(k, 40, seed=100 + k, bad=(k == 5))
             for k in range(13)]  # deliberately not a multiple of 8
    out = wgl_batch.check_many(models.CASRegister(), hists,
                               frontier_size=128, mesh=mesh)
    assert [r["valid?"] for r in out] == [k != 5 for k in range(13)]


def test_batched_independent_checker():
    h = []
    for k in range(4):
        sub = make_register_history(k, 20, seed=k, bad=(k == 3))
        for o in sub:
            h.append(o.assoc(value=ind.KV(k, o.value)))
    h = History(h).index()
    c = ind.batch_checker(models.CASRegister())
    r = c.check({}, h, {})
    assert r["valid?"] is False
    assert r["failures"] == [3]
    assert r["results"][0]["valid?"] is True


def test_batched_independent_checker_no_device_spec():
    """A model without a device spec degrades to the per-key CPU
    oracle instead of raising."""
    h = []
    for k in range(2):
        sub = make_register_history(k, 12, seed=k)
        for o in sub:
            h.append(o.assoc(value=ind.KV(k, o.value)))
    h = History(h).index()
    c = ind.batch_checker(models.NoOp())
    r = c.check({}, h, {})
    assert r["valid?"] is True
    assert set(r["results"]) == {0, 1}


def test_batched_escalation_on_overflow():
    """A frontier of 1 overflows instantly; lanes must escalate to the
    adaptive kernel and still produce correct verdicts."""
    from jepsen_tpu.ops import wgl_batch

    hists = [make_register_history(k, 25, seed=7 + k, bad=(k == 1))
             for k in range(3)]
    out = wgl_batch.check_many(models.CASRegister(), hists, frontier_size=1)
    assert [r["valid?"] for r in out] == [True, False, True]


# ---------------------------------------------------------------------------
# The columnar split: keyed histories stay columns through the split
# ---------------------------------------------------------------------------

MIXED_KEYS = [0, 1, "a", (2, "b"), None, 2 ** 40]


def keyed_history(seed, double_invoke=False):
    """A seeded keyed history with what the split and the crash split
    must carry through: nemesis ops, `:info` crashes, invokes with no
    completion, client ops with no KV value, unencodable and
    out-of-int32 inner values, an out-of-int32 process and keys of
    mixed types."""
    rng = random.Random(seed)
    procs = list(range(6)) + [2 ** 33]
    key_of = {p: rng.choice(MIXED_KEYS) for p in procs}
    open_: dict = {}
    ops_ = []
    for _ in range(240):
        r = rng.random()
        if r < 0.04:
            ops_.append(info_op("nemesis", rng.choice(["start", "stop"]),
                                None))
            continue
        live = [p for p in procs if p not in open_]
        if open_ and (r < 0.5 or not live):
            p = rng.choice(sorted(open_))
            f, v = open_.pop(p)
            kind = rng.random()
            if kind < 0.08:
                ops_.append(info_op(p, f, v))
            elif kind < 0.12:
                procs.remove(p)             # crashed, never completes
                continue
            elif kind < 0.2:
                ops_.append(fail_op(p, f, v))
            else:
                if f == "read":
                    inner = rng.choice([0, 1, 2, None])
                    v = v if not isinstance(v, ind.KV) \
                        else ind.KV(v.key, inner)
                ops_.append(ok_op(p, f, v))
            continue
        if not live:
            break
        p = rng.choice(live)
        f = rng.choice(["read", "write", "cas"])
        inner = {"read": None, "write": rng.choice([0, 1, 2, 2 ** 35]),
                 "cas": rng.choice([[0, 1], [1, 2], [0, 1, 2]])}[f]
        if rng.random() < 0.05:
            inner = "unencodable"
        # the out-of-int32 process keeps to its key, so the other keys'
        # columns stay complete
        bare = rng.random() < 0.06 and p < 2 ** 31
        v = inner if bare else ind.KV(key_of[p], inner)
        ops_.append(invoke_op(p, f, v))
        open_[p] = (f, v)
        if double_invoke and len(ops_) == 60:
            ops_.append(invoke_op(p, f, v))
    return ops_


def with_columns(ops_, how):
    if how == "journal":
        return History(ops_, journal=True).index()
    from jepsen_tpu.history import pack_history
    h = History(ops_).index()
    return h.attach_packed(pack_history(h))


def tagged_columns(packed):
    """The columns as comparable lists, f by tag rather than code."""
    tag = {c: t for t, c in packed.f_codes.items()}
    return {"index": packed.index.tolist(),
            "process": packed.process.tolist(),
            "type": packed.type.tolist(),
            "f": [tag.get(c) for c in packed.f.tolist()],
            "value": packed.value.tolist(),
            "value_ok": packed.value_ok.tolist(),
            "time": packed.time.tolist(),
            "vkind": packed.vkind.tolist(),
            "key": packed.key}


@pytest.mark.parametrize("how", ["journal", "pack_history"])
@pytest.mark.parametrize("seed", range(6))
def test_columnar_split_matches_object_split(seed, how):
    from jepsen_tpu.history import pack_history

    h = with_columns(keyed_history(seed), how)
    assert h.packed_columns().key is not None
    ks = sorted(ind.history_keys(h), key=repr)
    assert len(ks) >= 3
    subs = ind.subhistories(ks, h)
    assert all(isinstance(s, ind._KeyHistory) for s in subs)
    for k, sub in zip(ks, subs):
        ref = ind.subhistory(k, h)
        assert len(sub) == len(ref)
        # the columns stand for the ops before any op is built
        assert tagged_columns(sub.packed_columns()) \
            == tagged_columns(pack_history(ref))
        assert not sub.ops_built
        assert sub.ops == ref.ops
        assert sub.ops_built
        # un-keyed ops are shared, keyed ones are new unwrapped copies
        for o, r, parent in zip(sub.ops, ref.ops, (h.ops[i] for i in
                                                   sub._rows.tolist())):
            assert (o is parent) == (r is parent)


@pytest.mark.parametrize("seed", range(6))
def test_history_keys_with_and_without_columns(seed):
    ops_ = keyed_history(seed)
    with_cols = with_columns(ops_, "journal")
    plain = History(ops_)
    assert plain.packed_columns() is None
    assert ind.history_keys(with_cols) == ind.history_keys(plain)
    # a key the history lacks holds the un-keyed ops alone, either way
    absent_cols, absent = (ind.subhistories(["absent"], x)[0]
                           for x in (with_cols, plain))
    assert absent_cols.ops == absent.ops
    # and a history with columns but no keyed record has no keys
    assert ind.history_keys(
        History([invoke_op(0, "read", None), ok_op(0, "read", 1)],
                journal=True)) == set()


def test_unhashable_key_takes_the_object_split():
    from jepsen_tpu.history import KEY_UNHELD

    h = History([invoke_op(0, "read", ind.KV([1], None)),
                 ok_op(0, "read", ind.KV([1], 2))], journal=True)
    assert (h.packed_columns().key == KEY_UNHELD).all()
    with pytest.raises(TypeError):
        ind.history_keys(h)          # as the object walk always did


@pytest.mark.parametrize("double_invoke", [False, True])
@pytest.mark.parametrize("seed", range(6))
def test_split_crashed_cols_matches_objects(seed, double_invoke):
    from jepsen_tpu.ops import planner

    h = with_columns(keyed_history(seed, double_invoke), "journal")
    ks = sorted(ind.history_keys(h), key=repr)
    compared = 0
    for sub in ind.subhistories(ks, h):
        packed = sub.packed_columns()
        if not planner._cols_complete(packed):
            assert (packed.process == -2).any()   # the out-of-int32 id
            continue
        want = planner._split_crashed(sub.ops)
        got = planner._split_crashed_cols(packed)
        if want is None:
            assert got is None
            continue
        compared += 1
        assert got[0].tolist() == want[0].tolist()
        assert got[1] == len(want[1])
    assert compared >= 1 or double_invoke


def _counts(name):
    from jepsen_tpu import telemetry
    _, by_label = telemetry.REGISTRY.collect().get(name, ("counter", {}))
    return {dict(k).get("path"): float(m.value)
            for k, m in by_label.items()}


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


def journaled_campaign_history():
    """Three keys through the run loop's journal: one crash-free, one
    with a crashed write, one with a planted stale read."""
    ops_ = []
    for k, crash, bad in ((0, False, False), (1, True, False),
                          (2, False, True)):
        rng = random.Random(40 + k)
        value, sub = None, []
        for i in range(24):
            p = 10 * k + i % 2
            if rng.random() < 0.5:
                sub += [invoke_op(p, "read", None), ok_op(p, "read", value)]
            else:
                value = rng.randint(0, 4)
                sub += [invoke_op(p, "write", value),
                        ok_op(p, "write", value)]
        if crash:
            sub[4:4] = [invoke_op(10 * k + 3, "write", 9)]
            sub += [info_op(10 * k + 3, "write", 9)]
        if bad:
            sub += [invoke_op(10 * k + 5, "read", None),
                    ok_op(10 * k + 5, "read", 7)]
        ops_ += [o.assoc(value=ind.KV(k, o.value)) for o in sub]
    ops_.insert(7, info_op("nemesis", "start", None))
    h = History(journal=True)
    for o in ops_:
        h.append(o)
    return h.index()


def test_journaled_keyed_run_checks_as_its_object_twin():
    from jepsen_tpu import telemetry

    h = journaled_campaign_history()
    plain = History(list(h.ops))
    chk = ind.batch_checker(models.CASRegister())
    names = ("jepsen_scan_keys_total", "jepsen_split_keys_total",
             telemetry.SPLIT_OPS_BUILT)
    runs = {}
    for label, hist in (("columns", h), ("objects", plain)):
        before = {n: _counts(n) for n in names}
        r = chk.check({}, hist, {})
        runs[label] = (r, {n: _delta(_counts(n), before[n])
                           for n in names})
    (rc, nc), (ro, no) = runs["columns"], runs["objects"]
    assert rc["failures"] == ro["failures"] == [2]
    assert {k: (v["valid?"], v.get("op_index"))
            for k, v in rc["results"].items()} \
        == {k: (v["valid?"], v.get("op_index"))
            for k, v in ro["results"].items()}
    assert rc["results"][1].get("crashed_ignored") == 1
    assert rc["results"][2]["op_index"] is not None
    # with columns: no op-object scan, ops built for the invalid key only
    assert "objects" not in nc["jepsen_scan_keys_total"]
    assert nc["jepsen_split_keys_total"] == {"columnar": 3.0}
    assert nc[telemetry.SPLIT_OPS_BUILT] == {None: 1.0}
    # without (cli analyze): the object split and scans, as before —
    # three keys and the crashed key's stripped twin
    assert no["jepsen_scan_keys_total"] == {"objects": 4.0}
    assert no["jepsen_split_keys_total"] == {"objects": 3.0}
    assert no[telemetry.SPLIT_OPS_BUILT] == {None: 3.0}
