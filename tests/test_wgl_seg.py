"""Segment-parallel WGL engine (ops/wgl_seg.py): differential equivalence
with the CPU oracle, quiescent-cut segmentation, the multi-key batch
mode, decomposition, and the Unsupported fallback gates.

Mirrors the reference's checker-test strategy (checker_test.clj): literal
histories with known verdicts plus randomized differential coverage."""

import random

import numpy as np
import pytest

from jepsen_tpu import models
from jepsen_tpu.history import (History, fail_op, info_op, invoke_op, ok_op)
from jepsen_tpu.ops import planner, wgl_cpu, wgl_seg


def rand_history(seed, n_ops=80, conc=3, buggy=False, vmax=3,
                 crash_at=None, max_open=0, attach=False):
    """The ONE random register-history generator shared by the seg
    batteries and the fuzz battery (test_fuzz_differential).  The
    max_open / attach options are rng-neutral when off, so every
    pinned seed's stream is unchanged by their addition."""
    rng = random.Random(seed)
    ops, value = [], None
    open_ops = {}
    crashed = False
    i = 0
    while i < n_ops:
        p = rng.randrange(conc)
        if p in open_ops:
            ops.append(open_ops.pop(p))
            continue
        if max_open and len(open_ops) >= max_open:
            ops.append(open_ops.pop(rng.choice(list(open_ops))))
            continue
        i += 1
        f = rng.choice(("read", "read", "write", "cas"))
        if f == "read":
            ops.append(invoke_op(p, "read", None))
            v = value if not (buggy and rng.random() < 0.08) \
                else rng.randint(0, vmax)
            open_ops[p] = ok_op(p, "read", v)
        elif f == "write":
            v = rng.randint(0, vmax)
            ops.append(invoke_op(p, "write", v))
            value = v
            if crash_at is not None and i >= crash_at and not crashed:
                crashed = True
                open_ops[p] = info_op(p, "write", v)
            else:
                open_ops[p] = ok_op(p, "write", v)
        else:
            old, new = rng.randint(0, vmax), rng.randint(0, vmax)
            ops.append(invoke_op(p, "cas", [old, new]))
            if value == old:
                value = new
                open_ops[p] = ok_op(p, "cas", [old, new])
            else:
                open_ops[p] = fail_op(p, "cas", [old, new])
    for c in open_ops.values():
        ops.append(c)
    h = History(ops).index()
    if attach:
        from jepsen_tpu.history import pack_history
        h.attach_packed(pack_history(h))
    return h


class TestSingleHistory:
    def test_trivial_valid(self):
        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "read", None), ok_op(1, "read", 1)]).index()
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is True
        assert r["engine"] == "wgl_seg"

    def test_stale_read_invalid_with_localization(self):
        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "read", None), ok_op(1, "read", 2)]).index()
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is False
        assert r["anomaly"] == "nonlinearizable"
        assert r["op"]["f"] == "read"

    def test_concurrent_reorder_valid(self):
        # read overlaps the write that produces its value
        h = History([invoke_op(0, "write", 3),
                     invoke_op(1, "read", None), ok_op(1, "read", 3),
                     ok_op(0, "write", 3)]).index()
        assert wgl_seg.check(models.CASRegister(), h)["valid?"] is True

    def _differential(self, tr, seeds):
        mism = []
        for seed in seeds:
            h = rand_history(seed, buggy=(seed % 3 == 0),
                             conc=4 if seed % 2 else 3)
            want = wgl_cpu.check(models.CASRegister(), h)["valid?"]
            got = wgl_seg.check(models.CASRegister(), h,
                                target_returns_per_segment=tr)["valid?"]
            if want != got:
                mism.append(seed)
        assert not mism

    def test_differential_vs_cpu_oracle(self):
        # CI-shaped smoke slice; the full 25-seed x 3-granularity
        # battery is the slow twin below.
        self._differential(16, range(5))

    @pytest.mark.slow
    @pytest.mark.parametrize("tr", [4, 16, 512])
    def test_differential_vs_cpu_oracle_full(self, tr):
        self._differential(tr, range(25))

    def test_many_segments_produced(self):
        h = rand_history(3, n_ops=400)
        r = wgl_seg.check(models.CASRegister(), h,
                          target_returns_per_segment=8)
        assert r["segments"] > 4
        assert r["valid?"] is True

    def test_mutex_model(self):
        good = History([invoke_op(0, "acquire", None),
                        ok_op(0, "acquire", None),
                        invoke_op(1, "release", None),
                        ok_op(1, "release", None)]).index()
        assert wgl_seg.check(models.Mutex(), good)["valid?"] is True
        bad = History([invoke_op(0, "acquire", None),
                       ok_op(0, "acquire", None),
                       invoke_op(1, "acquire", None),
                       ok_op(1, "acquire", None)]).index()
        assert wgl_seg.check(models.Mutex(), bad)["valid?"] is False

    def test_crashed_history_handled_on_device(self):
        # One effect-bearing crashed write: the bounded crash kernel
        # (tier 2) carries it as a permanent slot; verdict == oracle.
        h = rand_history(5, crash_at=10)
        r = wgl_seg.check(models.CASRegister(), h)
        o = wgl_cpu.check(models.CASRegister(), h)
        assert r["valid?"] == o["valid?"]
        assert r["engine"] == "wgl_seg"
        assert r.get("crashed") == 1

    def test_no_device_spec_unsupported(self):
        h = rand_history(1)
        with pytest.raises(wgl_seg.Unsupported):
            wgl_seg.check(models.NoOp(), h)

    def test_empty_history(self):
        r = wgl_seg.check(models.CASRegister(), History([]))
        assert r["valid?"] is True


def crash_history(seed, n_calls=40, conc=3, crash_rate=0.1, vmax=3,
                  corrupt=False, crash_f=("read", "write", "cas"),
                  effect_rate=0.5):
    """Simulated register under concurrent clients where crashed ops may
    or may not have taken effect — the shape a real nemesis run
    produces (client timeout, DB may have applied the op)."""
    rng = random.Random(seed)
    ops, value = [], None
    open_procs = {}
    made = 0
    while made < n_calls or open_procs:
        closable = list(open_procs)
        if made >= n_calls or (closable and rng.random() < 0.5):
            if not closable:
                break
            p = rng.choice(closable)
            f, v, eff, crashed = open_procs.pop(p)
            if crashed:
                ops.append(info_op(p, f, v))
                if eff:
                    value = v if f == "write" else \
                        (v[1] if value == v[0] else value)
            elif f == "read":
                ops.append(ok_op(p, f, value))
            elif f == "write":
                value = v
                ops.append(ok_op(p, f, v))
            elif value == v[0]:
                value = v[1]
                ops.append(ok_op(p, f, v))
            else:
                ops.append(fail_op(p, f, v))
        else:
            free = [p for p in range(conc) if p not in open_procs]
            if not free:
                continue
            p = rng.choice(free)
            f = rng.choice(("read", "write", "cas"))
            v = (None if f == "read" else rng.randint(0, vmax)
                 if f == "write" else
                 [rng.randint(0, vmax), rng.randint(0, vmax)])
            crashed = rng.random() < crash_rate and f in crash_f
            eff = crashed and f != "read" and rng.random() < effect_rate
            open_procs[p] = (f, v, eff, crashed)
            ops.append(invoke_op(p, f, v))
            made += 1
    if corrupt:
        idx = [i for i, o in enumerate(ops)
               if o.type == "ok" and o.f == "read" and o.value is not None]
        if idx:
            i = rng.choice(idx)
            ops[i] = ops[i].assoc(value=(ops[i].value + 1) % (vmax + 1))
    return History(ops).index()


class TestCrashed:
    """Crash-tolerance tiers of the segment engine (differential vs the
    CPU oracle — knossos treats a crashed op as concurrent with the
    entire rest of the history, doc/tutorial/06-refining.md:12-19)."""

    def _battery(self, seeds):
        model = lambda: models.CASRegister()  # noqa: E731
        for seed in seeds:
            h = crash_history(seed, n_calls=30, corrupt=seed % 2 == 1)
            o = wgl_cpu.check(model(), h)
            try:
                r = wgl_seg.check(model(), h)
            except wgl_seg.Unsupported:
                continue           # residual case: serial fallback
            assert r["valid?"] == o["valid?"], (seed, r, o)

    @pytest.mark.slow
    def test_differential_battery(self):
        self._battery(range(2))

    @pytest.mark.slow
    def test_differential_battery_full(self):
        self._battery(range(8))

    def test_inert_crashed_reads_dropped(self):
        # >_MAX_CRASHED crashed reads: all inert => dropped outright,
        # exact verdict at full engine speed.
        h = crash_history(3, n_calls=60, crash_rate=0.45,
                          crash_f=("read",))
        ncrash = sum(1 for o in h if o.type == "info")
        assert ncrash > 4
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is True
        assert r["crashed_dropped"] == ncrash
        assert r["engine"] == "wgl_seg"

    def test_consumption_of_crashed_write(self):
        # A crashed write that took effect and is observed by a later
        # read: valid ONLY if the crashed op is linearized (tier 2).
        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "write", 2),   # crashes, takes effect
                     invoke_op(0, "read", None), ok_op(0, "read", 2),
                     invoke_op(0, "read", None), ok_op(0, "read", 2),
                     info_op(1, "write", 2)]).index()
        o = wgl_cpu.check(models.CASRegister(), h)
        assert o["valid?"] is True
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is True
        assert r.get("crashed") == 1

    def test_single_use_of_crashed_write(self):
        # The crashed write may be linearized ONCE: a second read of its
        # value after an intervening overwrite is non-linearizable.
        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "write", 2),   # crashes
                     invoke_op(0, "read", None), ok_op(0, "read", 2),
                     invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(0, "read", None), ok_op(0, "read", 2),
                     info_op(1, "write", 2)]).index()
        o = wgl_cpu.check(models.CASRegister(), h)
        assert o["valid?"] is False
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is False

    def test_many_ineffective_crashes_stripped_valid(self):
        # >_MAX_CRASHED effect-free crashed writes on a valid history:
        # tier 3 proves validity on the stripped twin.
        h = crash_history(11, n_calls=80, crash_rate=0.2,
                          crash_f=("write", "cas"), effect_rate=0.0)
        ncrash = sum(1 for o in h if o.type == "info")
        assert ncrash > 4
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["valid?"] is True
        assert r.get("crashed_ignored") == ncrash or \
            r.get("crashed_dropped", 0) + r.get("crashed", 0) == ncrash

    def test_mutex_crashed_acquire(self):
        # A crashed acquire may or may not hold the lock; both
        # continuations must be explored (acquire is NOT inert).
        good = History([invoke_op(0, "acquire", None),
                        info_op(0, "acquire", None),
                        invoke_op(1, "acquire", None),
                        ok_op(1, "acquire", None)]).index()
        o = wgl_cpu.check(models.Mutex(), good)
        r = wgl_seg.check(models.Mutex(), good)
        assert r["valid?"] == o["valid?"] is True

        # two COMPLETED acquires with no release can never both
        # linearize, crashed acquire or not
        bad = History([invoke_op(0, "acquire", None),
                       info_op(0, "acquire", None),
                       invoke_op(1, "acquire", None),
                       ok_op(1, "acquire", None),
                       invoke_op(2, "acquire", None),
                       ok_op(2, "acquire", None)]).index()
        o = wgl_cpu.check(models.Mutex(), bad)
        r = wgl_seg.check(models.Mutex(), bad)
        assert r["valid?"] == o["valid?"] is False

    def test_crashed_release_consumed(self):
        # The second acquire is only linearizable if the CRASHED
        # release took effect - consumption on the mutex model.
        h = History([invoke_op(0, "acquire", None),
                     ok_op(0, "acquire", None),
                     invoke_op(0, "release", None),
                     invoke_op(1, "acquire", None),
                     ok_op(1, "acquire", None),
                     info_op(0, "release", None)]).index()
        o = wgl_cpu.check(models.Mutex(), h)
        r = wgl_seg.check(models.Mutex(), h)
        assert r["valid?"] == o["valid?"] is True

    def test_residual_many_effectful_crashes_unsupported(self):
        # Many effect-bearing crashed writes whose effects are observed:
        # stripped twin is invalid, bound exceeded => Unsupported (the
        # serial engines own this residue).
        ops = [invoke_op(9, "write", 0), ok_op(9, "write", 0)]
        for i in range(6):
            ops += [invoke_op(i, "write", i % 3 + 1)]
        for i in range(6):
            ops += [invoke_op(9, "read", None),
                    ok_op(9, "read", i % 3 + 1)]
            ops += [invoke_op(8, "write", 0), ok_op(8, "write", 0)]
        for i in range(6):
            ops += [info_op(i, "write", i % 3 + 1)]
        h = History(ops).index()
        o = wgl_cpu.check(models.CASRegister(), h)
        with pytest.raises(wgl_seg.Unsupported):
            wgl_seg.check(models.CASRegister(), h)
        # ...and the checker-level chain still reaches the exact verdict
        from jepsen_tpu import checker as ck
        c = ck.linearizable({"model": models.cas_register()})
        r = c.check({}, h)
        assert r["valid?"] == o["valid?"]


class TestDecomposition:
    def test_register_family_decomposes(self):
        h = rand_history(2)
        spec = models.CASRegister().device_spec()
        pl = wgl_seg.plan(wgl_seg.prepare(h), spec, models.CASRegister())
        assert pl.diag_w is not None
        # reads are pure-diagonal; writes/cas have one constant target
        assert (pl.diag_w + pl.const_w <= 1.0 + 1e-6).all()

    def test_state_enumeration_closed(self):
        h = rand_history(4, vmax=2)
        spec = models.CASRegister().device_spec()
        pl = wgl_seg.plan(wgl_seg.prepare(h), spec, models.CASRegister())
        Sn = pl.states.shape[0]
        # unknown + at most vmax+1 written values
        assert 1 <= Sn <= 5
        assert (pl.next_state < Sn).all()


class TestRegsPath:
    """The register-delta batch kernel (default): per-return invoke
    deltas + device-maintained open-set registers, vs the candidate-table
    kernel and the CPU oracle."""

    def test_regs_is_default_engine(self):
        hists = [rand_history(700 + s, n_ops=40) for s in range(4)]
        res = wgl_seg.check_many(models.CASRegister(), hists)
        assert all(r["engine"] == "wgl_seg_batch_regs" for r in res)

    @pytest.mark.slow
    def test_regs_matches_table_kernel_and_oracle(self, monkeypatch):
        # high concurrency (R up to 6) forces invoke bursts that spill
        # into virtual rows; buggy keys must be flagged by both kernels
        hists = [rand_history(800 + s, n_ops=60, conc=1 + s % 6,
                              buggy=(s % 3 == 0)) for s in range(18)]
        m = models.CASRegister()
        res_regs = wgl_seg.check_many(m, hists)
        # force the candidate-table lanes: shut the register-delta gate
        monkeypatch.setattr(planner, "_regs_eligible",
                            lambda *a, **k: False)
        res_tab = wgl_seg.check_many(m, hists)
        monkeypatch.undo()
        assert all(r["engine"] == "wgl_seg_batch_regs" for r in res_regs)
        assert all(r["engine"] == "wgl_seg_batch" for r in res_tab)
        for h, rr, rt in zip(hists, res_regs, res_tab):
            want = wgl_cpu.check(m, h)["valid?"]
            assert rr["valid?"] == want
            assert rt["valid?"] == want

    def test_regs_slot_reuse_after_retire(self):
        # sequential ops maximally reuse slot 0: every row both retires
        # and re-registers the same slot (I = min(2, R) = 1 here)
        ops = []
        for v in range(12):
            ops.append(invoke_op(0, "write", v))
            ops.append(ok_op(0, "write", v))
            ops.append(invoke_op(0, "read", None))
            ops.append(ok_op(0, "read", v))
        good = History(list(ops)).index()
        ops[-1] = ok_op(0, "read", 77)          # stale final read
        bad = History(ops).index()
        res = wgl_seg.check_many(models.CASRegister(), [good, bad])
        assert res[0]["valid?"] is True
        assert res[1]["valid?"] is False

    def test_regs_mesh_sharded(self):
        import jax
        from jax.sharding import Mesh

        hists = [rand_history(900 + s, n_ops=30, conc=3,
                              buggy=(s == 5)) for s in range(16)]
        mesh = Mesh(np.array(jax.devices()), ("keys",))
        m = models.CASRegister()
        res = wgl_seg.check_many(m, hists, mesh=mesh, mesh_axis="keys")
        assert all(r["engine"] == "wgl_seg_batch_regs" for r in res)
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(m, h)["valid?"]

    def test_regs_nibble_nondecomposable_model(self):
        # A mod-3 incrementing counter: 'inc' maps each state to a
        # DIFFERENT target (s -> s+1 mod 3), so _decompose() fails and
        # the regs kernel must take its nibble (non-decomposed) branch.
        import dataclasses

        import jax.numpy as jnp

        def mod3_step(state, f, a, b, a_ok):
            s = state[0]
            is_inc = f == 0
            ns = jnp.where(is_inc, (s + 1) % 3, s)
            legal = is_inc | ((f == 1) & (a.astype(jnp.int32) == s))
            return jnp.where(legal, ns, s)[None], legal

        @dataclasses.dataclass(frozen=True)
        class Mod3(models.Model):
            value: int = 0

            def step(self, op):
                if op.f == "inc":
                    return Mod3((self.value + 1) % 3)
                if op.f == "read":
                    if op.value == self.value:
                        return self
                    return models.inconsistent("bad read")
                return models.inconsistent(f"unknown f {op.f!r}")

            def device_spec(self):
                return models.DeviceSpec(
                    1, {"inc": 0, "read": 1},
                    lambda m: np.array([m.value], np.int32), mod3_step)

        from jepsen_tpu.ops.wgl_seg import _decompose, _encode_calls, \
            _enumerate_states
        from jepsen_tpu.ops.prep import prepare

        def mk(read_vals):
            ops = []
            for i, rv in enumerate(read_vals):
                ops.append(invoke_op(0, "inc", None))
                ops.append(ok_op(0, "inc", None))
                ops.append(invoke_op(1, "read", rv))
                ops.append(ok_op(1, "read", rv))
            return History(ops).index()

        m = Mod3()
        good = mk([1, 2, 0, 1])
        bad = mk([1, 2, 0, 2])
        # prove the model is non-decomposable (so the nibble branch runs)
        spec = m.device_spec()
        prep = prepare(good)
        uops, _ = _encode_calls(prep.calls, spec)
        _, legal, nxt = _enumerate_states(
            spec, np.array([0], np.int32), uops, 64)
        assert _decompose(legal, nxt) == (None, None, None)
        res = wgl_seg.check_many(m, [good, bad])
        assert all(r["engine"] == "wgl_seg_batch_regs" for r in res)
        assert res[0]["valid?"] is True
        assert res[1]["valid?"] is False
        assert res[1]["valid?"] == wgl_cpu.check(m, bad)["valid?"]
        # single-history J=Sn regs path through the same nibble branch
        r1 = wgl_seg.check(m, good, target_returns_per_segment=2)
        assert r1["valid?"] is True and r1["segments"] > 1, r1

    def test_regs_mutex_small_state(self):
        m = models.Mutex()
        ops = []
        for i in range(6):
            ops.append(invoke_op(0, "acquire", None))
            ops.append(ok_op(0, "acquire", None))
            ops.append(invoke_op(0, "release", None))
            ops.append(ok_op(0, "release", None))
        good = History(list(ops)).index()
        bad = History(ops[:-2] + [invoke_op(1, "acquire", None),
                                  ok_op(1, "acquire", None)]).index()
        res = wgl_seg.check_many(m, [good, bad])
        assert res[0]["valid?"] is True
        assert res[1]["valid?"] == wgl_cpu.check(m, bad)["valid?"]


class TestBatch:
    def test_batch_matches_oracle(self):
        hists = [rand_history(100 + s, n_ops=40,
                              buggy=(s % 4 == 0)) for s in range(30)]
        res = wgl_seg.check_many(models.CASRegister(), hists)
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(
                models.CASRegister(), h)["valid?"]

    def test_crashed_keys_stay_in_batch(self):
        # A crashed key rides the batch as its crash-stripped twin when
        # the stripped verdict is valid; otherwise it is re-checked
        # exactly (bounded crash kernel) — never a wrong verdict.
        hists = [rand_history(s, n_ops=30) for s in range(6)]
        hists[2] = rand_history(2, n_ops=30, crash_at=5)
        res = wgl_seg.check_many(models.CASRegister(), hists)
        assert all(r["engine"].startswith("wgl_seg")
                   for r in res), [r["engine"] for r in res]
        assert "crashed_ignored" in res[2] or "crashed" in res[2]
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(
                models.CASRegister(), h)["valid?"]

    def test_unencodable_key_falls_back_to_cpu(self):
        # A value outside int32 is beyond BOTH device engines; the
        # default fallback chain must still reach the CPU oracle
        # instead of crashing the whole batch.
        hists = [rand_history(s, n_ops=20) for s in range(3)]
        big = History([invoke_op(0, "write", 2 ** 40),
                       ok_op(0, "write", 2 ** 40),
                       invoke_op(1, "read", None),
                       ok_op(1, "read", 2 ** 40)]).index()
        hists[1] = big
        res = wgl_seg.check_many(models.CASRegister(), hists)
        assert res[1]["valid?"] is True
        assert res[1]["engine"] == "fallback"
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(
                models.CASRegister(), h)["valid?"]

    def test_failed_encode_does_not_pollute_shared_intern(self):
        # A key that raises Unsupported mid-encode must leave the shared
        # seen/rows tables untouched — its ops would otherwise grow the
        # enumerated state space for every other key in the batch.
        spec = models.CASRegister().device_spec()
        good = wgl_seg.prepare(rand_history(1, n_ops=10))
        bad = wgl_seg.prepare(History(
            [invoke_op(0, "write", 5), ok_op(0, "write", 5),
             invoke_op(0, "write", 2 ** 40),
             ok_op(0, "write", 2 ** 40)]).index())
        seen: dict = {}
        rows: list = []
        wgl_seg._encode_calls(good.calls, spec, seen, rows)
        n_rows = len(rows)
        with pytest.raises(wgl_seg.Unsupported):
            wgl_seg._encode_calls(bad.calls, spec, seen, rows)
        assert len(rows) == n_rows
        assert len(seen) == n_rows

    def test_native_scan_matches_python_scan(self):
        # the C scanner must be bit-identical to the Python twin on
        # every in-scope key and agree on out-of-scope verdicts
        from jepsen_tpu import native

        mod = native.histscan()
        if mod is None:
            pytest.skip("no C toolchain")
        spec = models.CASRegister().device_spec()
        for s in range(25):
            h = rand_history(400 + s, n_ops=40,
                             crash_at=(12 if s % 5 == 0 else None),
                             conc=2 + s % 4)
            seen_p, rows_p = {}, []
            seen_c, rows_c = {}, []
            fk_p = wgl_seg._fast_scan(h, spec, seen_p, rows_p, 10)
            fk_c = wgl_seg._native_scan(h.ops, spec, seen_c, rows_c, 10)
            assert (fk_p is None) == (fk_c is None), s
            assert [tuple(int(x) for x in r) for r in rows_c] == \
                [tuple(int(x) for x in r) for r in rows_p], s
            if fk_p is None:
                continue
            assert fk_c.n_calls == fk_p.n_calls
            assert fk_c.max_open == fk_p.max_open
            assert np.array_equal(np.asarray(fk_c.cuts),
                                  np.asarray(fk_p.cuts))
            rs, counts, cs, cu = fk_c.arrays
            flat_p = [(slot, s2, u2) for slot, cands in fk_p.rets
                      for s2, u2 in cands]
            flat_c = []
            k = 0
            for r, (slot, cnt) in enumerate(zip(rs, counts)):
                assert slot == fk_p.rets[r][0]
                for j in range(cnt):
                    flat_c.append((int(slot), int(cs[k]), int(cu[k])))
                    k += 1
            assert flat_c == flat_p

    def test_int_subclass_values_encode_by_value(self):
        # IntEnum-style values must encode by VALUE in both scanners,
        # exactly like the serial engines' isinstance-based encoder —
        # encoding them as "unknown" changes verdicts
        import enum

        class V(enum.IntEnum):
            A = 1
            B = 2

        good = History([invoke_op(0, "write", V.A),
                        ok_op(0, "write", V.A),
                        invoke_op(1, "read", None),
                        ok_op(1, "read", 1)]).index()
        bad = History([invoke_op(0, "write", V.A),
                       ok_op(0, "write", V.A),
                       invoke_op(1, "read", None),
                       ok_op(1, "read", 2)]).index()
        res = wgl_seg.check_many(models.CASRegister(), [good, bad])
        assert [r["valid?"] for r in res] == [True, False]
        assert all(r["engine"].startswith("wgl_seg_batch") for r in res)

    def test_single_history_mesh_sharded(self):
        # ONE history's segment axis sharded over the 8-device mesh
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("segs",))
        h = rand_history(31, n_ops=400, conc=3)
        r = wgl_seg.check(models.CASRegister(), h, mesh=mesh,
                          mesh_axis="segs",
                          target_returns_per_segment=4)
        assert r["valid?"] is True
        assert r["segments"] >= 8
        assert r["sharded"] is True
        bad = History(list(h) + [invoke_op(9, "read", None),
                                 ok_op(9, "read", 77)]).index()
        r = wgl_seg.check(models.CASRegister(), bad, mesh=mesh,
                          mesh_axis="segs",
                          target_returns_per_segment=4)
        assert r["valid?"] is False
        assert r["sharded"] is True
        assert r.get("op_index") is not None
        # and without a mesh the flag reads False
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["sharded"] is False

    @pytest.mark.parametrize("conc", [8, 9])
    def test_table_lanes_past_the_regs_gate(self, conc):
        # at concurrency 8-9 the batch's R passes the register-delta
        # gate (R <= 6): the shape alone routes every key to the
        # candidate-table lanes, buggy keys included
        m = models.CASRegister()
        hists = [rand_history(950 + 10 * conc + s, n_ops=70, conc=conc,
                              buggy=(s % 3 == 1)) for s in range(6)]
        res = wgl_seg.check_many(m, hists)
        assert res[0]["dispatch"]["R"] > 6
        want = [wgl_cpu.check(m, h)["valid?"] for h in hists]
        assert False in want
        for r, w in zip(res, want):
            assert r["engine"] == "wgl_seg_batch"
            assert r["valid?"] == w

    def test_native_disabled_env(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_NO_NATIVE", "1")
        from jepsen_tpu import native
        assert native.histscan() is None

    def test_empty_key(self):
        hists = [History([]), rand_history(1, n_ops=20)]
        res = wgl_seg.check_many(models.CASRegister(), hists)
        assert res[0]["valid?"] is True
        assert res[0]["op_count"] == 0

    def test_mesh_sharded(self):
        import jax
        from jax.sharding import Mesh

        n = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ("keys",))
        hists = [rand_history(200 + s, n_ops=24, conc=2)
                 for s in range(2 * n)]
        bad = History(list(hists[0])
                      + [invoke_op(9, "read", None),
                         ok_op(9, "read", 77)]).index()
        hists[0] = bad
        res = wgl_seg.check_many(models.CASRegister(), hists,
                                 mesh=mesh, mesh_axis="keys")
        assert res[0]["valid?"] is False
        for h, r in zip(hists[1:], res[1:]):
            assert r["valid?"] == wgl_cpu.check(
                models.CASRegister(), h)["valid?"]


class TestCheckerIntegration:
    def test_linearizable_auto_uses_seg(self):
        from jepsen_tpu import checker as ck

        h = rand_history(7)
        c = ck.linearizable({"model": models.cas_register()})
        r = c.check({}, h)
        assert r["valid?"] == wgl_cpu.check(
            models.CASRegister(), h)["valid?"]
        assert r.get("engine") == "wgl_seg"

    def test_competition_mode(self):
        from jepsen_tpu import checker as ck

        c = ck.linearizable({"model": models.cas_register(),
                             "algorithm": "competition"})
        good = rand_history(3)
        r = c.check({}, good)
        assert r["valid?"] is True
        assert r["competition-winner"] in ("device", "cpu")
        bad = rand_history(4, buggy=True, n_ops=120)
        o = wgl_cpu.check(models.CASRegister(), bad)
        r = c.check({}, bad)
        assert r["valid?"] == o["valid?"]

    def test_competition_unknown_does_not_win(self):
        # A CPU racer capped at max_configs=1 hits config-explosion
        # almost instantly and reports :unknown; that must NOT beat the
        # device racer's definitive verdict (ADVICE r2: competition was
        # strictly worse than auto on hard histories otherwise).
        from jepsen_tpu import checker as ck

        c = ck.linearizable({"model": models.cas_register(),
                             "algorithm": "competition",
                             "max_configs": 1})
        h = rand_history(11, n_ops=80, conc=3)
        r = c.check({}, h)
        assert r["valid?"] in (True, False)
        from jepsen_tpu.ops import wgl_cpu as oracle
        assert r["valid?"] == oracle.check(
            models.CASRegister(), h)["valid?"]

    def test_invalid_device_verdict_carries_analysis_artifacts(self):
        # checker.clj:155-158 parity: configs + final-paths (truncated
        # to 10) accompany invalid verdicts even on the device path.
        from jepsen_tpu import checker as ck

        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "read", None),
                     ok_op(1, "read", 2)]).index()
        c = ck.linearizable({"model": models.cas_register()})
        r = c.check({}, h)
        assert r["valid?"] is False
        assert r.get("engine", "").startswith("wgl")
        assert isinstance(r.get("configs"), list)
        paths = r.get("final-paths")
        assert paths and len(paths) <= 10
        assert any(at["inconsistent"] for pth in paths
                   for at in pth["attempts"])

    @pytest.mark.parametrize("engine_tried", [False, True])
    def test_artifact_rebuild_is_capped(self, engine_tried, monkeypatch):
        # The final-paths rebuild runs the oracle, which is exponential
        # in crashed calls: it runs under wgl_cpu.ARTIFACT_CAPS, and not
        # at all when the engine's own capped attempt already ran out.
        from jepsen_tpu import checker as ck

        h = History([invoke_op(0, "write", 1), ok_op(0, "write", 1),
                     invoke_op(1, "read", None),
                     ok_op(1, "read", 2)]).index()
        dev = {"valid?": False, "op_index": 2, "engine": "wgl_seg"}
        if engine_tried:
            dev["final-paths-error"] = "oracle unknown: timeout"
            monkeypatch.setattr(wgl_cpu, "check", lambda *a, **k: 1 / 0)
        else:
            monkeypatch.setitem(wgl_cpu.ARTIFACT_CAPS, "max_configs", 0)
        c = ck.linearizable({"model": models.cas_register()})
        monkeypatch.setattr(c, "_device_check", lambda hist: dict(dev))
        r = c.check({}, h)
        assert r["valid?"] is False and r["op_index"] == 2
        assert "final-paths" not in r and "configs" not in r
        assert r["final-paths-error"] == (
            "oracle unknown: timeout" if engine_tried
            else "oracle unknown: config-explosion")

    def test_linearizable_crashed_stays_on_device(self):
        # Crash-bearing histories stay on the segment engine (bounded
        # crash kernel) instead of falling back to the serial path.
        from jepsen_tpu import checker as ck

        h = rand_history(8, crash_at=12)
        c = ck.linearizable({"model": models.cas_register()})
        r = c.check({}, h)
        assert r["valid?"] == wgl_cpu.check(
            models.CASRegister(), h)["valid?"]
        assert r.get("engine") == "wgl_seg"


class TestColumnarScanAndPipeline:
    """Round-3 paths: the native columnar scan (fast_scan_cols), the
    delta packer, the on-device composed verdict, and check_pipeline —
    all must be verdict-identical to the CPU oracle and, where they
    share outputs, bit-identical to the object scan."""

    def test_cols_scan_bit_identical_to_object_scan(self):
        from jepsen_tpu.history import pack_history
        spec = models.CASRegister(0).device_spec()
        agree = 0
        for s in range(30):
            h = rand_history(s, n_ops=160, conc=4,
                             crash_at=40 if s % 6 == 0 else None)
            pk = pack_history(h)
            s1, r1 = {}, []
            fk1 = wgl_seg._native_scan(h.ops, spec, s1, r1, 10)
            s2, r2 = {}, []
            fk2 = wgl_seg._native_scan_cols(pk, spec, s2, r2, 10)
            assert (fk1 is None) == (fk2 is None), s
            if fk1 is None:
                continue
            agree += 1
            a1, a2 = wgl_seg._fk_arrays(fk1), wgl_seg._fk_arrays(fk2)
            assert all(np.array_equal(x, y) for x, y in zip(a1, a2))
            assert r1 == r2 and s1 == s2
            assert np.array_equal(np.asarray(fk1.cuts),
                                  np.asarray(fk2.cuts))
            # delta stream invariants: counts sum to calls, one delta
            # per ok call, concatenation ordered by invoke position
            dc, dslot, duop = fk2.deltas
            assert dc.sum() == len(dslot) == len(duop) == fk2.n_calls
            assert len(dc) == fk2.n_rets
        assert agree >= 20

    @pytest.mark.slow
    def test_delta_packer_matches_snapshot_packer_verdicts(self):
        from jepsen_tpu.history import pack_history
        model = models.CASRegister(0)
        for s in range(24):
            h = rand_history(300 + s, n_ops=200, conc=4,
                             buggy=(s % 3 == 0))
            h.attach_packed(pack_history(h))
            r = wgl_seg.check(model, h)
            o = wgl_cpu.check(model, h)
            assert r["valid?"] == o["valid?"], s

    def test_check_pipeline_matches_oracle(self):
        from jepsen_tpu.history import pack_history
        model = models.CASRegister(0)
        hists = [rand_history(500 + s, n_ops=220, conc=4,
                              buggy=(s % 4 == 1)) for s in range(10)]
        for h in hists:
            h.attach_packed(pack_history(h))
        res = wgl_seg.check_pipeline(model, hists)
        for h, r in zip(hists, res):
            o = wgl_cpu.check(model, h)
            assert r["valid?"] == o["valid?"]
            if r["valid?"] is False:
                assert r.get("op_index") == o.get("op_index")

    def test_check_pipeline_strays_and_crashes(self):
        # crashed histories fall off the pipeline but still get exact
        # verdicts via the straggler path
        from jepsen_tpu.history import pack_history
        model = models.CASRegister(0)
        hists = [rand_history(700 + s, n_ops=160, conc=3,
                              crash_at=50 if s % 2 == 0 else None)
                 for s in range(6)]
        for h in hists:
            h.attach_packed(pack_history(h))
        res = wgl_seg.check_pipeline(model, hists)
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(model, h)["valid?"]

    def test_pipeline_without_packed_columns(self):
        model = models.CASRegister(0)
        hists = [rand_history(900 + s, n_ops=120, conc=3)
                 for s in range(4)]
        res = wgl_seg.check_pipeline(model, hists)
        for h, r in zip(hists, res):
            assert r["valid?"] == wgl_cpu.check(model, h)["valid?"]

    def test_pipeline_speculative_death_exact_rerun(self, monkeypatch):
        # VERDICT r4 #5a: with spec_rounds < R, an invalid history's
        # speculative death must trigger the exact re-run (flagged
        # `speculation: exact-rerun`) and carry the oracle's witness —
        # pins the operational trigger of the soundness argument.
        from jepsen_tpu.history import pack_history
        monkeypatch.setattr(wgl_seg, "SPEC_ROUNDS", 1)
        model = models.CASRegister(0)
        hists = [rand_history(1200 + s, n_ops=140, conc=5,
                              buggy=(s % 2 == 1)) for s in range(4)]
        for h in hists:
            h.attach_packed(pack_history(h))
        res = wgl_seg.check_pipeline(model, hists)
        fired = 0
        for h, r in zip(hists, res):
            o = wgl_cpu.check(model, h)
            assert r["valid?"] == o["valid?"]
            if r["valid?"] is False and r.get("pipelined") \
                    and r.get("speculation") == "exact-rerun":
                fired += 1
                assert r.get("op_index") == o.get("op_index")
        # at least one buggy deep-enough history must have exercised
        # the rerun branch (R >= 2 > spec_rounds=1 for these shapes)
        assert fired >= 1

    def test_pipeline_spec_rounds_sweep_verdict_identical(
            self, monkeypatch):
        # VERDICT r4 #5b: SPEC_ROUNDS in {1, 2, R} must not
        # change any verdict or witness (fewer rounds only
        # under-approximate; survivors are exact VALID, deaths re-run).
        from jepsen_tpu.history import pack_history
        model = models.CASRegister(0)
        hists = [rand_history(1300 + s, n_ops=140, conc=5,
                              buggy=(s % 3 == 2)) for s in range(4)]
        for h in hists:
            h.attach_packed(pack_history(h))
        outs = []
        for sr in (1, 2, 8):             # 8 clamps to R: exact rounds
            monkeypatch.setattr(wgl_seg, "SPEC_ROUNDS", sr)
            outs.append(wgl_seg.check_pipeline(model, hists))
        for rs in zip(*outs):
            assert len({r["valid?"] for r in rs}) == 1
            assert len({r.get("op_index") for r in rs}) == 1
        # at full rounds a death is exact — the rerun must NOT fire
        assert not any(r.get("speculation") for r in outs[-1])

    def test_delta_and_snapshot_packers_place_identically(self):
        # Both packers must produce the same shape, identical return
        # rows, and the same SET of (slot, uop) registrations in every
        # row — a direct guard on the duplicated spill-row layout math
        # staying in sync.  (Within-row ORDER may differ: the delta
        # stream is invoke-ordered, snapshots are slot-ordered; both
        # register before the row's closure, so order is immaterial.)
        from jepsen_tpu.history import pack_history
        spec = models.CASRegister(0).device_spec()
        checked = 0
        for s in range(12):
            h = rand_history(40 + s, n_ops=160, conc=3)
            seen, rows = {}, []
            fk = wgl_seg._native_scan_cols(pack_history(h), spec,
                                           seen, rows, 10)
            if fk is None or not fk.n_calls:
                continue
            R = fk.max_open
            cuts = np.asarray(fk.cuts, np.int32)
            seg_ends = wgl_seg._segment_ends(cuts, 16)
            U, I = len(rows), min(2, R)
            d_ret, d_islot, d_iuop, d_lp = wgl_seg._pack_regs_single(
                fk, seg_ends, R, U, I)
            seg_fk = wgl_seg._segments_from_fk(fk, R, seg_ends)
            s_ret, s_islot, s_iuop, s_lp = wgl_seg._pack_regs(
                [(k, f) for k, f in enumerate(seg_fk)],
                len(seg_ends), R, U, I)
            assert d_lp == s_lp
            assert np.array_equal(d_ret, s_ret)

            def regsets(ret, islot, iuop):
                # registrations grouped per return (virtual spill rows
                # attach to the return they precede — closure reaches
                # the same fixpoint anywhere before the retirement)
                L, K, _ = islot.shape
                out = []
                for k in range(K):
                    acc, groups = [], []
                    for r in range(L):
                        acc += [(int(a), int(b)) for a, b in
                                zip(islot[r, k], iuop[r, k]) if a >= 0]
                        if ret[r, k] >= 0:
                            groups.append((int(ret[r, k]),
                                           tuple(sorted(acc))))
                            acc = []
                    groups.append((-1, tuple(sorted(acc))))
                    out.append(groups)
                return out
            assert regsets(d_ret, d_islot, d_iuop) == \
                regsets(s_ret, s_islot, s_iuop)
            checked += 1
        assert checked >= 6

    def test_namedtuple_cas_value_encodes_as_pair_everywhere(self):
        # The C object scan, the C columnar scan, and the Python twin
        # must intern identical uop rows for tuple/list SUBCLASS values
        # (ADVICE r3: CheckExact in the C scan diverged).
        import collections
        from jepsen_tpu.history import History, pack_history
        P = collections.namedtuple("P", "old new")
        h = History([invoke_op(0, "write", 0), ok_op(0, "write", 0),
                     invoke_op(0, "cas", P(0, 1)),
                     ok_op(0, "cas", P(0, 1)),
                     invoke_op(1, "read", None),
                     ok_op(1, "read", 1)]).index()
        spec = models.CASRegister(0).device_spec()
        outs = []
        for scan in (wgl_seg._native_scan,
                     lambda o, *a: wgl_seg._native_scan_cols(
                         pack_history(h), *a),
                     wgl_seg._fast_scan):
            seen, rows = {}, []
            arg = h if scan is wgl_seg._fast_scan else h.ops
            fk = scan(arg, spec, seen, rows, 10)
            outs.append(sorted(tuple(r) for r in rows))
        assert outs[0] == outs[1] == outs[2]
        r = wgl_seg.check(models.CASRegister(0), h)
        o = wgl_cpu.check(models.CASRegister(0), h)
        assert r["valid?"] == o["valid?"] is True

    def test_journal_append_huge_int_does_not_crash(self):
        # ADVICE r3: the run loop journals every op; values beyond
        # int64 must mark not-ok instead of raising OverflowError.
        h = History(journal=True)
        h.append(invoke_op(0, "write", 2 ** 70))
        h.append(ok_op(0, "write", 2 ** 70))
        cols = h.packed_columns()
        assert cols is not None and not cols.value_ok[0, 0]


class TestRefutation:
    """Round-3 refutation paths: segment-local witness localization
    (entry-mask replay) and the sound crash-relaxed refutation tier."""

    def test_refutation_smoke(self):
        # default-tier representative of the slow batteries below:
        # the first crash-heavy corrupt history that stays on the
        # batched engine must fire the crash-relaxed tier and name an
        # exact-op witness equal to the oracle's (stops at one match;
        # the full sweeps are the slow twins)
        from jepsen_tpu.history import History, pack_history
        model = models.CASRegister(0)
        for s in range(40, 60):
            h0 = crash_history(s, n_calls=80, conc=3, crash_rate=0.15,
                               effect_rate=0.6)
            ops = list(h0)
            idx = [i for i, o in enumerate(ops)
                   if o.type == "ok" and o.f == "read"]
            if len(idx) < 4:
                continue
            ops[idx[len(idx) * 3 // 4]] = \
                ops[idx[len(idx) * 3 // 4]].assoc(value=99)
            h = History(ops).index()
            h.attach_packed(pack_history(h))
            try:
                r = wgl_seg.check(model, h, localize=False)
            except wgl_seg.Unsupported:
                continue
            if r.get("refutation") != "crash-relaxed":
                continue
            o = wgl_cpu.check(model, h, max_configs=4_000_000)
            assert r["valid?"] is False and o["valid?"] is False
            assert r["witness"] == "relaxed-exact"
            assert r["op_index"] == o["op_index"]
            return
        pytest.fail("no crash-relaxed firing shape in the seed range")

    @pytest.mark.slow
    def test_deep_witness_matches_oracle(self):
        # seed 13 regression: a fail pair straddling the segment end
        # must drop ONLY the unpaired invoke, not every invoke of that
        # process, and the replay must be ONE union walk over the
        # entry states (per-state replays die at different returns).
        from jepsen_tpu.history import pack_history
        model = models.CASRegister(0)
        for s in (3, 9, 13, 15, 18, 21):
            h = rand_history(s, n_ops=500, conc=4, buggy=True)
            h.attach_packed(pack_history(h))
            r = wgl_seg.check(model, h)
            o = wgl_cpu.check(model, h)
            assert r["valid?"] == o["valid?"]
            if r["valid?"] is False:
                assert r.get("op_index") == o.get("op_index"), s

    @pytest.mark.slow
    def test_relaxed_refutation_sound_and_bounded(self):
        from jepsen_tpu.history import History, pack_history
        model = models.CASRegister(0)
        fired = 0
        for s in range(10):
            h = crash_history(s, n_calls=70, conc=3, crash_rate=0.15,
                              corrupt=(s % 2 == 0), effect_rate=0.6)
            h = History(list(h)).index()
            h.attach_packed(pack_history(h))
            try:
                r = wgl_seg.check(model, h)
            except wgl_seg.Unsupported:
                continue
            o = wgl_cpu.check(model, h, max_configs=4_000_000)
            if r.get("refutation") == "crash-relaxed":
                fired += 1
                assert r["valid?"] is False
                # the refutation always names an exact op (VERDICT r3
                # #3: per-row death localization, no oracle needed)
                assert r.get("op_index") is not None
                assert r.get("witness") in ("relaxed-exact",
                                            "segment-bound")
                if o["valid?"] != "unknown":
                    # soundness: relaxed-invalid implies truly invalid
                    assert o["valid?"] is False, s
                    wb = r["witness_bound_index"]
                    wi = o.get("op_index")
                    assert wi is None or wi <= wb, (wi, wb)
            elif o["valid?"] != "unknown":
                assert r["valid?"] == o["valid?"], s
        assert fired >= 2

    @pytest.mark.slow
    def test_relaxed_exact_witness_equals_oracle(self):
        # A violation that is NOT crash-explainable (value 99 was never
        # written by any call, crashed or not): the relaxed config set
        # dies at exactly the return the true search dies at, so the
        # localized witness must EQUAL the oracle's (VERDICT r3 #3).
        from jepsen_tpu.history import History, pack_history
        model = models.CASRegister(0)
        matched = 0
        for s in range(40, 60):
            h0 = crash_history(s, n_calls=80, conc=3, crash_rate=0.15,
                               effect_rate=0.6)
            ops = list(h0)
            idx = [i for i, o in enumerate(ops)
                   if o.type == "ok" and o.f == "read"]
            if len(idx) < 4:
                continue
            ops[idx[len(idx) * 3 // 4]] = \
                ops[idx[len(idx) * 3 // 4]].assoc(value=99)
            h = History(ops).index()
            h.attach_packed(pack_history(h))
            try:
                r = wgl_seg.check(model, h, localize=False)
            except wgl_seg.Unsupported:
                continue
            if r.get("refutation") != "crash-relaxed":
                continue
            o = wgl_cpu.check(model, h, max_configs=4_000_000)
            if o["valid?"] != False:
                continue
            assert r["witness"] == "relaxed-exact", s
            assert r["op_index"] == o["op_index"], (
                s, r["op_index"], o["op_index"])
            matched += 1
        assert matched >= 2, matched

    @pytest.mark.slow
    def test_relaxed_refutation_battery(self):
        from jepsen_tpu.history import History, pack_history
        model = models.CASRegister(0)
        for s in range(10, 34):
            h = crash_history(s, n_calls=90, conc=4, crash_rate=0.12,
                              corrupt=(s % 2 == 0), effect_rate=0.5)
            h = History(list(h)).index()
            h.attach_packed(pack_history(h))
            try:
                r = wgl_seg.check(model, h)
            except wgl_seg.Unsupported:
                continue
            o = wgl_cpu.check(model, h, max_configs=4_000_000)
            if o["valid?"] == "unknown":
                continue
            if r.get("refutation") == "crash-relaxed":
                assert o["valid?"] is False, s
            else:
                assert r["valid?"] == o["valid?"], s


class TestRelaxedWideStates:
    """VERDICT r3 #5: the crash-relaxed tier's state-bitmask rows were
    u32 (Sn <= 32); the sn_words=2 lift covers registers up to 64
    enumerated states — crash-heavy refutation is no longer a
    tiny-state-only claim."""

    @pytest.mark.slow
    def test_wide_register_relaxed_refutation(self):
        from jepsen_tpu.history import History, pack_history
        model = models.CASRegister(0)
        fired = matched = 0
        for s in range(60, 90):
            h0 = crash_history(s, n_calls=80, conc=3, crash_rate=0.15,
                               vmax=40, effect_rate=0.6)
            ops = list(h0)
            idx = [i for i, o in enumerate(ops)
                   if o.type == "ok" and o.f == "read"]
            if len(idx) < 4:
                continue
            # plant an impossible value (never written by ANY call)
            ops[idx[len(idx) * 3 // 4]] = \
                ops[idx[len(idx) * 3 // 4]].assoc(value=63)
            h = History(ops).index()
            h.attach_packed(pack_history(h))
            try:
                r = wgl_seg.check(model, h, localize=False,
                                  max_states=80)
            except wgl_seg.Unsupported:
                continue
            if r.get("refutation") != "crash-relaxed":
                continue
            fired += 1
            assert r["valid?"] is False
            assert r["states"] > 32 if "states" in r else True
            assert r.get("op_index") is not None
            o = wgl_cpu.check(model, h, max_configs=4_000_000)
            if o["valid?"] is False:
                wi, wb = o.get("op_index"), r["witness_bound_index"]
                assert wi is None or wi <= wb, (s, wi, wb)
                if r.get("witness") == "relaxed-exact":
                    matched += 1
                    assert r["op_index"] == o["op_index"], (
                        s, r["op_index"], o["op_index"])
            if fired >= 3:
                break
        assert fired >= 1, "wide relaxed tier never fired"
