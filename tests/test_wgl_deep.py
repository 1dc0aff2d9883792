"""Differential battery for the deep-overlap Pallas megakernel
(ops/wgl_deep.py): identical verdicts AND identical witnesses to the
CPU oracle on the deep-concurrency regime the reference names as THE
cost cliff (`doc/tutorial/06-refining.md:7-10`,
`doc/tutorial/07-parameters.md:148-152`).  Runs on the CPU interpreter
(tests force JAX_PLATFORMS=cpu); the TPU lowering is the same kernel
body, exercised by chip_smoke.py's deep phases on hardware.

Histories here are deliberately SMALL: the interpreter executes the
event loop op-by-op in Python, so sizes are chosen to cover the
structural cases (deep R, spill rows, multi-block grids, crashes as
permanent slots, witness mapping) rather than throughput."""

import os
import random

import pytest

from jepsen_tpu import models
from jepsen_tpu.history import (History, info_op, invoke_op, ok_op,
                                fail_op, pack_history)
from jepsen_tpu.ops import wgl_cpu, wgl_deep, wgl_seg


def deep_history(n_calls, conc, seed, vmax=3, max_open=8,
                 crash_rate=0.0):
    """Bursty register workload bounded to `max_open` simultaneously
    open calls (the chip_smoke.make_history shape, trimmed for the
    interpreter)."""
    rng = random.Random(seed)
    ops, value = [], None
    open_ops = {}
    i = 0
    while i < n_calls:
        p = rng.choice(range(conc))
        if p in open_ops:
            ops.append(open_ops.pop(p))
            continue
        if len(open_ops) >= max_open:
            ops.append(open_ops.pop(rng.choice(list(open_ops))))
            continue
        i += 1
        f = rng.choice(("read", "read", "write", "cas"))
        if crash_rate and rng.random() < crash_rate:
            v = (None if f == "read" else rng.randint(0, vmax)
                 if f == "write" else
                 [rng.randint(0, vmax), rng.randint(0, vmax)])
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
    h.attach_packed(pack_history(h))
    return h


def corrupt(h, frac=0.7, value=99):
    reads = [i for i, o in enumerate(h.ops)
             if o.type == "ok" and o.f == "read"]
    h.ops[reads[int(len(reads) * frac)]].value = value
    h.attach_packed(pack_history(h))
    return h


class TestDeepDifferential:
    def test_valid_deep_overlap(self):
        # R 7-9: past the register-delta gate, on the deep engine
        for mo in (7, 8, 9):
            h = deep_history(120, 14, seed=50 + mo, max_open=mo)
            r = wgl_seg.check(models.CASRegister(), h,
                              max_open_bits=14)
            o = wgl_cpu.check(models.CASRegister(), h)
            assert r["valid?"] == o["valid?"] is True
            assert r["engine"] == "wgl_deep"
            assert r["max_open"] >= 7

    def test_invalid_witness_equality(self):
        # mo=10 at 0.9 depth: a deep-regime refutation (VERDICT r4 #3)
        # at interpreter scale
        for mo, frac in ((7, 0.6), (9, 0.8), (10, 0.9)):
            h = corrupt(deep_history(140, 14, seed=70 + mo,
                                     max_open=mo), frac)
            r = wgl_seg.check(models.CASRegister(), h,
                              max_open_bits=14)
            o = wgl_cpu.check(models.CASRegister(), h)
            assert r["valid?"] is False and o["valid?"] is False
            assert r["engine"] == "wgl_deep"
            assert r["op_index"] == o["op_index"]
            assert r["op"]["f"] == o["op"]["f"]

    def test_subtle_invalid_legal_value(self):
        # a stale read of a LEGAL value (not an impossible one): after
        # a deep-overlap prefix quiesces, write 2 then read 1 strictly
        # sequentially — no pending write can save the read, yet every
        # value is in-domain, so refuting requires the search to reach
        # that depth with the correct state set
        h = deep_history(140, 14, seed=91, vmax=2, max_open=8)
        tail = [invoke_op(0, "write", 2), ok_op(0, "write", 2),
                invoke_op(1, "read", None), ok_op(1, "read", 1)]
        h2 = History(h.ops + tail).index()
        h2.attach_packed(pack_history(h2))
        o = wgl_cpu.check(models.CASRegister(), h2)
        r = wgl_seg.check(models.CASRegister(), h2, max_open_bits=14)
        assert o["valid?"] is False
        assert r["valid?"] is False
        assert r["engine"] == "wgl_deep"
        assert r["op_index"] == o["op_index"]

    def test_crashes_as_permanent_slots(self):
        # crashed calls beyond the J-axis gate (Sn * 2^nc > 128) land
        # on the deep kernel, which has no entry-config axis at all
        h = deep_history(100, 12, seed=31, vmax=3, max_open=4,
                         crash_rate=0.06)
        nc = sum(1 for o in h if o.type == "info")
        if nc < 2:
            pytest.skip("seed produced too few crashes")
        r = wgl_seg.check(models.CASRegister(), h, max_open_bits=14)
        o = wgl_cpu.check(models.CASRegister(), h)
        assert r["valid?"] == o["valid?"]

    def test_spill_rows_burst(self):
        # an invoke burst far beyond I=2 per return row exercises the
        # virtual spill rows of the register-delta layout
        ops = []
        for p in range(9):
            ops.append(invoke_op(p, "write", p % 3))
        for p in range(9):
            ops.append(ok_op(p, "write", p % 3))
        ops += [invoke_op(0, "read", None), ok_op(0, "read", 2),
                invoke_op(1, "write", 1), ok_op(1, "write", 1),
                invoke_op(2, "read", None), ok_op(2, "read", 1)]
        h = History(ops).index()
        h.attach_packed(pack_history(h))
        r = wgl_seg.check(models.CASRegister(), h, max_open_bits=14)
        o = wgl_cpu.check(models.CASRegister(), h)
        assert r["valid?"] == o["valid?"] is True
        assert r["engine"] == "wgl_deep"

    def test_multi_block_grid(self):
        # > EB returns: the grid streams several SMEM blocks; frontier
        # and registers must persist across grid steps
        h = deep_history(620, 14, seed=11, max_open=7)
        r = wgl_seg.check(models.CASRegister(), h, max_open_bits=14)
        o = wgl_cpu.check(models.CASRegister(), h)
        assert r["valid?"] == o["valid?"] is True
        assert r["engine"] == "wgl_deep"

    def test_regs_kernel_still_owns_shallow(self):
        h = deep_history(120, 5, seed=3, max_open=4)
        r = wgl_seg.check(models.CASRegister(), h)
        assert r["engine"] == "wgl_seg"

    def test_supported_gate(self):
        assert wgl_deep.supported(14, 16, 100, True, "tpu")
        # ISSUE 10: word-split buys R=15/16 on one device; the
        # hypercube mesh buys 14 + log2(D); beyond that, serial chain
        assert wgl_deep.supported(15, 16, 100, True, "tpu")
        assert wgl_deep.supported(16, 16, 100, True, "tpu")
        assert not wgl_deep.supported(17, 16, 100, True, "tpu")
        assert wgl_deep.supported(17, 16, 100, True, "tpu",
                                  n_devices=8)
        assert not wgl_deep.supported(18, 16, 100, True, "tpu",
                                      n_devices=8)
        assert not wgl_deep.supported(8, 33, 100, True, "tpu")
        assert not wgl_deep.supported(8, 16, 100, False, "tpu")
        assert not wgl_deep.supported(8, 16, 100, True, "gpu")

    def test_cpu_interpreter_is_opt_in(self, monkeypatch):
        # ADVICE r4: on a production CPU backend the Pallas interpreter
        # (a per-event Python loop) must NOT swallow R > 6 histories;
        # it is opt-in for the test suite via JEPSEN_TPU_DEEP_INTERPRET
        monkeypatch.delenv("JEPSEN_TPU_DEEP_INTERPRET", raising=False)
        assert not wgl_deep.supported(8, 16, 100, True, "cpu")
        monkeypatch.setenv("JEPSEN_TPU_DEEP_INTERPRET", "1")
        assert wgl_deep.supported(8, 16, 100, True, "cpu")


class TestDeepPipeline:
    def test_mixed_depth_batch_stragglers(self):
        # VERDICT r4 #2, boundary moved by ISSUE 10: a batch mixing
        # in-scope deep histories with one BEYOND the new envelope
        # (R = 18 > deep_r_max) must NOT die with ValueError — the
        # R = 18 history rides the serial fallback chain and still gets
        # a correct verdict, while in-scope ones (R = 15 included, now
        # word-split) stay pipelined.
        model = models.CASRegister()
        h8 = deep_history(100, 14, seed=210, max_open=8)
        # deterministic R = 15 burst: now IN scope (word-split)
        ops15 = [invoke_op(p, "write", p % 3) for p in range(15)]
        ops15 += [ok_op(p, "write", p % 3) for p in range(15)]
        ops15 += [invoke_op(0, "read", None), ok_op(0, "read", 2)]
        h15 = History(ops15).index()
        h15.attach_packed(pack_history(h15))
        # deterministic R = 18 burst: beyond every device tier
        ops18 = [invoke_op(p, "write", p % 3) for p in range(18)]
        ops18 += [ok_op(p, "write", p % 3) for p in range(18)]
        ops18 += [invoke_op(0, "read", None), ok_op(0, "read", 2)]
        h18 = History(ops18).index()
        h18.attach_packed(pack_history(h18))
        hbad = corrupt(deep_history(100, 14, seed=212, max_open=8), 0.7)
        res = wgl_deep.check_pipeline(model, [h8, h15, h18, hbad])
        o15 = wgl_cpu.check(model, h15)
        o18 = wgl_cpu.check(model, h18)
        obad = wgl_cpu.check(model, hbad)
        assert res[0]["valid?"] is True
        assert res[0]["engine"] == "wgl_deep" and res[0]["pipelined"]
        assert res[1]["valid?"] == o15["valid?"]
        assert res[1]["engine"] == "wgl_deep"      # in scope now
        assert res[1]["deep_variant"] == "word-split"
        assert res[2]["valid?"] == o18["valid?"]
        assert res[2].get("engine") != "wgl_deep"  # straggler fallback
        assert res[3]["valid?"] is False
        assert res[3]["engine"] == "wgl_deep"
        assert res[3]["op_index"] == obad["op_index"]

    def test_state_space_growth_does_not_poison_batch(self):
        # code-review r5: a history whose values push the enumerated
        # state space past max_states must become a straggler (serial
        # fallback), not abort the batch with Unsupported
        model = models.CASRegister()
        h8 = deep_history(80, 12, seed=230, max_open=7)
        wide_ops = []
        for p in range(3):
            for v in range(p * 30, p * 30 + 28):   # 84 distinct values
                wide_ops += [invoke_op(p, "write", v),
                             ok_op(p, "write", v)]
        hwide = History(wide_ops).index()
        hwide.attach_packed(pack_history(hwide))
        res = wgl_deep.check_pipeline(model, [h8, hwide],
                                      max_states=64)
        assert res[0]["valid?"] is True
        assert res[0]["engine"] == "wgl_deep"
        assert res[1]["valid?"] is True            # straggler verdict

    def test_pipeline_stats_decomposition(self):
        model = models.CASRegister()
        hs = [deep_history(80, 12, seed=220 + s, max_open=7)
              for s in range(2)]
        st = {}
        res = wgl_deep.check_pipeline(model, hs, stats=st)
        assert all(r["valid?"] is True for r in res)
        assert {"scan", "fetch"} <= set(st)
        assert all(v >= 0 for v in st.values())


def burst_history(mo, seed=0, n_tail=60, crash_lead=0):
    """A history whose overlap depth is EXACTLY `mo`: random deep tail
    plus a deterministic burst of `mo` simultaneously-open writes.
    With `crash_lead`, that many crashed (:info) calls open first and
    never return — permanent slots, so R_eff = mo + crash_lead."""
    ops = []
    for c in range(crash_lead):
        ops.append(invoke_op(500 + c, "write", c % 3))
        ops.append(info_op(500 + c, "write", c % 3))
    h = deep_history(n_tail, 10, seed=900 + mo + seed, max_open=7)
    ops += list(h.ops)
    ops += [invoke_op(200 + p, "write", p % 3) for p in range(mo)]
    ops += [ok_op(200 + p, "write", p % 3) for p in range(mo)]
    h2 = History(ops).index()
    h2.attach_packed(pack_history(h2))
    return h2


class TestDeepSharded:
    """ISSUE 10: the R = 14 ceiling broken two ways — word-split
    sub-plane stacks on one device (R = 15/16) and the hypercube
    mask shard across the mesh (R = 17 on 8 devices) — both
    differentially pinned to the oracle and the serial engines,
    witness equality included."""

    def _mesh(self, n, axis="cfg"):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices("cpu")[:n]), (axis,))

    def test_word_split_differential(self):
        # R = 15/16 on ONE device: the same kernel with the mask axis
        # factored into sub-planes.  Verdict + witness vs the oracle
        # AND the serial device frontier engine (wgl-serial chain).
        from jepsen_tpu.ops import wgl
        model = models.CASRegister()
        for mo in (15, 16):
            h = burst_history(mo, seed=1)
            r = wgl_seg.check(model, h, max_open_bits=16)
            o = wgl_cpu.check(model, h)
            s = wgl.check(model, h)
            assert r["valid?"] == o["valid?"] == s["valid?"] is True
            assert r["engine"] == "wgl_deep"
            assert r["deep_variant"] == "word-split"
            assert r["shards"] == (2 if mo == 15 else 4)
            hb = corrupt(h, 0.6)
            rb = wgl_seg.check(model, hb, max_open_bits=16)
            ob = wgl_cpu.check(model, hb)
            sb = wgl.check(model, hb)
            assert rb["valid?"] is ob["valid?"] is sb["valid?"] is False
            assert rb["engine"] == "wgl_deep"
            assert rb["op_index"] == ob["op_index"] == sb["op_index"]

    def test_word_split_crashed_slots(self):
        # crashed calls are permanent slots: rn = 14 normal + 1 crashed
        # pushes R_eff to 15, onto the word-split stack
        model = models.CASRegister()
        h = burst_history(14, seed=2, crash_lead=1)
        r = wgl_seg.check(model, h, max_open_bits=16)
        o = wgl_cpu.check(model, h)
        assert r["valid?"] == o["valid?"]
        assert r["engine"] == "wgl_deep"
        assert r.get("crashed") == 1
        assert r["deep_variant"] == "word-split"

    def test_hypercube_forced_meshes(self):
        # randomized differential battery on forced 2/4/8-device host
        # meshes: R = 15 on 2, 16 on 4, 17 on 8 (= 14 + log2 D), each
        # bit-identical to the oracle, with the exchange schedule
        # reported (one pairwise ppermute per high slot per round)
        model = models.CASRegister()
        for mo, nd in ((15, 2), (16, 4), (17, 8)):
            mesh = self._mesh(nd)
            h = burst_history(mo, seed=3, n_tail=50)
            r = wgl_deep.check_hypercube(model, [h], mesh)[0]
            o = wgl_cpu.check(model, h)
            assert r["valid?"] == o["valid?"] is True, (mo, nd)
            assert r["deep_variant"] == "hypercube"
            assert r["shards"] == nd
            assert r["exchange_rounds"] > 0
            assert r["dispatch"]["plan"]["engine"] == "wgl_deep_hc"
            hb = corrupt(h, 0.6)
            rb = wgl_deep.check_hypercube(model, [hb], mesh)[0]
            ob = wgl_cpu.check(model, hb)
            assert rb["valid?"] is ob["valid?"] is False, (mo, nd)
            assert rb["op_index"] == ob["op_index"], (mo, nd)

    def test_hypercube_matches_word_split_and_serial(self):
        # the SAME R = 15 history through all three: hypercube mesh,
        # word-split single device, serial frontier — one verdict, one
        # witness
        from jepsen_tpu.ops import wgl
        model = models.CASRegister()
        hb = corrupt(burst_history(15, seed=4), 0.5)
        mesh = self._mesh(2)
        rh = wgl_deep.check_hypercube(model, [hb], mesh)[0]
        rw = wgl_seg.check(model, hb, max_open_bits=16)
        rs = wgl.check(model, hb)
        assert rh["valid?"] is rw["valid?"] is rs["valid?"] is False
        assert rh["op_index"] == rw["op_index"] == rs["op_index"]

    def test_pipeline_mesh_straggler_routing(self):
        # with a mesh, an R = 17 straggler verdicts on the hypercube
        # tier; an R = 18 one still reaches the serial chain — the
        # fallback ladder provably engages beyond the new boundary
        model = models.CASRegister()
        mesh = self._mesh(8)
        res = wgl_deep.check_pipeline(
            model, [burst_history(8, 5), burst_history(17, 5),
                    burst_history(18, 5)], mesh=mesh)
        assert res[0]["engine"] == "wgl_deep" and res[0]["pipelined"]
        assert res[1]["deep_variant"] == "hypercube"
        assert res[1]["shards"] == 8 and res[1]["valid?"] is True
        assert res[2].get("engine") != "wgl_deep"   # serial chain
        assert res[2]["valid?"] is True

    def test_check_mesh_routes_deep_batches_to_hypercube(self):
        # check_mesh keeps its replicated one-history-per-device layout
        # for R within one device's stack and mask-shards past it
        model = models.CASRegister()
        mesh = self._mesh(8, axis="hists")
        res = wgl_deep.check_mesh(model, [burst_history(17, 6)], mesh)
        assert res[0]["deep_variant"] == "hypercube"
        assert res[0]["valid?"] is True

    def test_oom_mid_shard_bisection(self, monkeypatch):
        # an OOM at the stacked verdict fetch (the sub-plane stacks of
        # a multi-history batch) must surface to the ResilientRunner
        # and bisect the HISTORY axis — per-history retries then
        # succeed, verdicts land, the bisection counter fires
        from jepsen_tpu import telemetry
        from jepsen_tpu.errors import DeviceOOM
        from jepsen_tpu.ops import runner
        model = models.CASRegister()
        real_stack = wgl_seg._build_stack

        def oom_stack(n):
            if n > 1:
                raise DeviceOOM(
                    "RESOURCE_EXHAUSTED: sub-plane stack fetch")
            return real_stack(n)

        monkeypatch.setattr(wgl_seg, "_build_stack", oom_stack)
        hists = [burst_history(15, 7 + s, n_tail=40) for s in range(3)]
        before = telemetry.REGISTRY.counter(
            "jepsen_runner_oom_bisections_total").value
        rr = runner.ResilientRunner(engine="deep_pipeline",
                                    sleep=lambda s: None)
        res = rr.check(model, hists)
        after = telemetry.REGISTRY.counter(
            "jepsen_runner_oom_bisections_total").value
        assert after > before
        for h, r in zip(hists, res):
            assert r["valid?"] is wgl_cpu.check(model, h)["valid?"]
            assert r["engine"] == "wgl_deep"

    def test_oom_mid_shard_demotes_single_history(self, monkeypatch):
        # a SINGLE history whose stack OOMs on dispatch is demoted to
        # the straggler chain by check_pipeline itself — counted,
        # verdict still exact, batchmates unharmed
        from jepsen_tpu.errors import DeviceOOM
        real_dispatch = wgl_deep.dispatch_tables

        def oom_dispatch(ret_t, islot_t, iuop_t, a1t, a2t, t0t,
                         R, Sn, stats=None):
            if R > 14:
                raise DeviceOOM("RESOURCE_EXHAUSTED: sub-plane stack")
            return real_dispatch(ret_t, islot_t, iuop_t, a1t, a2t,
                                 t0t, R, Sn, stats=stats)

        monkeypatch.setattr(wgl_deep, "dispatch_tables", oom_dispatch)
        model = models.CASRegister()
        h8 = burst_history(8, 9, n_tail=40)
        h15 = burst_history(15, 9, n_tail=40)
        res = wgl_deep.check_pipeline(model, [h8, h15])
        assert res[0]["engine"] == "wgl_deep"
        assert res[0]["valid?"] is True
        # demoted straggler: correct verdict off the deep kernel
        assert res[1]["valid?"] is True
        assert res[1].get("deep_variant") != "word-split"
        assert res[0]["dispatch"]["oom_demoted"] == 1
