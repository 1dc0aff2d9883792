#!/usr/bin/env python
"""Drive the checker's device path once on the chip, at the sizes users run.

One process, one seed.  Each phase goes through the entry point a user
calls, checks every verdict against the CPU oracle (`ops.wgl_cpu_native`)
or the planted anomaly, and prints one JSON line: sizes, the `engine` and
`backend` of every verdict (counted), compile seconds from
`planner.cache_stats()` and from XLA's compile events, wall seconds, the agreement checks, and the
engine fallbacks it counted.  The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

and it is printed only when every phase passed on a TPU.

    python chip_smoke.py               # one chip: e2e, multikey, single,
                                       # crash, deep, elle
    python chip_smoke.py --chips 4     # the mesh paths on four chips,
                                       # each against its one-device run

`tests/test_chip_smoke.py` runs the same phases at tiny sizes on the
CPU's virtual devices.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import sys
import time
import traceback
import types
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The sizes of the reference's own recipes (BASELINE.json's configs):
# the etcd suite's 300 ops per key over thousands of keys, a 100k-op CAS
# register, a crashed-op run, the deep-overlap envelope, and 10k-txn
# list-append histories.
FULL = dict(e2e_s=15, keys=3400, key_ops=300, key_conc=5, oracle_keys=100,
            single_ops=100_000, crash_ops=50_000, deep_hists=16,
            deep_ops=20_000, deep_open=12, hc_ops=1_200, elle_txns=10_000,
            lattice_txns=5_000, small_txns=1_000, elle_threshold=8192,
            lattice_threshold=4096)
DEVICE_ENGINES = ("wgl_seg", "wgl_deep", "elle-mesh", "elle-device",
                  "lattice-mesh", "lattice-device")


class PhaseFailed(AssertionError):
    pass


def expect(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def make_history(n_ops: int, concurrency: int, seed: int = 7,
                 vmax: int = 4, crash_rate: float = 0.0,
                 max_open: int = 0, crash_vmax: int = 0) -> "History":
    """An etcd-shaped register workload (r/w/cas mix, etcd.clj:145-147)
    executed against a sequentially-consistent in-memory register with
    process interleaving.  With crash_rate, that fraction of calls
    time out (:info, never taking effect) — the nemesis-run shape the
    reference calls its worst cost driver (a crashed op stays
    concurrent with the entire rest of the history,
    doc/tutorial/06-refining.md:12-19).  max_open > 0 bounds the
    simultaneously-open NORMAL calls (bursty interleaving: many worker
    processes, bounded overlap depth — the live-process count still
    spans `concurrency`)."""
    from jepsen_tpu.history import (History, fail_op, info_op, invoke_op,
                                    ok_op, pack_history)

    rng = random.Random(seed)
    ops, value = [], None
    open_ops: dict = {}  # process -> (completion op) pending flush
    procs = list(range(concurrency))
    i = 0
    while i < n_ops:
        p = rng.choice(procs)
        if p in open_ops:
            ops.append(open_ops.pop(p))
            continue
        if max_open and len(open_ops) >= max_open:
            if open_ops:
                ops.append(open_ops.pop(rng.choice(list(open_ops))))
            continue
        i += 1
        f = rng.choice(("read", "read", "write", "cas"))
        if crash_rate and rng.random() < crash_rate:
            # timed-out call: invoke journaled, :info completion, no
            # effect on the register (the DB never applied it).
            # crash_vmax > 0 restricts CRASHED ops' values to
            # 0..crash_vmax so a subtle-violation planter can pick a
            # legal value that is provably not crash-explainable
            cm = crash_vmax or vmax
            v = (None if f == "read" else rng.randint(0, cm)
                 if f == "write" else
                 [rng.randint(0, cm), rng.randint(0, cm)])
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
    # The framework's run loop journals ops into a ColumnJournal as
    # they land (core.py), so a real history's columnar representation
    # exists before analysis starts; building it here at construction
    # time reproduces that (the scan engines then never walk Python
    # objects).  The CPU oracle still receives the Op objects.
    h.attach_packed(pack_history(h))
    return h


def plant_stale_read(h: "History", frac: float, vmax: int,
                     forbidden=()) -> "tuple[int, int] | None":
    """Plant a SUBTLE violation (VERDICT r3 #4): rewrite one ok-read to
    a LEGAL value w that no linearization can produce — w is excluded
    from the read's concurrency window (not the register value at the
    window start, not written/cas-targeted by any call whose own
    window intersects it) — instead of an out-of-domain constant.  The
    violation is invisible to any local scan (w is written legitimately
    elsewhere in the history) and refuting it requires the search to
    carry the true state set to the read's depth.  `forbidden` removes
    further candidates (e.g. every crashed call's value, so the
    crash-relaxed tier's epsilon-jumps cannot explain w either).
    Mutates h in place; returns (op_position, planted_value) or None.

    Window analysis: walk the ops maintaining the sequential register
    value and each process's open invoke; for the chosen read, V = the
    value at its invoke + every write value / cas target of calls
    whose [invoke, complete] intersects the read's window.  Only such
    calls can linearize inside the window, so any legal w outside V
    (and outside `forbidden`) makes the read impossible."""
    import numpy as np

    from jepsen_tpu.history import pack_history
    ops = h.ops
    n = len(ops)
    value_at = np.zeros(n + 1, np.int64)     # seq value BEFORE op i
    cur = -1                                 # None encoded as -1
    for i, o in enumerate(ops):
        value_at[i] = cur
        if o.type == "ok" and o.f == "write":
            cur = o.value
        elif o.type == "ok" and o.f == "cas":
            cur = o.value[1]
    value_at[n] = cur
    # per-call (invoke_pos, completion_pos|inf, candidate value):
    # a call can linearize inside a window iff its own span intersects
    # it; crashed calls (no completion) stay open to the end
    pend: dict = {}
    inv_of: dict = {}
    inv_pos, comp_pos, wval = [], [], []
    for i, o in enumerate(ops):
        if o.type == "invoke":
            pend[o.process] = len(inv_pos)
            inv_pos.append(i)
            comp_pos.append(n)
            v = None
            if o.f == "write":
                v = o.value
            elif o.f == "cas":
                v = o.value[1]
            wval.append(-1 if v is None else int(v))
        elif o.process in pend:
            c = pend.pop(o.process)
            comp_pos[c] = i
            inv_of[i] = inv_pos[c]
    inv_pos = np.asarray(inv_pos, np.int64)
    comp_pos = np.asarray(comp_pos, np.int64)
    wval = np.asarray(wval, np.int64)
    reads = [i for i, o in enumerate(ops)
             if o.type == "ok" and o.f == "read"
             and o.value is not None and i in inv_of]
    start = int(len(reads) * frac)
    for i in reads[start:] + reads[:start]:
        lo = inv_of[i]
        # A write X can be the read's last-write in SOME linearization
        # iff X invokes before the read completes AND no write Y is
        # FORCED between them (Y forced <=> inv_Y > comp_X and
        # comp_Y < lo).  With M = max invoke position of writes
        # completing before the window, X qualifies iff comp_X >= M —
        # this keeps real-time-maximal writes that finish before the
        # window opens (ordering them last is legal), which a naive
        # comp >= lo overlap test wrongly excludes.
        before = (comp_pos < lo) & (wval >= 0)
        M = int(inv_pos[before].max()) if before.any() else 0
        touch = (inv_pos <= i) & (comp_pos >= M) & (wval >= 0)
        V = set(int(x) for x in np.unique(wval[touch]))
        V.add(int(value_at[lo]))
        w = next((x for x in range(vmax + 1)
                  if x not in V and x not in forbidden), None)
        if w is None:
            continue
        ops[i].value = w
        h.attach_packed(pack_history(h))
        return i, w
    return None


class Smoke:
    def __init__(self, sizes: dict, seed: int, devices: list):
        self.S = sizes
        self.seed = seed
        import jax

        from jepsen_tpu import models
        self.jax = jax
        self.devices = devices
        self.platform = self.devices[0].platform
        self.model = models.CASRegister()
        self.watch_compiles()

    # -- bookkeeping ---------------------------------------------------------

    def watch_compiles(self):
        """Sum XLA's backend compile seconds, whichever path compiled
        (planner.cache_stats() sees only planner.compiled's)."""
        self.xla_compile_s = 0.0

        def on(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.xla_compile_s += duration
        self.jax.monitoring.register_event_duration_secs_listener(on)

    @staticmethod
    def fallbacks() -> Counter:
        from jepsen_tpu import telemetry
        _, by_label = telemetry.REGISTRY.collect().get(
            "jepsen_engine_fallback_total", ("counter", {}))
        out: Counter = Counter()
        for key, m in by_label.items():
            lab = dict(key)
            out[f"{lab.get('engine')}:{lab.get('reason')}"] += int(m.value)
        return out

    def tally(self, verdicts) -> dict:
        """Counted engines and backends; every verdict must come from
        the device platform and name a device engine."""
        engines = Counter(str(v.get("engine")) for v in verdicts)
        backends = Counter(str(v.get("backend")) for v in verdicts)
        expect(set(backends) == {self.platform},
               f"verdict backends {dict(backends)} != {self.platform}")
        expect(all(e.startswith(DEVICE_ENGINES) for e in engines),
               f"non-device engine among {dict(engines)}")
        return {"engines": dict(engines), "backends": dict(backends)}

    def phase(self, name, fn):
        """Run one phase; print its line; a failure is reported, never
        raised, so one chip call shows every phase.  No phase's shape
        routes to a lower tier by design, so any engine fallback it
        counts fails it."""
        from jepsen_tpu.ops import planner
        fb0 = self.fallbacks()
        c0 = planner.cache_stats()["compile_s"]
        x0 = self.xla_compile_s
        t0 = time.monotonic()
        line: dict = {"phase": name}
        try:
            line.update(fn())
            ok = True
        except Exception as e:          # noqa: BLE001 - reported below
            traceback.print_exc()
            line["error"] = f"{type(e).__name__}: {e}"[:1500]
            ok = False
        fb = self.fallbacks() - fb0
        line["compile_s"] = planner.cache_stats()["compile_s"] - c0
        line["xla_compile_s"] = self.xla_compile_s - x0
        line["wall_s"] = time.monotonic() - t0
        line["fallbacks"] = dict(fb)
        if fb and ok:
            line["error"] = f"engine fallbacks {dict(fb)}"
            ok = False
        line["ok"] = ok
        print(json.dumps(line, default=str), flush=True)
        return ok

    # -- histories -----------------------------------------------------------

    @staticmethod
    def invoke_of(h, pos: int) -> int:
        """Index of the invoke whose completion sits at ops[pos]."""
        ops = h.ops
        p = ops[pos].process
        i = pos
        while i >= 0 and not (ops[i].process == p
                              and ops[i].type == "invoke"):
            i -= 1
        return ops[i].index

    def planted(self, n_ops, conc, seed, vmax=4, **kw):
        """(history, witness index) with a stale read planted at 90%
        depth that no linearization can explain (plant_stale_read);
        the witness is the planted read's invoke."""
        for s in range(seed, seed + 20):
            h = make_history(n_ops, conc, seed=s, vmax=vmax, **kw)
            got = plant_stale_read(
                h, 0.9, vmax, forbidden=set(range(kw["crash_vmax"] + 1))
                if kw.get("crash_vmax") else ())
            if got is not None:
                return h, self.invoke_of(h, got[0])
        raise PhaseFailed("no plantable stale read")

    def oracle(self, h) -> dict:
        from jepsen_tpu.ops import wgl_cpu_native
        return wgl_cpu_native.check(self.model, h)

    def agree(self, verdict, h, want_valid, want_witness=None) -> dict:
        """The device verdict against the oracle's on the same history:
        validity, and the witness op where it is invalid."""
        o = self.oracle(h)
        expect(o["valid?"] is want_valid,
               f"oracle says {o['valid?']}, construction {want_valid}")
        expect(verdict["valid?"] is want_valid,
               f"device says {verdict['valid?']}, oracle {want_valid}")
        if want_valid:
            return {"oracle": "agrees"}
        expect(verdict.get("op_index") == o.get("op_index"),
               f"witness {verdict.get('op_index')} != oracle "
               f"{o.get('op_index')}")
        if want_witness is not None:
            expect(o.get("op_index") == want_witness,
                   f"oracle witness {o.get('op_index')} != planted "
                   f"{want_witness}")
        return {"oracle": "agrees", "witness": o.get("op_index")}

    def txn_history(self, n_txn, seed, plant):
        """A list-append history run serially against a correct store
        (so it holds no anomaly of its own), then the `plant` txns on
        fresh keys and sessions: the flagged classes must be exactly
        the planted one."""
        from jepsen_tpu.history import History, invoke_op, ok_op
        rng = random.Random(seed)
        n_keys, n_proc = 64, 16
        store: dict = {f"k{i}": [] for i in range(n_keys)}
        ops, nxt = [], 0
        for _ in range(n_txn):
            p = rng.randrange(n_proc)
            mops = []
            for _ in range(rng.randint(1, 4)):
                k = f"k{rng.randrange(n_keys)}"
                if rng.random() < 0.5:
                    nxt += 1
                    store[k].append(nxt)
                    mops.append(["append", k, nxt])
                else:
                    mops.append(["r", k, list(store[k])])
            ops.append(invoke_op(p, "txn", [[f, k, None if f == "r" else v]
                                            for f, k, v in mops]))
            ops.append(ok_op(p, "txn", mops))
        for p, mops in plant:
            ops.append(invoke_op(n_proc + p, "txn",
                                 [[f, k, None if f == "r" else v]
                                  for f, k, v in mops]))
            ops.append(ok_op(n_proc + p, "txn", mops))
        return History(ops).index()

    # -- one chip ------------------------------------------------------------

    def e2e(self):
        """The kvd suite's own `test` command, in-process: a real
        daemon, real TCP clients, the SIGSTOP nemesis, core.analyze."""
        from jepsen_tpu import checker as ck
        from jepsen_tpu import cli, store
        from jepsen_tpu.suites import kvd

        store.BASE = ROOT / "store"
        seen: dict = {}

        class Capture(ck.Checker):
            def __init__(self, inner):
                self.inner = inner

            def check(self, test, history, opts=None):
                seen["results"] = self.inner.check(test, history, opts)
                seen["ops"] = len(history)
                return seen["results"]

        def test_fn(opts):
            t = kvd.test_for(opts)
            t["checker"] = Capture(t["checker"])
            return t

        try:
            rc = cli.main(cli.single_test_cmd(test_fn, kvd._opt_fn),
                          ["test", "--time-limit", str(self.S["e2e_s"])])
        finally:
            self.stop_kvd()
        expect(rc == 0, f"kvd test exited {rc}")
        per_key = list(seen["results"]["linear"]["results"].values())
        expect(per_key, "no per-key verdicts")
        expect(all(v["valid?"] is True for v in per_key),
               "a per-key verdict is not valid")
        return {"sizes": {"time_limit_s": self.S["e2e_s"],
                          "ops": seen["ops"], "keys": len(per_key)},
                **self.tally(per_key)}

    @staticmethod
    def stop_kvd():
        """The suite's teardown stops the daemon; after a failed setup
        this does, so the smoke leaves no process behind."""
        import signal

        from jepsen_tpu.suites import kvd
        try:
            with open(os.path.join(kvd.DIR, "kvd.pid")) as f:
                pid = int(f.read().strip())
            os.kill(pid, signal.SIGCONT)
            os.kill(pid, signal.SIGKILL)
        except (OSError, ValueError):
            pass

    def multikey(self):
        """The etcd-shaped independent register through the suites'
        batched independent checker (-> wgl_seg.check_many)."""
        from jepsen_tpu import independent
        from jepsen_tpu.history import History
        S = self.S
        hists = [make_history(S["key_ops"], S["key_conc"],
                              seed=self.seed + 1000 + k)
                 for k in range(S["keys"])]
        bad = S["keys"] // 2
        expect(plant_stale_read(hists[bad], 0.9, 4) is not None,
               "no plantable stale read")
        ops = [o.assoc(value=independent.KV(k, o.value), index=None)
               for k, h in enumerate(hists) for o in h.ops]
        hist = History(ops).index()
        t0 = time.monotonic()
        res = independent.batch_checker(self.model).check(None, hist)
        check_s = time.monotonic() - t0
        per = res["results"]
        expect(len(per) == S["keys"], f"{len(per)} verdicts")
        expect(res["failures"] == [bad],
               f"failures {res['failures'][:5]} != [{bad}]")
        rng = random.Random(self.seed)
        sample = sorted({bad, *rng.sample(range(S["keys"]),
                                          S["oracle_keys"] - 1)})
        subs = independent.subhistories(sample, hist)
        for k, sub in zip(sample, subs):
            self.agree(per[k], sub, k != bad)
        return {"sizes": {"keys": S["keys"], "ops_per_key": S["key_ops"],
                          "concurrency": S["key_conc"]},
                **self.tally(per.values()),
                "check_s": check_s,
                "agree": {"refuted": [bad], "oracle_keys": len(sample),
                          "witness": per[bad].get("op_index")}}

    def single(self):
        """One 100k-op CAS register through checker.linearizable, and
        its twin with a stale read at 90% depth."""
        from jepsen_tpu import checker as ck
        S = self.S
        lin = ck.linearizable(self.model)
        h = make_history(S["single_ops"], 5, seed=self.seed, vmax=9)
        bad, wit = self.planted(S["single_ops"], 5, self.seed + 1, vmax=9)
        t0 = time.monotonic()
        v, vb = lin.check(None, h), lin.check(None, bad)
        check_s = time.monotonic() - t0
        return {"sizes": {"ops": S["single_ops"], "concurrency": 5},
                **self.tally([v, vb]), "check_s": check_s,
                "agree": {"valid": self.agree(v, h, True),
                          "planted": self.agree(vb, bad, False, wit)}}

    def crash(self):
        """A 50k-op history with ~1% crashed calls (the hard
        regime) and its planted twin, both through
        checker.linearizable.  The twin's witness is the planted read:
        the planter proves no linearization, crashed calls included,
        can explain it.  The oracle that rebuilds its final-paths is
        exponential in the crashed calls, so it runs under the artifact
        caps and may report final-paths-error instead."""
        from jepsen_tpu import checker as ck
        S = self.S
        h = make_history(S["crash_ops"], 16, seed=self.seed + 2,
                         crash_rate=0.01, max_open=6)
        bad, wit = self.planted(S["crash_ops"], 16, self.seed + 3,
                                vmax=9, crash_rate=0.01, max_open=6,
                                crash_vmax=7)
        t0 = time.monotonic()
        lin = ck.linearizable(self.model, max_open_bits=12)
        v, vb = lin.check(None, h), lin.check(None, bad)
        check_s = time.monotonic() - t0
        expect(v["valid?"] is True, f"crash history judged {v['valid?']}")
        expect(vb["valid?"] is False, f"twin judged {vb['valid?']}")
        expect(vb.get("op_index") == wit,
               f"witness {vb.get('op_index')} != planted {wit}")
        crashed = sum(1 for o in h.ops if o.type == "info")
        return {"sizes": {"ops": S["crash_ops"], "crashed": crashed,
                          "processes": 16},
                **self.tally([v, vb]), "check_s": check_s,
                "agree": {"valid": True, "planted_witness": wit,
                          "refutation": vb.get("refutation"),
                          "final_paths": ("final-paths" in vb
                                          or vb.get("final-paths-error"))}}

    def deep(self):
        """16 x 20k-op histories at max_open 12 through the compiled
        wgl_deep megakernel (wgl_deep.check_pipeline), one planted."""
        from jepsen_tpu.ops import wgl_deep
        S = self.S
        mo = S["deep_open"]
        hs = [make_history(S["deep_ops"], 16,
                           seed=self.seed + 41 + 101 * s, vmax=9,
                           max_open=mo)
              for s in range(S["deep_hists"] - 1)]
        bad, wit = self.planted(S["deep_ops"], 16, self.seed + 57,
                                vmax=9, max_open=mo)
        hs.insert(len(hs) // 2, bad)
        stats: dict = {}
        t0 = time.monotonic()
        vs = wgl_deep.check_pipeline(self.model, hs, stats=stats)
        check_s = time.monotonic() - t0
        expect(self.platform == "cpu" or not os.environ.get(
            "JEPSEN_TPU_DEEP_INTERPRET"), "Pallas interpret mode is on")
        expect(all(v.get("engine") == "wgl_deep" for v in vs),
               f"engines {Counter(v.get('engine') for v in vs)}")
        demoted = int(stats.get("oom_demoted") or 0)
        expect(demoted == 0, f"oom_demoted {demoted}")
        i_bad = hs.index(bad)
        expect([i for i, v in enumerate(vs) if v["valid?"] is not True]
               == [i_bad], "verdicts disagree with the construction")
        return {"sizes": {"histories": len(hs), "ops": S["deep_ops"],
                          "max_open": mo},
                **self.tally(vs), "check_s": check_s,
                "interpret": self.platform == "cpu",
                "oom_demoted": demoted,
                "agree": {"planted": self.agree(vs[i_bad], bad, False,
                                                wit),
                          "valid_sampled": self.agree(vs[0], hs[0], True)}}

    G_SINGLE = [(0, [["append", "px", 1]]),
                (1, [["append", "px", 2], ["append", "py", 1]]),
                (2, [["r", "px", [1, 2]], ["r", "py", []]])]
    MONOTONIC_WRITES = [(0, [["append", "px", 1]]),
                        (0, [["append", "px", 2]]),
                        (1, [["r", "px", [2, 1]]])]

    def elle(self):
        """A 10k-txn list-append history with a planted G-single through
        checker.elle (elle-mesh), a 5k-txn session history with a
        planted monotonic-writes through the lattice checker
        (lattice-mesh), and at 1k txns the device tiers against the
        host tiers."""
        from jepsen_tpu.checker import elle as elle_ck
        from jepsen_tpu.lattice import checker as lattice_ck
        S = self.S
        h = self.txn_history(S["elle_txns"], self.seed + 5, self.G_SINGLE)
        v = elle_ck.checker(workload="list-append",
                            mesh_threshold=S["elle_threshold"]).check(
            None, h)
        expect(v["engine"] == "elle-mesh", f"elle engine {v['engine']}")
        expect(v["anomaly-types"] == ["G-single"],
               f"elle flagged {v['anomaly-types']}")
        hl = self.txn_history(S["lattice_txns"], self.seed + 6,
                              self.MONOTONIC_WRITES)
        vl = lattice_ck.checker(
            workload="list-append",
            mesh_threshold=S["lattice_threshold"]).check(None, hl)
        expect(vl["engine"] == "lattice-mesh",
               f"lattice engine {vl['engine']}")
        expect(vl["anomaly-types"] == ["monotonic-writes"],
               f"lattice flagged {vl['anomaly-types']}")
        small = {}
        for name, plant, mk in (
                ("elle", self.G_SINGLE,
                 lambda a: elle_ck.checker(workload="list-append",
                                           algorithm=a)),
                ("lattice", self.MONOTONIC_WRITES,
                 lambda a: lattice_ck.checker(workload="list-append",
                                              algorithm=a))):
            hs = self.txn_history(S["small_txns"], self.seed + 7, plant)
            dev, host = mk("device").check(None, hs), \
                mk("host").check(None, hs)
            expect(dev["engine"].endswith("-device"), dev["engine"])
            expect(host["engine"].endswith("-host"), host["engine"])
            expect(dev["anomaly-types"] == host["anomaly-types"],
                   f"{name}: device {dev['anomaly-types']} != host "
                   f"{host['anomaly-types']}")
            small[name] = dev["anomaly-types"]
        return {"sizes": {"elle_txns": S["elle_txns"],
                          "lattice_txns": S["lattice_txns"],
                          "host_compare_txns": S["small_txns"]},
                "engines": {v["engine"]: 1, vl["engine"]: 1},
                "agree": {"elle": v["anomaly-types"],
                          "lattice": vl["anomaly-types"],
                          "device_eq_host_1k": small,
                          "elle_rounds": v.get("rounds")}}

    # -- four chips ----------------------------------------------------------

    @contextlib.contextmanager
    def probe_shardings(self):
        """Count the devices of every device array the engines fetch to
        the host (their `np.asarray` calls) while the block runs."""
        import numpy as np

        from jepsen_tpu.ops import elle_mesh, wgl_deep, wgl_seg
        jax = self.jax
        seen: list = []

        class NpProbe(types.ModuleType):
            def __getattr__(self, name):
                return getattr(np, name)

            @staticmethod
            def asarray(a, *args, **kw):
                if isinstance(a, jax.Array):
                    seen.append(len(a.sharding.device_set))
                return np.asarray(a, *args, **kw)

        mods = (wgl_seg, wgl_deep, elle_mesh)
        for mod in mods:
            mod.np = NpProbe("numpy")
        try:
            yield seen
        finally:
            for mod in mods:
                mod.np = np

    def spans(self, seen: list, n: int) -> dict:
        """Every result the engine fetched spans all `n` devices."""
        expect(seen and set(seen) == {n},
               f"fetched results span {sorted(set(seen))} devices, not {n}")
        out = {"fetched_results": len(seen), "result_devices": n}
        seen.clear()
        return out

    def mesh(self):
        """The mesh paths on every device, each against its one-device
        run: verdicts and witnesses equal, results spanning all
        devices."""
        with self.probe_shardings() as seen:
            return self._mesh(seen)

    def _mesh(self, seen: list):
        import numpy as np
        from jax.sharding import Mesh

        from jepsen_tpu.ops import elle_mesh, wgl_deep, wgl_seg
        S, m = self.S, self.model
        n = len(self.devices)
        mesh = Mesh(np.array(self.devices), ("keys",))
        out: dict = {}

        def same(a, b, what):
            key = [(x["valid?"], x.get("op_index")) for x in a]
            expect(key == [(x["valid?"], x.get("op_index")) for x in b],
                   f"{what}: mesh and one-device verdicts differ")

        hists = [make_history(S["key_ops"], S["key_conc"],
                              seed=self.seed + 1000 + k)
                 for k in range(S["keys"])]
        plant_stale_read(hists[S["keys"] // 2], 0.9, 4)
        one = wgl_seg.check_many(m, hists)
        seen.clear()
        many = wgl_seg.check_many(m, hists, mesh=mesh, mesh_axis="keys")
        same(many, one, "check_many")
        out["check_many"] = {"keys": len(hists),
                             "invalid": sum(not x["valid?"] for x in many),
                             **self.spans(seen, n)}

        h = make_history(S["single_ops"], 5, seed=self.seed, vmax=9)
        bad, wit = self.planted(S["single_ops"], 5, self.seed + 1, vmax=9)
        one = [wgl_seg.check(m, x) for x in (h, bad)]
        seen.clear()
        sh = [wgl_seg.check(m, x, mesh=mesh, mesh_axis="keys")
              for x in (h, bad)]
        same(sh, one, "check(mesh=)")
        expect(all(x.get("sharded") for x in sh), "segments not sharded")
        expect(sh[1].get("op_index") == wit, "segment witness")
        out["check_segments"] = {"ops": S["single_ops"],
                                 "segments": sh[0].get("segments"),
                                 **self.spans(seen, n)}

        mo = S["deep_open"]
        dh = [make_history(S["deep_ops"], 16,
                           seed=self.seed + 41 + 101 * s, vmax=9,
                           max_open=mo) for s in range(n - 1)]
        dbad, dwit = self.planted(S["deep_ops"], 16, self.seed + 57,
                                  vmax=9, max_open=mo)
        dh.append(dbad)
        one = wgl_deep.check_pipeline(m, dh)
        seen.clear()
        dm = wgl_deep.check_mesh(m, dh, mesh, mesh_axis="keys")
        same(dm, one, "check_mesh")
        expect(dm[-1].get("op_index") == dwit, "deep witness")
        expect(all(x.get("sharded") for x in dm), "deep not sharded")
        out["deep_mesh"] = {"histories": len(dh), **self.spans(seen, n)}

        r_hc = wgl_deep.R_BASE + (n.bit_length() - 1)
        hc = self.hypercube_history(r_hc, S["hc_ops"])
        one = wgl_deep.check_pipeline(m, hc)
        seen.clear()
        hv = wgl_deep.check_hypercube(m, hc, mesh)
        same(hv, one, "check_hypercube")
        expect(all(x.get("deep_variant") == "hypercube"
                   and x.get("shards") == n for x in hv),
               "hypercube variant")
        expect(hv[1].get("op_index") == hc[1].ops[-2].index,
               "hypercube witness")
        out["hypercube"] = {"R": r_hc, "histories": len(hc),
                            "exchange_rounds": hv[0].get("exchange_rounds"),
                            **self.spans(seen, n)}

        from jepsen_tpu.elle import infer as infer_mod
        inf = infer_mod.infer(self.txn_history(S["elle_txns"],
                                               self.seed + 5,
                                               self.G_SINGLE),
                              workload="list-append")
        one = elle_mesh.classify_packed([inf.packed_stacked()], [inf.n],
                                        max_devices=1)
        seen.clear()
        em = elle_mesh.classify_packed([inf.packed_stacked(n_dev=n)],
                                       [inf.n], devices=self.devices)
        expect(em[0]["anomalies"] == one[0]["anomalies"],
               "elle_mesh: mesh and one-device classes differ")
        expect(set(em[0]["anomalies"]) == {"G-single"},
               f"elle_mesh flagged {sorted(em[0]['anomalies'])}")
        expect(em[0]["shards"] == n, "elle shards")
        out["elle_mesh"] = {"txns": S["elle_txns"],
                            "rounds": em[0]["rounds"],
                            **self.spans(seen, n)}
        return {"devices": n, "paths": out}

    @staticmethod
    def hypercube_history(R: int, n_ops: int) -> list:
        """A history forced to max_open R by a burst of R concurrent
        writes, and its twin ending in a read of a never-written value
        (witness: that read)."""
        from jepsen_tpu.history import History, invoke_op, ok_op, \
            pack_history
        base = make_history(n_ops, 20, seed=985, vmax=9, max_open=14)
        burst = ([invoke_op(100 + p, "write", p % 10) for p in range(R)]
                 + [ok_op(100 + p, "write", p % 10) for p in range(R)])
        out = []
        for tail in ([], [invoke_op(0, "read", None),
                          ok_op(0, "read", 77)]):
            h = History([o.assoc(index=None) for o in base.ops]
                        + burst + tail).index()
            h.attach_packed(pack_history(h))
            out.append(h)
        return out

    # -- driver --------------------------------------------------------------

    PHASES = ("e2e", "multikey", "single", "crash", "deep", "elle")

    def run(self) -> bool:
        if len(self.devices) > 1:
            return self.phase("mesh", self.mesh)
        ok = True
        for name in self.PHASES:
            ok &= self.phase(name, getattr(self, name))
        return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh paths and their one-device "
                         "comparisons")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found {devs[0].platform}, not tpu",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    from jepsen_tpu.ops import planner
    planner.ensure_persistent_cache()
    smoke = Smoke(FULL, args.seed, devs[:args.chips])
    if not smoke.run():
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
