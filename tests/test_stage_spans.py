"""Stage spans (`telemetry.Stages`): one primitive gives the stage
counters and the profiler's `jepsen.*` annotations.  Self seconds never
overlap, the single path and `check_many` count each call's stages once,
the device programs carry stable names, and a CPU profiler trace of an
`independent.batch_checker` check shows the spans nested in the call,
matching the counters."""

import time
from pathlib import Path

import numpy as np
import pytest

from jepsen_tpu import independent as ind
from jepsen_tpu import models, telemetry
from jepsen_tpu.history import History
from jepsen_tpu.ops import planner, wgl_seg
from tests.test_wgl_seg import rand_history

COUNTS = ("wire_bytes", "overlap_chunks")


def counter(name: str, label: str) -> dict:
    """{label value: total} of one registry counter."""
    _, by_label = telemetry.REGISTRY.collect().get(name, ("counter", {}))
    out: dict = {}
    for key, m in by_label.items():
        v = dict(key).get(label)
        out[v] = out.get(v, 0.0) + m.value
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


def seconds(stages: dict) -> dict:
    return {k: v for k, v in stages.items() if k not in COUNTS}


def keyed_history(n_keys: int, n_ops: int = 60, seed: int = 11,
                  invalid_key=None) -> History:
    """`n_keys` register histories run one after another, values
    wrapped as independent keys, three processes per key."""
    ops = []
    for k in range(n_keys):
        sub = rand_history(seed + k, n_ops=n_ops, conc=3,
                           buggy=(k == invalid_key))
        ops += [o.assoc(value=ind.KV(k, o.value),
                        process=o.process + 3 * k) for o in sub]
    return History(ops).index()


class TestPrimitive:
    def test_self_seconds_exclude_counted_children(self):
        st = telemetry.Stages()
        t0 = time.monotonic()
        with st.span("outer"):
            time.sleep(0.02)
            with st.span("inner"):
                time.sleep(0.03)
        wall = time.monotonic() - t0
        assert st.seconds["inner"] >= 0.03
        assert 0.02 <= st.seconds["outer"] < 0.03
        assert sum(st.seconds.values()) <= wall

    def test_children_of_another_stages_object_are_subtracted(self):
        outer, inner = telemetry.Stages(), telemetry.Stages()
        with outer.span("kernel"):
            with inner.span("witness"):
                time.sleep(0.03)
        assert inner.seconds["witness"] >= 0.03
        assert outer.seconds["kernel"] < 0.03

    def test_trace_only_spans_stay_in_the_parent(self):
        st = telemetry.Stages()
        with st.span("kernel"):
            with telemetry.trace("kernel.fetch"):
                time.sleep(0.02)
        assert set(st.seconds) == {"kernel"}
        assert st.seconds["kernel"] >= 0.02

    def test_a_raise_closes_the_span(self):
        st = telemetry.Stages()
        with pytest.raises(ValueError):
            with st.span("scan"):
                raise ValueError("boom")
        with st.span("pack"):
            pass
        assert set(st.seconds) == {"scan", "pack"}
        # nothing left open: a later span has no parent to charge
        assert getattr(telemetry._open, "span", None) is None

    def test_tallies_reach_the_registry_on_flush_only(self):
        st = telemetry.Stages()
        before = counter("jepsen_test_tally_total", "kind")
        for _ in range(3):
            st.tally("jepsen_test_tally_total", 2, kind="a")
        assert counter("jepsen_test_tally_total", "kind") == before
        st.flush()
        got = delta(counter("jepsen_test_tally_total", "kind"), before)
        assert got == {"a": 6}
        st.flush()                      # tallies are cleared
        assert delta(counter("jepsen_test_tally_total", "kind"),
                     before) == {"a": 6}

    def test_attach_counts_stage_seconds_once(self):
        before = counter(telemetry.STAGE_SECONDS, "stage")
        stages = {"scan": 0.5, "wire_bytes": 10}
        telemetry.attach_dispatch([{}], {"engine": "e1"}, stages)
        telemetry.attach_dispatch([{}], {"engine": "e2"}, stages,
                                  count_stages=False)
        assert delta(counter(telemetry.STAGE_SECONDS, "stage"),
                     before) == pytest.approx({"scan": 0.5})


class TestNoDoubleCounts:
    def test_check_many_counts_its_stages_once(self, monkeypatch):
        """A batch whose verdicts come from two engines (one key with no
        calls) counts each stage once, and the packing the overlapped
        executor runs inside its window is `pack`, never `kernel`."""
        model = models.CASRegister()
        hs = [rand_history(s, n_ops=60) for s in range(5)] + [History([])]
        wgl_seg.check_many(model, hs)           # compile outside
        real = planner._native_pack_compact

        def slow_pack(*a, **kw):
            time.sleep(0.2)
            return real(*a, **kw)
        monkeypatch.setattr(planner, "_native_pack_compact", slow_pack)
        before = counter(telemetry.STAGE_SECONDS, "stage")
        calls0 = counter(telemetry.DEVICE_CALLS, "engine")
        t0 = time.monotonic()
        rs = wgl_seg.check_many(model, hs)
        wall = time.monotonic() - t0
        engines = {r["engine"] for r in rs}
        assert engines == {"wgl_seg_batch_regs", "wgl_seg_batch"}
        stages = seconds(rs[0]["stages"])
        assert seconds(rs[-1]["stages"]) == stages
        got = delta(counter(telemetry.STAGE_SECONDS, "stage"), before)
        assert got == pytest.approx(stages, abs=1e-9)
        assert {"scan", "tables", "pack", "kernel"} <= set(stages)
        assert sum(stages.values()) <= wall
        assert stages["pack"] >= 0.2
        assert stages["kernel"] < 0.2
        assert delta(counter(telemetry.DEVICE_CALLS, "engine"),
                     calls0) == {"wgl_seg_batch_regs": 1}
        assert not any(k.startswith("time_") for r in rs for k in r)

    def test_single_path_splits_plan_into_scan_tables_pack(self):
        model = models.CASRegister()
        h = rand_history(3, n_ops=400, attach=True)
        wgl_seg.check(model, h)                 # compile outside
        before = counter(telemetry.STAGE_SECONDS, "stage")
        scans = counter(telemetry.SCAN_KEYS, "path")
        lanes = counter(telemetry.LANES, "kind")
        t0 = time.monotonic()
        r = wgl_seg.check(model, h)
        wall = time.monotonic() - t0
        stages = seconds(r["stages"])
        assert set(stages) == {"scan", "tables", "pack", "kernel"}
        got = delta(counter(telemetry.STAGE_SECONDS, "stage"), before)
        assert got == pytest.approx(stages, abs=1e-9)
        assert sum(stages.values()) <= wall
        assert delta(counter(telemetry.SCAN_KEYS, "path"),
                     scans) == {"columnar": 1}
        assert delta(counter(telemetry.LANES, "kind"),
                     lanes) == {"used": r["segments"]}
        assert not any(k.startswith("time_") for k in r)

    def test_witness_is_a_front_stage(self):
        model = models.CASRegister()
        h = rand_history(1, n_ops=300, buggy=True, attach=True)
        wgl_seg.check(model, h)
        before = counter(telemetry.CHECKER_SECONDS, "stage")
        r = wgl_seg.check(model, h)
        assert r["valid?"] is False and r.get("op_index") is not None
        assert "witness" in delta(counter(telemetry.CHECKER_SECONDS,
                                          "stage"), before)
        assert "witness" not in r["stages"]


class TestProgramNames:
    def test_the_cells_programs_are_named_for_their_engine(self):
        import jax

        sds = jax.ShapeDtypeStruct
        K, L, Sn, R, U, Rp = 128, 16, 5, 2, 10, 8192
        many = wgl_seg._build_kernel_regs_many_c(
            K, L, 1, Sn, R, True, R, 1, U, Rp, False)
        batch = many.lower(sds((Rp * 2 + 4 * (K + 1),), np.uint8),
                           sds((3 * U,), np.uint32)).as_text()
        I, K1 = 2, 4
        single = wgl_seg._build_kernel_regs_packed(
            K1, L, I, 1, Sn, R, True, R, 1, Sn, 0, R, U, False)
        one = single.lower(sds((L * K1 * (1 + 2 * I),), np.uint8),
                           sds((3 * U,), np.uint32)).as_text()
        assert "@jit_wgl_seg_batch_regs" in batch.splitlines()[0]
        assert "@jit_wgl_seg_regs" in one.splitlines()[0]
        assert "jit_fn" not in batch and "jit_fn" not in one


class TestProfilerTrace:
    COUNTED = ("keys", "split", "scan", "tables", "pack", "kernel",
               "witness", "assemble")

    def test_spans_on_the_host_plane_match_the_counters(self, tmp_path):
        """One batched check under the profiler: the program's counted
        `jepsen.*` spans lie inside the call on the host plane, and each
        stage's self time in the trace matches its counter."""
        import jax
        from jax.profiler import ProfileData

        model = models.CASRegister()
        h = keyed_history(6, invalid_key=2)
        chk = ind.batch_checker(model)
        chk.check(None, h)                      # compile outside
        names = (telemetry.STAGE_SECONDS, telemetry.CHECKER_SECONDS)
        before = [counter(n, "stage") for n in names]
        scans = counter(telemetry.SCAN_KEYS, "path")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test.call"):
                out = chk.check(None, h)
        finally:
            jax.profiler.stop_trace()
        assert out["valid?"] is False and out["failures"] == [2]
        counted: dict = {}
        for n, b in zip(names, before):
            counted.update(delta(counter(n, "stage"), b))
        assert delta(counter(telemetry.SCAN_KEYS, "path"),
                     scans) == {"objects": 6}

        trace = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
        pd = ProfileData.from_file(str(trace))
        host = [p for p in pd.planes if p.name == "/host:CPU"]
        assert host, [p.name for p in pd.planes]
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for ln in host[0].lines for e in ln.events]
        call = [(a, b) for n, a, b in events if n == "test.call"]
        assert len(call) == 1
        lo, hi = call[0]
        mine = [(n[len("jepsen."):], a, b) for n, a, b in events
                if n.startswith("jepsen.") and lo <= a and b <= hi]
        spans = [s for s in mine if s[0] in self.COUNTED]
        for stage in ("split", "scan", "tables", "pack", "kernel"):
            assert any(s[0] == stage for s in spans), stage
        # the call transfers its host arrays itself off a mesh (no
        # donation on the CPU, so no explicit put)
        assert {n for n, _, _ in mine} >= {"kernel.call", "kernel.fetch"}
        got = self_seconds(spans)
        assert set(got) == set(counted)
        for stage, s in got.items():
            assert abs(s - counted[stage]) <= max(
                0.1 * counted[stage], 5e-4), (stage, s, counted[stage])


def self_seconds(spans: list) -> dict:
    """{name: seconds} of trace spans less their directly nested
    counted spans (one host thread, so spans nest)."""
    out: dict = {}
    stack: list = []                    # [name, end, child_ns, start]

    def close(top):
        name, end, child, start = top
        out[name] = out.get(name, 0.0) + (end - start - child) / 1e9
        if stack:
            stack[-1][2] += end - start
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        stack.append([name, b, 0, a])
    while stack:
        close(stack.pop())
    return out


class TestBenchmarkReaders:
    """`benchmark/metrics/{scan,pack}_s_per_req.py`: the window's stage
    seconds per request, and nothing where the program has no such
    stage (a parent without the span)."""

    @pytest.mark.parametrize("name,stage", [("scan_s_per_req", "scan"),
                                            ("pack_s_per_req", "pack")])
    def test_stage_seconds_per_request(self, name, stage):
        from benchmark import harness
        mod = harness.load_module(harness.BENCH_DIR, "metrics", name)
        reqs = [{"latency_s": 0.01}] * 4
        ctx = {"requests": reqs,
               "stages": {stage: 0.02, "kernel": 0.5, "plan": 1.0}}
        assert mod.read(ctx) == pytest.approx(0.005)
        assert mod.read({"requests": reqs, "stages": {"plan": 1.0}}) \
            is None
        assert mod.read({"requests": [], "stages": {stage: 1.0}}) is None
