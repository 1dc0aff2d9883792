"""The one engine planner (ops/planner.py, ISSUE 8): routing
properties (every shape -> exactly one terminating chain, decided by
the shape and the backend alone), plan rendering into dispatch records,
the compiled-plan cache, and the async double-buffered executor's correctness
(verdict-identical to serial dispatch; ResilientRunner bisection still
fires mid-pipeline with donation enabled)."""

import hashlib
import os
import random

import numpy as np
import pytest

from jepsen_tpu import models, telemetry
from jepsen_tpu.errors import DeviceOOM
from jepsen_tpu.ops import planner, runner, wgl_cpu, wgl_deep, wgl_seg
from tests.test_wgl_seg import rand_history


def rand_shape(rng) -> planner.Shape:
    return planner.Shape(
        kind=rng.choice(["linear", "linear-many", "linear-pipeline",
                         "deep-pipeline", "batch-many"]),
        R=rng.randrange(0, 20),
        crashes=rng.choice([0, 0, 0, 1, 2, 5, 9]),
        Sn=rng.choice([None, 1, 2, 5, 8, 16, 33, 80]),
        U=rng.choice([None, 1, 50, 40_000]),
        decomposed=rng.choice([None, True, False]),
        batch=rng.choice([1, 3, 128, 3400]),
        n_ops=rng.randrange(0, 10_000),
        mesh=rng.choice([None, None, 2, 8]),
        device=rng.random() < 0.9,
        max_states=rng.choice([16, 64]),
        max_open_bits=rng.choice([10, 14]))


# ---------------------------------------------------------------------------
# Routing properties — the ROADMAP #1 acceptance pins
# ---------------------------------------------------------------------------

class TestPlanProperties:
    def test_every_shape_routes_to_one_terminating_chain(self):
        """Seeded-random sweep: every generated (R, crashes, Sn, batch,
        mesh, backend) shape yields exactly one chain, duplicate-free,
        ending in a total engine — nothing can fall off the ladder."""
        rng = random.Random(11)
        for _ in range(400):
            shape = rand_shape(rng)
            env = {}
            backend = rng.choice(["cpu", "tpu"])
            if backend == "cpu" and rng.random() < 0.5:
                env["JEPSEN_TPU_DEEP_INTERPRET"] = "1"
            pl = planner.plan_engines(shape, env=env, backend=backend)
            assert pl.chain, (shape, env)
            assert pl.engine == pl.chain[0]
            assert len(set(pl.chain)) == len(pl.chain), pl.chain
            assert pl.chain[-1] in planner.TERMINAL_ENGINES, \
                (shape, env, pl.chain)
            assert pl.why, (shape, env)

    def test_sweep_chains_pinned(self):
        """The chain every swept shape gets on each backend, with no
        environment and with the cpu interpreter available, pinned by
        digest: routing is a function of the shape and the backend
        alone, so a change to any shape's chain is a routing change and
        must update the digest on purpose."""
        rng = random.Random(11)
        rows = []
        for _ in range(400):
            shape = rand_shape(rng)
            for backend, env in (
                    ("cpu", {}),
                    ("cpu", {"JEPSEN_TPU_DEEP_INTERPRET": "1"}),
                    ("tpu", {})):
                rows.append(" ".join(planner.plan_engines(
                    shape, env=env, backend=backend).chain))
        assert len(set(rows)) == 23
        assert hashlib.sha256("\n".join(rows).encode()).hexdigest() == (
            "a414086730d668da2aaf7cc33a45ac4de186255bf6940fd9e7edaf6a798bff26")

    def test_deep_interpret_is_availability_not_a_prune_knob(self):
        # the one variable that can ADD an engine is a backend
        # capability (like running on a TPU), not routing
        shape = planner.Shape(kind="linear", R=9, Sn=4, U=6,
                              decomposed=True)
        off = planner.plan_engines(shape, env={}, backend="cpu")
        on = planner.plan_engines(
            shape, env={"JEPSEN_TPU_DEEP_INTERPRET": "1"},
            backend="cpu")
        assert "wgl_deep" not in off.chain
        assert on.engine == "wgl_deep"
        # on TPU it changes nothing
        t_off = planner.plan_engines(shape, env={}, backend="tpu")
        t_on = planner.plan_engines(
            shape, env={"JEPSEN_TPU_DEEP_INTERPRET": "1"},
            backend="tpu")
        assert t_off.chain == t_on.chain

    def test_pinned_routes(self):
        S = planner.Shape
        # shallow decomposed register: register-delta head
        assert planner.plan_engines(
            S(kind="linear", R=3, Sn=4, U=6, decomposed=True),
            env={}, backend="cpu").engine == "wgl_seg_regs"
        # deep regime on TPU
        assert planner.plan_engines(
            S(kind="linear", R=12, Sn=4, U=6, decomposed=True),
            env={}, backend="tpu").engine == "wgl_deep"
        # undecomposable wide state: serial chain
        pl = planner.plan_engines(
            S(kind="linear", R=12, Sn=40, U=6, decomposed=False),
            env={}, backend="tpu")
        assert pl.engine == "wgl"
        assert pl.chain[-1] == "wgl_cpu"
        # batch, mesh-sharded or not: register-delta lanes
        for mesh in (None, 8):
            pl = planner.plan_engines(
                S(kind="linear-many", R=4, Sn=4, U=9, decomposed=True,
                  batch=100, mesh=mesh), env={}, backend="cpu")
            assert pl.engine == "wgl_seg_batch_regs"

    def test_elle_tiers(self):
        pl = planner.plan_elle(100_000)
        assert pl.chain == ("elle-mesh", "elle-device", "elle-host")
        pl = planner.plan_elle(100)
        assert pl.chain == ("elle-device", "elle-host")
        assert ("elle-mesh", "n_max=100 below mesh_threshold") \
            in pl.rejected
        assert planner.plan_elle(5, algorithm="mesh").chain == \
            ("elle-mesh", "elle-host")
        assert planner.plan_elle(5, algorithm="host").chain == \
            ("elle-host",)

    def test_live_bucket_matches_engine_bucketing(self):
        pl = planner.plan_live(lanes=5, events=100, bits=3, states=4)
        assert pl.engine == "live-jit"
        assert pl.fallbacks == ("live-host",)
        # pow2 lanes, 64-floored events, 2^bits rows, 8-floored states
        assert pl.bucket == ("live", 8, 128, 8, 8)

    def test_gates_shared_with_engines(self):
        # wgl_seg routes on the planner's own gate (re-export), and
        # wgl_deep.supported delegates — the gates cannot drift
        assert wgl_seg._regs_eligible is planner._regs_eligible
        for args in ((9, 4, 6, True), (3, 33, 6, True),
                     (14, 32, 100, True), (15, 4, 6, True),
                     (17, 4, 6, True)):
            for backend in ("cpu", "tpu"):
                for nd in (None, 2, 8):
                    assert wgl_deep.supported(
                        *args, backend, n_devices=nd) == \
                        planner.deep_supported(*args, backend,
                                               n_devices=nd)
        assert wgl_deep.R_BASE == planner.DEEP_R_BASE

    def test_deep_r_max_envelope(self):
        # ISSUE 10: the hard DEEP_R_MAX constant is gone; the boundary
        # is backend/mesh-aware
        assert not hasattr(planner, "DEEP_R_MAX")
        assert planner.deep_r_max("tpu", 1) == 16       # word-split
        assert planner.deep_r_max("tpu", 2) == 16
        assert planner.deep_r_max("tpu", 8) == 17       # hypercube
        assert planner.deep_split_planes(14) == 1
        assert planner.deep_split_planes(15) == 2
        assert planner.deep_split_planes(16) == 4

    def test_deep_variant_routes_and_shard_knob(self):
        S = planner.Shape
        # R=15 single device: word-split head, plan carries provenance
        pl = planner.plan_engines(
            S(kind="linear", R=15, Sn=4, U=6, decomposed=True),
            env={}, backend="tpu")
        assert pl.engine == "wgl_deep_split"
        assert pl.deep_variant == "word-split" and pl.shards == 2
        # R=17 with an 8-device mesh: the hypercube tier is in chain
        pl = planner.plan_engines(
            S(kind="linear", R=17, Sn=4, U=6, decomposed=True, mesh=8),
            env={}, backend="tpu")
        assert "wgl_deep_hc" in pl.chain
        # deep-mesh batches beyond one device's stack route hypercube
        pl = planner.plan_engines(
            S(kind="deep-mesh", R=17, Sn=4, U=6, decomposed=True,
              mesh=8), env={}, backend="tpu")
        assert pl.engine == "wgl_deep_hc"
        assert pl.deep_variant == "hypercube"
        assert pl.shards == 8 and pl.exchange_rounds == 3

    @pytest.mark.parametrize("mesh", [None, 2, 8])
    def test_deep_boundary_by_shape(self, mesh):
        """The deep envelope ends where the shape says: at
        R == deep_r_max a deep variant heads the chain, one past it the
        serial kernel does."""
        S = planner.Shape
        r_max = planner.deep_r_max("tpu", mesh)
        at = planner.plan_engines(
            S(kind="linear", R=r_max, Sn=4, U=6, decomposed=True,
              mesh=mesh), env={}, backend="tpu")
        assert at.engine.startswith("wgl_deep"), at.chain
        assert at.deep_variant
        past = planner.plan_engines(
            S(kind="linear", R=r_max + 1, Sn=4, U=6, decomposed=True,
              mesh=mesh), env={}, backend="tpu")
        assert past.engine == "wgl", past.chain
        assert not any(e.startswith("wgl_deep") for e in past.chain)


# ---------------------------------------------------------------------------
# Plan rendering — verdicts carry the plan verbatim
# ---------------------------------------------------------------------------

class TestPlanRendering:
    def test_check_attaches_planner_plan(self):
        r = wgl_seg.check(models.CASRegister(), rand_history(5))
        d = r["dispatch"]
        assert d["engine"] == r["engine"]
        pl = d["plan"]
        assert pl["engine"] == "wgl_seg_regs"
        assert pl["fallbacks"][-1] == "wgl_cpu"
        assert d["why"] == pl["why"]
        assert d["fallback_chain"] == pl["fallbacks"]
        assert pl["bucket"][0] == "wgl_seg_regs"
        assert "_plan" not in r          # internal key never leaks

    def test_check_many_attaches_plan(self):
        rs = wgl_seg.check_many(models.CASRegister(),
                                [rand_history(40 + s) for s in range(3)])
        for r in rs:
            pl = r["dispatch"]["plan"]
            assert pl["engine"] == "wgl_seg_batch_regs"
            assert pl["why"]

    def test_summarize_renders_plans(self):
        events = [{"type": "dispatch", "verdicts": 2, "record": {
            "engine": "wgl_seg",
            "why": "R=3 Sn=4: register-delta segment kernel",
            "fallback_chain": ["wgl_seg", "wgl", "wgl_cpu"],
            "plan": {"engine": "wgl_seg_regs"},
        }}]
        out = telemetry.summarize(events)
        assert "dispatch plans:" in out
        assert "wgl_seg -> wgl_seg -> wgl -> wgl_cpu" in out
        assert "register-delta segment kernel" in out

    def test_web_dispatch_panel(self):
        from jepsen_tpu import web
        events = [{"type": "dispatch", "verdicts": 3, "record": {
            "engine": "wgl_seg", "why": "pipelined",
            "fallback_chain": ["wgl", "wgl_cpu"],
            "plan": {"bucket": ["wgl_seg_pipeline", 4]}}}]
        html_out = web._dispatch_plans_html(events)
        assert "Dispatch plans" in html_out
        assert "pipelined" in html_out
        assert "wgl_seg_pipeline" in html_out


# ---------------------------------------------------------------------------
# Compiled-plan cache
# ---------------------------------------------------------------------------

class TestCompiledPlanCache:
    def test_hit_miss_counters(self):
        calls = []

        def builder(x):
            calls.append(x)
            return lambda: x

        before = planner.cache_stats()
        key = ("test-engine", ("b", 1, id(self)))
        fn1 = planner.compiled(*key, builder, 7)
        fn2 = planner.compiled(*key, builder, 7)
        assert fn1 is fn2 and calls == [7]
        after = planner.cache_stats()
        assert after["miss"] == before["miss"] + 1
        assert after["hit"] == before["hit"] + 1

    def test_info_reports_hit(self):
        info: dict = {}
        key = ("test-engine", ("info", id(self)))
        planner.compiled(*key, lambda: object, info=info)
        assert info["hit"] is False
        planner.compiled(*key, lambda: object, info=info)
        assert info["hit"] is True

    def test_aot_lower_compile_and_timing(self):
        import jax
        import jax.numpy as jnp

        def builder():
            return jax.jit(lambda x: x + 1)

        before = planner.cache_stats()["compile_s"]
        fn = planner.compiled(
            "test-engine", ("aot", id(self)), builder,
            lower_args=(jax.ShapeDtypeStruct((4,), jnp.int32),))
        out = np.asarray(fn(np.arange(4, dtype=np.int32)))
        assert out.tolist() == [1, 2, 3, 4]
        # the AOT compile was timed into the planner's accounting
        assert planner.cache_stats()["compile_s"] > before

    @pytest.mark.parametrize("env_set", [True, False])
    def test_persistent_cache_one_rule(self, env_set, monkeypatch,
                                       tmp_path):
        """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
        otherwise the fixed checkout path, never the CWD's."""
        import jax
        before = jax.config.jax_compilation_cache_dir
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            want = str(tmp_path)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            monkeypatch.chdir(tmp_path)
            want = planner.CACHE_DIR
            assert os.path.isabs(want)
            assert want.endswith(os.path.join(".cache", "jax"))
            # anchored on the package, not the CWD
            checkout = os.path.dirname(os.path.dirname(want))
            assert os.path.isdir(os.path.join(checkout, "jepsen_tpu"))
        got = planner.ensure_persistent_cache()
        assert got == want
        assert planner.cache_stats()["persistent_dir"] == want
        if env_set:
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert jax.config.jax_compilation_cache_dir == want
        # small kernels persist in both cases
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

    def test_engine_paths_count_into_cache(self):
        planner.reset_cache_stats()
        hists = [rand_history(700 + s, n_ops=60) for s in range(3)]
        wgl_seg.check_many(models.CASRegister(), hists)
        first = planner.cache_stats()
        assert first["miss"] >= 1
        wgl_seg.check_many(models.CASRegister(), hists)
        second = planner.cache_stats()
        assert second["hit"] > first["hit"]
        assert second["miss"] == first["miss"]   # warm: zero compiles


# ---------------------------------------------------------------------------
# Async double-buffered executor
# ---------------------------------------------------------------------------

class TestOverlapExecutor:
    def test_interleaving_and_depth_bound(self):
        log = []

        class Out:
            def __init__(self, i):
                self.i = i

            def block_until_ready(self):
                log.append(("block", self.i))

        outs = runner.overlap(
            range(5),
            pack=lambda i: log.append(("pack", i)) or i,
            dispatch=lambda i: log.append(("dispatch", i)) or Out(i),
            depth=2)
        assert [o.i for o in outs] == [0, 1, 2, 3, 4]
        # pack k+1 happens BEFORE anything blocks on k (overlap), and
        # the host never runs more than `depth` dispatches ahead
        assert log.index(("pack", 2)) < log.index(("block", 0))
        assert log == [
            ("pack", 0), ("dispatch", 0),
            ("pack", 1), ("dispatch", 1),
            ("pack", 2), ("dispatch", 2), ("block", 0),
            ("pack", 3), ("dispatch", 3), ("block", 1),
            ("pack", 4), ("dispatch", 4), ("block", 2)]

    def test_exceptions_propagate(self):
        def dispatch(i):
            if i == 3:
                raise DeviceOOM("RESOURCE_EXHAUSTED in chunk")
            return i

        with pytest.raises(DeviceOOM):
            runner.overlap(range(5), pack=lambda i: i,
                           dispatch=dispatch)

    @pytest.mark.parametrize("chunk", [2, 5])
    def test_chunked_check_many_bit_identical(self, monkeypatch, chunk):
        """Randomized differential sweep: double-buffered verdicts are
        identical to monolithic single-dispatch verdicts AND the CPU
        oracle — valid?, witness op_index, and engine attribution —
        including crash-bearing keys that ride the stripped twin and
        per-key fallback chains."""
        model = models.CASRegister()
        hists = [rand_history(1500 + s, n_ops=90, conc=3,
                              buggy=(s % 3 == 1),
                              crash_at=30 if s % 4 == 0 else None)
                 for s in range(11)]
        monkeypatch.setattr(wgl_seg, "OVERLAP_CHUNK", len(hists) + 1)
        mono = wgl_seg.check_many(model, hists)
        monkeypatch.setattr(wgl_seg, "OVERLAP_CHUNK", chunk)
        buffered = wgl_seg.check_many(model, hists)
        for i, (a, b) in enumerate(zip(mono, buffered)):
            assert a["valid?"] == b["valid?"], i
            assert a.get("op_index") == b.get("op_index"), i
            assert a.get("engine") == b.get("engine"), i
            o = wgl_cpu.check(model, hists[i])
            assert a["valid?"] == o["valid?"], i
        assert any(r.get("stages", {}).get("overlap_chunks", 0) > 1
                   for r in buffered if isinstance(r, dict))

    def test_oom_mid_pipeline_bisection_with_donation(self):
        """An OOM raised mid-overlap (with donated input buffers in
        play) must surface to the ResilientRunner and bisect, not
        wedge: every dispatch re-packs a fresh host buffer, so retries
        never touch a consumed donation."""
        import jax

        donated = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        oom_state = {"armed": True}

        def engine(model, hists, **kw):
            del model, kw

            def pack(h):
                return np.asarray([len(h.ops)], np.int32)

            def dispatch(payload):
                if oom_state["armed"] and len(hists) > 1:
                    raise DeviceOOM(
                        "RESOURCE_EXHAUSTED: out of memory on chunk")
                # donation consumes the freshly-packed buffer only
                return donated(payload)

            outs = runner.overlap(hists, pack, dispatch)
            return [{"valid?": True,
                     "op_count": int(np.asarray(o)[0]) - 1}
                    for o in outs]

        hists = [rand_history(2000 + s, n_ops=40) for s in range(6)]
        before = telemetry.REGISTRY.counter(
            "jepsen_runner_oom_bisections_total").value
        rr = runner.ResilientRunner(engine=engine, max_group=8,
                                    sleep=lambda s: None)
        rs = rr.check(models.CASRegister(), hists)
        after = telemetry.REGISTRY.counter(
            "jepsen_runner_oom_bisections_total").value
        assert after > before                    # bisection fired
        assert all(r["valid?"] is True for r in rs)
        assert all(r["op_count"] == len(h.ops)
                   for r, h in zip(rs, hists))


# ---------------------------------------------------------------------------
# Extraction pins (ISSUE 8 satellite: host planning lives in planner)
# ---------------------------------------------------------------------------

class TestExtraction:
    def test_wgl_seg_reexports_are_planner_objects(self):
        for name in ("plan", "_assign_slots", "_segment_ends",
                     "_cols_args", "_scan_history", "_fast_scan",
                     "_native_scan", "_enumerate_states", "_decompose",
                     "_encode_calls", "_fk_arrays", "SegPlan",
                     "_FastKey", "Unsupported"):
            assert getattr(wgl_seg, name) is getattr(planner, name), \
                name

    def test_wgl_seg_below_three_thousand_lines(self):
        # the satellite's stated acceptance: the host-planning section
        # moved out, wgl_seg keeps kernels + entry points
        import inspect

        src = inspect.getsource(wgl_seg)
        assert src.count("\n") < 3000, src.count("\n")

    def test_runner_resolve_engine_unchanged(self):
        assert runner._resolve_engine("seg_many") \
            is wgl_seg.check_many
        assert runner._resolve_engine("auto") \
            is wgl_seg.check_pipeline
