"""Batched WGL: linearizability of MANY independent histories in one
device program.

The reference checks independent keys with a bounded thread pool
(`jepsen/src/jepsen/independent.clj:247-298` + checker.clj:104), each key
an isolated JVM search.  Here every per-key history is packed into a
columnar batch and the whole check is `vmap` of the frontier kernel over
the key axis — one XLA program for a million-op multi-key history
(SURVEY.md §2.4, BASELINE config 3).  The key axis shards over a TPU
mesh with `jax.sharding` (keys are embarrassingly parallel; no
collectives needed beyond the final gather).

Unlike ops/wgl.py's adaptive single-history kernel (tiered closure
pools, pure-op fast path — both built on `lax.cond`, which `vmap` would
turn into run-both-branches), this kernel uses one fixed frontier size.
Per-key register histories are short and narrow, so a small frontier
almost always suffices; lanes that overflow AND look invalid escalate
host-side to the adaptive kernel.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import numpy as np

from jepsen_tpu.errors import BackendUnavailable
from jepsen_tpu.ops import frontier
from jepsen_tpu.ops.prep import prepare
from jepsen_tpu.ops.wgl import WGLPlan, _bucket, plan

@functools.lru_cache(maxsize=32)
def _build_batch_kernel(step_fn, F: int, C: int, W: int, S: int):
    import jax
    import jax.numpy as jnp

    Wd = max((W + 31) // 32, 1)
    u32 = jnp.uint32

    has_bit, set_bit, clear_bit = frontier.make_bit_ops(Wd)
    dedupe_compact = frontier.make_dedupe_compact(Wd, S)

    def kernel(ret_call, ret_slot, cand_call, cand_slot, fv, av, bv, okv,
               init_state, n_events):
        masks0 = jnp.zeros((F, Wd), u32)
        states0 = jnp.zeros((F, S), jnp.int32).at[0].set(init_state)
        valid0 = jnp.zeros(F, bool).at[0].set(True)

        def ev_cond(carry):
            r, _, _, _, dead, _ = carry
            return (r < n_events) & ~dead

        def ev_body(carry):
            r, masks, states, valid, dead, overflow = carry
            tslot = ret_slot[r]
            cc = cand_call[r]
            cs = cand_slot[r]
            jc = jnp.clip(cc, 0, None)
            cf, ca, cb, cok = fv[jc], av[jc], bv[jc], okv[jc]
            open_c = cc >= 0

            def cl_cond(c):
                m, s, v, ovf, rounds, progressed, _ = c
                lacks = v & ~has_bit(m, jnp.broadcast_to(tslot, (F,)))
                return jnp.any(lacks) & (rounds < C) & progressed & ~ovf

            def cl_body(c):
                m, s, v, ovf, rounds, _, prev_count = c
                lacks = v & ~has_bit(m, jnp.broadcast_to(tslot, (F,)))

                def per_config(mask, state, lack):
                    def per_cand(slot, f_, a_, b_, ok_, is_open):
                        st2, legal = step_fn(state, f_, a_, b_, ok_)
                        not_lin = ~has_bit(mask[None, :], slot[None])[0]
                        okc = lack & is_open & not_lin & legal
                        m2 = set_bit(mask[None, :], slot[None])[0]
                        return m2, st2, okc
                    return jax.vmap(per_cand)(cs, cf, ca, cb, cok, open_c)

                chm, chs, chv = jax.vmap(per_config)(m, s, lacks)
                pool_m = jnp.concatenate([m, chm.reshape(F * C, Wd)])
                pool_s = jnp.concatenate([s, chs.reshape(F * C, S)])
                pool_v = jnp.concatenate([v, chv.reshape(F * C)])
                nm, ns, nv, o2, count = dedupe_compact(
                    pool_m, pool_s, pool_v, F)
                return (nm, ns, nv, ovf | o2, rounds + 1,
                        count > prev_count, count)

            masks, states, valid, ovf, _, _, _ = jax.lax.while_loop(
                cl_cond, cl_body,
                (masks, states, valid, jnp.bool_(False), jnp.int32(0),
                 jnp.bool_(True), jnp.int32(-1)))

            # prune configs that never linearized the returning call,
            # then retire its slot
            sat = has_bit(masks, jnp.broadcast_to(tslot, (F,)))
            valid = valid & sat
            masks = clear_bit(masks, jnp.broadcast_to(tslot, (F,)))
            dead = ~jnp.any(valid)
            return r + 1, masks, states, valid, dead, overflow | ovf

        r, masks, states, valid, dead, overflow = jax.lax.while_loop(
            ev_cond, ev_body,
            (jnp.int32(0), masks0, states0, valid0, jnp.bool_(False),
             jnp.bool_(False)))
        return {"ok": ~dead, "failed_event": jnp.where(dead, r - 1, -1),
                "overflow": overflow, "frontier": jnp.sum(valid)}

    return jax.jit(jax.vmap(kernel))


def _pad_plan(pl: WGLPlan, R: int, C: int, N: int) -> WGLPlan:
    """Pad a plan's arrays to batch-wide shapes: R events, C candidates,
    N calls."""

    def pad2(x, rows, cols, fill):
        out = np.full((rows, cols), fill, x.dtype)
        out[:x.shape[0], :x.shape[1]] = x
        return out

    def pad1(x, rows, fill):
        out = np.full(rows, fill, x.dtype)
        out[:x.shape[0]] = x
        return out

    return WGLPlan(
        pad1(pl.ret_call, R, -1), pad1(pl.ret_slot, R, 0),
        pad2(pl.cand_call, R, C, -1), pad2(pl.cand_slot, R, C, 0),
        pad1(pl.f, N, 0), pad1(pl.a, N, 0), pad1(pl.b, N, 0),
        pad1(pl.a_ok, N, False), pl.init_state,
        pl.n_calls, pl.n_events, pl.max_open)


def check_many(model, histories: Sequence, *,
               frontier_size: int = 256,
               mesh=None,
               escalate: bool = True,
               stats: Optional[dict] = None) -> list[dict[str, Any]]:
    """Check linearizability of many independent histories in one
    batched device call.  Returns one knossos-shaped result map per
    history (same keys as ops.wgl.check).

    mesh: optional jax.sharding.Mesh; the key axis is sharded over its
    first axis (pure data parallelism — each device checks its shard of
    keys).

    `stats` (always collected; pass a dict to read it back) receives
    the per-stage host-time decomposition — plan / pack / dispatch /
    fetch / assemble seconds — mirroring wgl_seg.check_pipeline's
    protocol, and every verdict carries a dispatch record
    (jepsen_tpu.telemetry)."""
    import jax

    from jepsen_tpu import telemetry

    spec = model.device_spec()
    if spec is None:
        raise BackendUnavailable(f"model {model!r} has no device spec")
    st = telemetry.Stages(stats)
    stats = st.seconds

    with st.span("plan"):
        preps = [h if hasattr(h, "calls") else prepare(h)
                 for h in histories]
        results: list[Optional[dict]] = [None] * len(preps)
        lanes = []  # (index, plan)
        for i, prep in enumerate(preps):
            if not prep.calls:
                results[i] = {"valid?": True, "op_count": 0}
                continue
            lanes.append((i, plan(prep, spec, model)))
    if not lanes:
        return [r for r in results]

    with st.span("pack"):
        R = _bucket(max(pl.ret_call.shape[0] for _, pl in lanes))
        C = _bucket(max(pl.cand_call.shape[1] for _, pl in lanes), 4)
        N = _bucket(max(pl.n_calls for _, pl in lanes))
        S = lanes[0][1].init_state.shape[0]
        W = max(C, _bucket(max(pl.max_open for _, pl in lanes), 4))

        padded = [_pad_plan(pl, R, C, N) for _, pl in lanes]
        K = len(padded)
        # Pad the key axis to a multiple of the mesh size so it shards
        # evenly.
        K_pad = K
        if mesh is not None:
            d = int(np.prod(list(mesh.shape.values())))
            K_pad = ((K + d - 1) // d) * d
        while len(padded) < K_pad:
            padded.append(padded[0])  # duplicate lane; result ignored

        def stack(attr):
            return np.stack([getattr(p, attr) for p in padded])

        args = [stack("ret_call"), stack("ret_slot"),
                stack("cand_call"), stack("cand_slot"), stack("f"),
                stack("a"), stack("b"), stack("a_ok"), stack("init_state"),
                np.asarray([p.n_events for p in padded], np.int32)]

        stats["wire_bytes"] = (stats.get("wire_bytes", 0)
                               + sum(a.nbytes for a in args))
    with st.span("dispatch"):
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            axis = mesh.axis_names[0]
            sharding = NamedSharding(mesh, PartitionSpec(axis))
            args = [jax.device_put(a, sharding) for a in args]

        kern = _build_batch_kernel(spec.step, int(frontier_size),
                                   int(C), int(W), int(S))
        dev = kern(*args)
    with st.span("fetch"):
        out = jax.device_get(dev)

    with st.span("assemble"):
        escalated: list = []
        for lane_idx, (i, pl) in enumerate(lanes):
            ok = bool(out["ok"][lane_idx])
            overflow = bool(out["overflow"][lane_idx])
            if ok or not overflow:
                r: dict[str, Any] = {
                    "valid?": ok, "op_count": pl.n_calls,
                    "frontier_size": frontier_size,
                    "final_frontier": int(out["frontier"][lane_idx])}
                if not ok:
                    ev = int(out["failed_event"][lane_idx])
                    cid = int(pl.ret_call[ev]) if ev >= 0 else -1
                    calls = preps[i].calls
                    if 0 <= cid < len(calls):
                        r["op"] = calls[cid].op.to_dict()
                        r["op_index"] = calls[cid].op.index
                    r["anomaly"] = "nonlinearizable"
                results[i] = r
            elif escalate:
                from jepsen_tpu.ops import wgl
                results[i] = wgl.check(model, preps[i])
                results[i].setdefault("engine", "wgl")
                escalated.append(i)
            else:
                results[i] = {"valid?": "unknown",
                              "cause": "frontier-overflow",
                              "op_count": pl.n_calls}
    # dispatch records (telemetry): batched lanes vs escalated lanes,
    # both rendering the planner-emitted plan (ops.planner)
    from jepsen_tpu.ops import planner
    mesh_desc = (dict(zip(mesh.axis_names, mesh.devices.shape))
                 if mesh is not None else None)
    batch_plan = planner.plan_engines(
        planner.Shape(kind="batch-many", batch=len(histories),
                      mesh=None if mesh is None else int(
                          np.prod(list(mesh.shape.values()))))).refine(
        why="vmap-over-keys frontier kernel "
            f"(frontier_size={int(frontier_size)})",
        bucket=("wgl_batch", int(frontier_size), int(R), int(C),
                int(W), int(S)))
    batched_rs = [r for i, r in enumerate(results)
                  if isinstance(r, dict) and i not in set(escalated)]
    telemetry.attach_dispatch(
        batched_rs,
        batch_plan.record(engine="wgl_batch", batch=len(histories),
                          mesh=mesh_desc),
        stages=stats)
    for i in escalated:
        telemetry.attach_dispatch(
            [results[i]],
            batch_plan.refine(
                why="frontier overflow on an invalid-looking lane; "
                    "escalated to the adaptive serial kernel").record(
                engine=results[i].get("engine", "wgl"), batch=1))
    return [r for r in results]
