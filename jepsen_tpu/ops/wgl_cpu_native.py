"""Native (C) CPU linearizability oracle — the second baseline.

Same Lowe-style just-in-time linearization as ops.wgl_cpu (the
knossos-equivalent reference implementation), with the hot loop in C
over the integer uop tables the device kernels use.  Device speedups
are stated against BOTH oracles (BASELINE.md) so the ratios carry no hidden
interpreter constant (the reference runs knossos on a 32 GB JVM,
jepsen/project.clj:30; this native oracle bounds any
"Python-was-just-slow" objection from below).

Scope: models with a DeviceSpec and no custom encode_op, histories
with <= 64 simultaneously pending (open + crashed) calls and <= 2^31
enumerated states.  Everything else falls back to the Python oracle —
check() is verdict-identical to wgl_cpu.check on the shared domain
(differential tests enforce it).
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from jepsen_tpu.ops.prep import PreparedHistory, prepare

_I32 = 2 ** 31


def check(model, history, *,
          max_configs: int = 1_000_000,
          time_limit: Optional[float] = None,
          cancel=None) -> dict[str, Any]:
    """Drop-in for wgl_cpu.check; falls back to it outside the native
    scope (no spec / custom encoding / deep pending / cancel racing —
    the C loop cannot observe a threading.Event mid-walk)."""
    from jepsen_tpu import native
    from jepsen_tpu.ops import wgl_cpu

    mod = native.wgloracle()
    spec = model.device_spec()
    if (mod is None or cancel is not None or spec is None
            or getattr(spec, "encode_op", None) is not None):
        return wgl_cpu.check(model, history, max_configs=max_configs,
                             time_limit=time_limit, cancel=cancel)
    from jepsen_tpu.ops.wgl import _generic_encode_op
    from jepsen_tpu.ops.wgl_seg import Unsupported, _enumerate_states

    seen: dict = {}
    rows: list = []
    calls = None
    prep = None
    ev_kind = ev_cid = call_uop_b = None
    n_calls = 0
    n_events = 0
    # Fast ingest: event streams built in C straight from the
    # history's columns (the same courtesy the device path gets from
    # the journal) — the Python prepare() walk only runs when no
    # columns exist or the columnar ingest is out of scope.
    packed = (history.packed_columns()
              if hasattr(history, "packed_columns") else None)
    if packed is not None and getattr(packed, "vkind", None) is not None \
            and getattr(packed, "key", None) is None \
            and hasattr(mod, "prep_cols"):
        fmap = _spec_fmap(packed, spec)
        out = mod.prep_cols(
            np.ascontiguousarray(packed.process, np.int32),
            np.ascontiguousarray(packed.type, np.uint8),
            np.ascontiguousarray(fmap),
            np.ascontiguousarray(packed.value[:, 0].astype(np.int32)),
            np.ascontiguousarray(packed.value[:, 1].astype(np.int32)),
            np.ascontiguousarray(packed.vkind, np.uint8),
            seen, rows)
        if out is not None:
            n_calls, ev_kind, ev_cid, call_uop_b, _ = out
            n_events = len(ev_kind)
    if ev_kind is None:
        prep = history if isinstance(history, PreparedHistory) \
            else prepare(history)
        calls = prep.calls
        if not calls:
            return {"valid?": True, "op_count": 0, "configs": []}
        call_uop = np.empty(len(calls), np.int32)
        for c in calls:
            fc, av, bv, okv = _generic_encode_op(c.op, spec.f_codes)
            if fc < 0 or not (-_I32 <= av < _I32
                              and -_I32 <= bv < _I32):
                return wgl_cpu.check(model, history,
                                     max_configs=max_configs,
                                     time_limit=time_limit)
            key = (fc, av, bv, okv)
            u = seen.get(key)
            if u is None:
                u = seen[key] = len(rows)
                rows.append(key)
            call_uop[c.id] = u
        ev_kind = np.asarray([k for _, k, _ in prep.events],
                             np.uint8).tobytes()
        ev_cid = np.asarray([c for _, _, c in prep.events],
                            np.int32).tobytes()
        call_uop_b = call_uop.tobytes()
        n_calls = len(calls)
        n_events = len(prep.events)
    if n_calls == 0:
        return {"valid?": True, "op_count": 0, "configs": []}
    uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
    init = np.asarray(spec.encode(model), np.int32)
    try:
        states, legal, next_state = _enumerate_states(
            spec, init, uops, 4096)
    except Unsupported:
        from jepsen_tpu import telemetry
        telemetry.count_fallback("wgl_cpu_native", "state-space")
        return wgl_cpu.check(model, history, max_configs=max_configs,
                             time_limit=time_limit)
    Sn = states.shape[0]

    code, events_done, fail_event, fail_cid, n_seen, surv, pend = \
        mod.run(ev_kind, ev_cid, call_uop_b,
                np.ascontiguousarray(legal, np.uint8).tobytes(),
                np.ascontiguousarray(next_state, np.uint32).tobytes(),
                int(Sn), 0,
                int(max_configs),
                float(time_limit * 1000) if time_limit else 0.0)

    if code == 4:                    # > 64 pending: Python fallback
        return wgl_cpu.check(model, history, max_configs=max_configs,
                             time_limit=time_limit)
    if code == 3:
        return {"valid?": "unknown", "cause": "timeout",
                "op_count": n_calls, "events_done": events_done,
                "events_total": n_events}
    if code == 2:
        return {"valid?": "unknown", "cause": "config-explosion",
                "op_count": n_calls, "configs": n_seen,
                "events_done": events_done,
                "events_total": n_events}

    if code == 0 and calls is None:
        # call records only needed for rendering (witness op, config
        # decode) — built lazily on the rare invalid verdict; call ids
        # align with the columnar ingest (both number ok+crashed
        # invokes densely in stream order, fail pairs dropped).
        prep = prepare(history)
        calls = prep.calls

    def decode_configs():
        out = []
        sv = np.frombuffer(surv or b"", np.uint64).reshape(-1, 2)
        pc = np.frombuffer(pend, np.int32)
        for mask, st in sv[:10]:
            lin = []
            for b in range(64):
                if (int(mask) >> b) & 1 and pc[b] >= 0:
                    if calls is not None:
                        idx = calls[pc[b]].op.index
                        if idx is not None:
                            lin.append(idx)
                    else:            # valid fast path: raw call ids
                        lin.append(int(pc[b]))
            m = (spec.decode(states[int(st)])
                 if getattr(spec, "decode", None) else
                 {"state": states[int(st)].tolist()})
            out.append({"model": m, "pending-linearized": sorted(lin)})
        return out

    if code == 0:
        call = calls[fail_cid]
        return {"valid?": False,
                "op": call.op.to_dict(),
                "op_index": call.op.index,
                "op_count": n_calls,
                "anomaly": "nonlinearizable",
                "configs": decode_configs(),
                "engine": "wgl_cpu_native"}
    return {"valid?": True, "op_count": n_calls,
            "configs": decode_configs(),
            "engine": "wgl_cpu_native"}


def _spec_fmap(packed, spec):
    """Per-op spec f-codes from the packed history's f-id column."""
    nf = len(packed.f_codes)
    fcol = packed.f
    if nf == 0:
        return np.full(len(fcol), -1, np.int32)
    f2spec = np.full(nf, -1, np.int32)
    for tag, hid in packed.f_codes.items():
        code = spec.f_codes.get(tag)
        if code is not None:
            f2spec[hid] = code
    return np.where((fcol >= 0) & (fcol < nf),
                    f2spec[np.clip(fcol, 0, nf - 1)],
                    np.int32(-1)).astype(np.int32, copy=False)
