"""One engine planner: the (R, crashes, Sn, batch, mesh, backend) ->
engine-chain decision, made in ONE place (ROADMAP #1).

Before this module the routing across the eight `ops/` engines was
scattered through `wgl_seg.check/_check_fast/check_pipeline/check_many`
private `if` ladders, `wgl_deep.supported`, `checker/elle.py`'s tier
chain and `live/engine.py`'s bucket keys.  Every entry point now asks
`plan_engines(shape) -> Plan` and follows the plan; the Plan object is
*inspectable* (`plan.engine`, `plan.fallbacks`, `plan.why`,
`plan.bucket`, `plan.rejected`) and is rendered verbatim into the
dispatch record every verdict carries (`telemetry.attach_dispatch`), so
`results.json` explains its own routing instead of requiring the reader
to re-derive eight modules' worth of gating.

Two design rules, property-tested in tests/test_planner.py:

  * **Purity** — `plan_engines` is a pure function of (shape, env,
    backend): routing follows the history's shape and the backend,
    never a tuning variable.  The env is an explicit dict (default: a
    snapshot of the `JEPSEN_TPU_*` process environment) with one
    routing input, `JEPSEN_TPU_DEEP_INTERPRET`: a backend capability
    that widens what the 'cpu' backend can run (the Pallas
    interpreter) exactly as running on a TPU would.  The plan also
    records `JEPSEN_TPU_PACK_THREADS`, the host ingest pool's size.
  * **One terminating chain** — every shape routes to exactly one
    ordered chain whose last engine is total (`wgl_cpu`, `elle-host`,
    `live-host`): no shape can fall off the end of the ladder.

On top of the routing decision sit the two perf layers it unlocks
(ISSUE 8):

  * a **persistent compiled-plan cache** — `ensure_persistent_cache()`
    points the JAX compilation cache at `JAX_COMPILATION_CACHE_DIR` or
    the checkout's `.cache/jax/` so a fresh
    process (CLI one-shots, suite binaries, `serve-checker` restarts)
    reuses the previous process's XLA executables instead of paying the
    multi-second cold compile, and `compiled()` is the in-process
    shape-bucketed executable registry keyed
    (engine, bucket, jax version, backend) with hit/miss counters
    (`cache_stats()`, mirrored into the telemetry registry and the
    tier-1 CI artifact);
  * the **async double-buffered executor** (`ops.runner.overlap`)
    consumes `plan.bucket` to keep one compiled executable hot while
    host packing of the next chunk overlaps device compute of the
    current one.

The second half of this module is the host-side planning section
extracted from `wgl_seg.py` (ISSUE 8 satellite): history scanning
(`_fast_scan` and its C twins), quiescent-cut segmentation
(`_segment_ends`), slot assignment, state enumeration, and the
transition-relation decomposition — the pure host analysis every
engine's routing decision feeds on.  `wgl_seg` re-exports every moved
name, so call sites and the differential batteries are unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Optional

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu.errors import CheckError
from jepsen_tpu.history import (INFO, INVOKE, P_OUT_OF_RANGE, TYPE_CODE,
                                History)
from jepsen_tpu.models import DeviceSpec
from jepsen_tpu.ops.prep import PreparedHistory


class Unsupported(CheckError):
    """This history/model cannot use the segment-parallel engine; use
    ops.wgl (device serial) or ops.wgl_cpu instead.  Part of the
    jepsen_tpu.errors taxonomy (still a ValueError via CheckError);
    errors.classify maps it to BackendUnavailable when a whole batch
    falls out of device scope.  (Home module: the planner — `wgl_seg`
    re-exports it for its long-standing callers.)"""


# ---------------------------------------------------------------------------
# Plan objects
# ---------------------------------------------------------------------------

#: Engines that can check ANY in-scope input for their family — every
#: chain the planner emits ends with one of these.
TERMINAL_ENGINES = frozenset({"wgl_cpu", "elle-host", "live-host",
                              "lattice-host"})

@dataclasses.dataclass(frozen=True)
class Shape:
    """The routing-relevant shape of one check request — everything the
    engine decision is a function of (plus env/backend, passed to
    plan_engines separately so plans stay pure and reproducible).

    Fields with None mean "not known yet" (e.g. Sn before state
    enumeration); the planner is optimistic about unknowns — the engine
    itself raises `Unsupported` and the chain's next tier takes over.
    """

    kind: str = "linear"            # linear | linear-many |
    #                                 linear-pipeline | deep-pipeline |
    #                                 deep-mesh | batch-many | elle | live
    R: int = 0                      # max simultaneously-open calls
    crashes: int = 0                # crashed (:info) call count
    Sn: Optional[int] = None        # enumerated model states
    U: Optional[int] = None         # distinct encoded ops
    decomposed: Optional[bool] = None
    batch: int = 1                  # histories in this request
    n_ops: int = 0                  # ops (linear) / txns (elle)
    mesh: Optional[int] = None      # device count when mesh-sharded
    device: bool = True             # model has a DeviceSpec at all
    max_states: int = 64            # seg engine state-space budget
    max_open_bits: int = 10         # seg engine concurrency budget


@dataclasses.dataclass(frozen=True)
class Plan:
    """An inspectable engine plan: the chosen engine, the ordered
    fallbacks below it, why the head was chosen, the compiled-shape
    bucket its executable is cached under, and the full audit trail
    (engines rejected by shape gates)."""

    engine: str
    fallbacks: tuple = ()
    why: str = ""
    bucket: tuple = ()
    rejected: tuple = ()            # ((engine, reason), ...)
    shape: Optional[Shape] = None
    # Host-ingest routing (ISSUE 9): which pack backend the plan's
    # host side rides (native parallel ingest vs the pure-Python
    # packers) and at how many threads.  NOT part of the compiled
    # bucket — both backends emit bit-identical buffers, so the
    # executable cache is backend-agnostic.
    pack_backend: str = "python"
    pack_threads: int = 0
    # Deep-envelope provenance (ISSUE 10): which mask-plane variant the
    # head engine runs ("plane" | "word-split" | "hypercube" |
    # "replicated"), over how many shards (stacked sub-planes on one
    # device, or mesh devices), and how many pairwise hypercube
    # exchanges ONE closure round costs (= the high mask bits living on
    # the device axis; 0 for device-resident planes).
    deep_variant: str = ""
    shards: int = 0
    exchange_rounds: int = 0

    @property
    def chain(self) -> tuple:
        return (self.engine,) + tuple(self.fallbacks)

    def refine(self, **kw) -> "Plan":
        """A copy with entry-point-known fields filled in (typically
        the exact padded bucket once packing has run)."""
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        d = {"engine": self.engine, "fallbacks": list(self.fallbacks),
             "why": self.why, "bucket": list(self.bucket),
             "pack_backend": self.pack_backend,
             "pack_threads": self.pack_threads}
        if self.deep_variant:
            d["deep_variant"] = self.deep_variant
            d["shards"] = self.shards
            d["exchange_rounds"] = self.exchange_rounds
        if self.rejected:
            d["rejected"] = [list(r) for r in self.rejected]
        return d

    def record(self, engine: Optional[str] = None, **extra) -> dict:
        """The telemetry dispatch record rendering this plan VERBATIM:
        why and fallback_chain come from the plan, and the full plan
        dict rides under the `plan` key.  `engine` overrides the
        record's engine name for verdicts a lower tier produced (the
        plan itself still names its head)."""
        from jepsen_tpu import telemetry
        return telemetry.dispatch_record(
            engine if engine is not None else self.engine,
            why=self.why, fallback_chain=list(self.fallbacks),
            plan=self.to_dict(), **extra)


def _snapshot_env(env: Optional[dict]) -> dict:
    if env is not None:
        return env
    return {k: v for k, v in os.environ.items()
            if k.startswith("JEPSEN_TPU_")}


def _default_backend(backend: Optional[str]) -> str:
    if backend is not None:
        return backend
    import jax
    return jax.default_backend()


# ---------------------------------------------------------------------------
# Capability gates (pure; shared with the engines themselves)
# ---------------------------------------------------------------------------

def _regs_eligible(R: int, U: int, Sn: int, decomposed: bool,
                   r_cap: int = 6, sn_cap: int = 32) -> bool:
    """One gate for the register-delta kernel, shared by check(),
    check_many() and the relaxed tier so they cannot silently diverge:
    fixed rounds stay exact and compile small only for R <= r_cap, the
    uop index must fit int16, and the transition form must fit the
    decomposed (Sn <= sn_cap) or nibble (Sn <= 8) tables.  (The
    crashed-call path passes r_cap=8: its extra permanent slots are
    worth a bigger compile; the wide-state relaxed tier passes
    sn_cap=64 — its aux masks ride as sn_words=2 uint32 words.)"""
    return (R <= r_cap and U <= 32767
            and ((decomposed and Sn <= sn_cap)
                 or (not decomposed and Sn <= 8)))


#: wgl_deep's scope constants, owned here so the planner and the kernel
#: module cannot drift (wgl_deep re-exports them).  DEEP_R_BASE is the
#: overlap depth ONE resident [Sn, 2^R/32] uint32 plane covers — the
#: hard `DEEP_R_MAX = 14` cap it replaces (ISSUE 10); past it the mask
#: axis is partitioned instead of refused, so the routing boundary is
#: the function `deep_r_max(backend, n_devices)` below, not a constant.
DEEP_R_BASE = 14
#: Sub-planes the single-device word-split path may stack (2 buys
#: R = 15, 4 buys R = 16): each sub-plane stays one base-sized
#: [Sn, 512]-word tile, so per-op VPU appetite is unchanged and only
#: the stack (and the event walk's per-bit work) grows.
DEEP_SPLIT_MAX = 4
DEEP_SN_MAX = 32


def deep_split_planes(R: int) -> int:
    """Sub-plane count the word-split deep kernel stacks at overlap
    depth R (1 = the classic single resident plane)."""
    return 1 << max(0, int(R) - DEEP_R_BASE)


def deep_r_max(backend: Optional[str] = None,
               n_devices: Optional[int] = None) -> int:
    """THE deep-overlap boundary, replacing the hard DEEP_R_MAX = 14:

      * one device covers DEEP_R_BASE with a single resident plane and
        + log2(DEEP_SPLIT_MAX) more by word-splitting the plane into a
        stack of base-sized sub-planes (R = 15/16);
      * an n-device mesh covers DEEP_R_BASE + log2(n_devices) by
        mapping the top mask bits onto the device axis (the hypercube
        shard — R = 17 on 8 devices), whichever is larger.

    `backend` is part of the signature so per-backend envelopes can
    diverge without another call-site sweep; today the tpu kernel and
    its cpu interpreter share one boundary (whether the backend can run
    the deep engine AT ALL stays `deep_supported`'s concern)."""
    del backend
    r = DEEP_R_BASE + (DEEP_SPLIT_MAX.bit_length() - 1)
    if n_devices and int(n_devices) > 1:
        r = max(r, DEEP_R_BASE + (int(n_devices).bit_length() - 1))
    return r


def deep_supported(R: int, Sn: int, U: int, decomposed: bool,
                   backend: str, env: Optional[dict] = None,
                   n_devices: Optional[int] = None) -> bool:
    """Gate shared with the wgl_seg dispatcher: the deep kernel takes
    decomposable models with Sn <= 32 on TPU at any
    R <= deep_r_max(backend, n_devices) — the single-device word-split
    envelope by default, the hypercube-mesh envelope when `n_devices`
    names the mesh a caller can shard over.  It is *profitable* past
    the register-delta gate (R > 6); eligibility below that is still
    correct and used by the differential tests.

    The 'cpu' backend runs the Pallas INTERPRETER — a per-event Python
    loop, orders of magnitude slower than the compiled candidate-table
    fallback on long histories — so it is opt-in via
    JEPSEN_TPU_DEEP_INTERPRET=1 (set by the test suite, which runs
    deliberately tiny histories on the virtual CPU mesh); production
    CPU deployments keep the existing compiled fallback chain.  That
    variable is a backend-capability input (see module docstring)."""
    env = _snapshot_env(env)
    return (decomposed and 0 < R <= deep_r_max(backend, n_devices)
            and Sn <= DEEP_SN_MAX
            and U <= 32767
            and (backend == "tpu"
                 or (backend == "cpu"
                     and env.get("JEPSEN_TPU_DEEP_INTERPRET") == "1")))


# ---------------------------------------------------------------------------
# plan_engines — the one routing decision
# ---------------------------------------------------------------------------

def _linear_candidates(s: Shape, env: dict, backend: str):
    """Eligible engine ladder for ONE linearizability history, in
    priority order, with per-engine admission/rejection reasons."""
    cands: list = []
    rejected: list = []
    why: dict = {}
    nc = int(s.crashes)
    # unknowns are optimistic: the engine itself raises Unsupported
    # and the next tier takes over (the chain makes that safe)
    Sn = s.Sn if s.Sn is not None else 1
    U = s.U if s.U is not None else 1
    decomposed = s.decomposed if s.decomposed is not None else True
    r_cap = 8 if nc else 6
    R_eff = s.R + nc                # crashed calls hold permanent slots

    if nc > _MAX_CRASHED:
        rejected.append(("wgl_seg_regs",
                         f"{nc} crashed calls exceed the fast-scan cap "
                         f"({_MAX_CRASHED}); stripped/serial tiers own "
                         "this regime"))
    elif not _regs_eligible(R_eff, U, Sn, decomposed, r_cap=r_cap):
        rejected.append((
            "wgl_seg_regs",
            f"R={R_eff} (incl. {nc} crashed) / Sn={Sn} / U={U} outside "
            f"the register-delta gate (R<={r_cap}, decomposed Sn<=32)"))
    elif (Sn << nc) > 128:
        rejected.append(("wgl_seg_regs",
                         f"crash entry axis Sn*2^nc={Sn << nc} > 128"))
    else:
        cands.append("wgl_seg_regs")
        why["wgl_seg_regs"] = (
            f"R={R_eff} Sn={Sn}: register-delta segment kernel "
            "(quiescent cuts, device-maintained open set)")

    dmax1 = deep_r_max(backend, 1)
    if deep_supported(R_eff, Sn, U, decomposed, backend, env=env):
        dname = "wgl_deep" if R_eff <= DEEP_R_BASE else "wgl_deep_split"
        cands.append(dname)
        if dname == "wgl_deep":
            why[dname] = (
                f"R={R_eff} <= {DEEP_R_BASE}, Sn={Sn} <= {DEEP_SN_MAX} "
                "decomposed: deep-overlap Pallas megakernel"
                + (f" ({nc} crashed calls as permanent slots)"
                   if nc else ""))
        else:
            why[dname] = (
                f"R={R_eff} <= {dmax1}, Sn={Sn} <= {DEEP_SN_MAX} "
                "decomposed: word-split deep kernel "
                f"({deep_split_planes(R_eff)} stacked sub-planes)"
                + (f" ({nc} crashed calls as permanent slots)"
                   if nc else ""))
    else:
        rejected.append(("wgl_deep",
                         f"R={R_eff}/Sn={Sn}/backend={backend} outside "
                         "the deep megakernel gate"))
    # beyond one device's stack but within the mesh envelope: the
    # hypercube mask shard (top mask bits -> device index)
    if (s.mesh or 0) > 1 and R_eff > dmax1 and deep_supported(
            R_eff, Sn, U, decomposed, backend, env=env,
            n_devices=s.mesh):
        cands.append("wgl_deep_hc")
        why["wgl_deep_hc"] = (
            f"R={R_eff} <= {deep_r_max(backend, s.mesh)} on "
            f"the {s.mesh}-device hypercube shard (top mask bits -> "
            "device index, one ppermute per high slot per event round)")

    if nc == 0 and s.R <= s.max_open_bits and Sn <= s.max_states:
        cands.append("wgl_seg")
        why["wgl_seg"] = (f"R={s.R} <= {s.max_open_bits}, "
                          f"Sn={Sn} <= {s.max_states}: candidate-table "
                          "segment engine")
    elif 0 < nc <= _MAX_CRASHED and s.R <= s.max_open_bits \
            and Sn <= s.max_states:
        cands.append("wgl_seg")
        why["wgl_seg"] = (f"{nc} crashed calls within the bounded "
                          "crash tier (inert dropping / stripped "
                          "validity proof / crash kernel)")
    else:
        rejected.append(("wgl_seg",
                         f"R={s.R}/Sn={Sn}/crashes={nc} outside the "
                         "segment engine's scope"))

    if s.device:
        cands.append("wgl")
        why["wgl"] = "serial device frontier kernel (no depth limit)"
    else:
        rejected.append(("wgl", "model has no device spec"))
    cands.append("wgl_cpu")
    why["wgl_cpu"] = "exact CPU oracle (total)"
    return cands, rejected, why


def _many_candidates(s: Shape):
    """Eligible ladder for check_many's independent-key batch."""
    cands: list = []
    rejected: list = []
    why: dict = {}
    Sn = s.Sn if s.Sn is not None else 1
    U = s.U if s.U is not None else 1
    decomposed = s.decomposed if s.decomposed is not None else True
    if _regs_eligible(s.R, U, Sn, decomposed):
        cands.append("wgl_seg_batch_regs")
        why["wgl_seg_batch_regs"] = (
            f"R={s.R} Sn={Sn}: one register-delta lane per key, "
            "compact wire" + (f", sharded over {s.mesh} devices"
                              if s.mesh else ""))
    else:
        rejected.append(("wgl_seg_batch_regs",
                         f"R={s.R}/Sn={Sn}/U={U} outside the "
                         "register-delta gate"))
    if s.R <= s.max_open_bits and Sn <= s.max_states:
        cands.append("wgl_seg_batch")
        why["wgl_seg_batch"] = "candidate-table batch lanes"
    else:
        rejected.append(("wgl_seg_batch",
                         f"R={s.R}/Sn={Sn} outside the batch engine's "
                         "scope"))
    # per-key contraction: anything the batch lanes can't take rides
    # the single-history chain, then the serial engines
    cands.extend(["wgl_seg", "wgl", "wgl_cpu"])
    why["wgl_seg"] = "per-key single-history chain (crash tiers)"
    why["wgl"] = "serial device frontier kernel"
    why["wgl_cpu"] = "exact CPU oracle (total)"
    return cands, rejected, why


def plan_engines(shape: Shape, env: Optional[dict] = None,
                 backend: Optional[str] = None) -> Plan:
    """THE routing decision: shape -> one terminating engine chain.

    Pure in (shape, env, backend): env defaults to a snapshot of the
    process `JEPSEN_TPU_*` environment (read for the backend
    capability `JEPSEN_TPU_DEEP_INTERPRET` and the plan's pack
    threads), backend to `jax.default_backend()`.  Every entry point
    follows the returned plan instead of a private `if` ladder;
    `plan.record()` renders it verbatim into the dispatch record on
    every verdict."""
    env = _snapshot_env(env)
    backend = _default_backend(backend)
    s = shape

    if s.kind == "linear":
        cands, rejected, why = _linear_candidates(s, env, backend)
    elif s.kind == "linear-many":
        cands, rejected, why = _many_candidates(s)
    elif s.kind == "linear-pipeline":
        # the pipeline's stragglers go through the SINGLE-history
        # chain (check() per straggler), so that chain is what sits
        # below the grouped head — not the batch layouts
        cands, rejected, why = _linear_candidates(s, env, backend)
        cands = ["wgl_seg_pipeline"] + \
            [c for c in cands if c != "wgl_seg_regs"]
        why["wgl_seg_pipeline"] = (
            "grouped register-delta pipeline (async dispatch, "
            "one fetch)")
    elif s.kind == "deep-pipeline":
        cands, rejected, why = [], [], {}
        Sn = s.Sn if s.Sn is not None else 1
        U = s.U if s.U is not None else 1
        dec = s.decomposed if s.decomposed is not None else True
        if deep_supported(max(s.R, 1), Sn, U, dec, backend, env=env):
            cands.append("wgl_deep_pipeline")
            why["wgl_deep_pipeline"] = (
                "pipelined deep megakernel (async dispatch, one fetch)"
                + (f"; word-split x{deep_split_planes(s.R)} past "
                   f"R={DEEP_R_BASE}" if s.R > DEEP_R_BASE else ""))
        else:
            rejected.append(("wgl_deep_pipeline",
                             f"R={s.R}/Sn={Sn}/backend={backend} "
                             "outside the deep gate"))
        if (s.mesh or 0) > 1 and deep_supported(
                max(s.R, 1), Sn, U, dec, backend, env=env,
                n_devices=s.mesh):
            # the pipeline's deep stragglers (R past one device's
            # stack) ride the hypercube shard before the serial chain
            cands.append("wgl_deep_hc")
            why.setdefault("wgl_deep_hc", (
                f"hypercube straggler tier over {s.mesh} devices"))
        cands.extend(["wgl_seg", "wgl", "wgl_cpu"])
        why.setdefault("wgl_seg", "per-straggler single-history chain")
        why.setdefault("wgl", "serial device frontier kernel")
        why.setdefault("wgl_cpu", "exact CPU oracle (total)")
    elif s.kind == "deep-mesh":
        rejected = []
        if s.R > deep_r_max(backend, 1):
            cands = ["wgl_deep_hc", "wgl_seg", "wgl", "wgl_cpu"]
            why = {"wgl_deep_hc": (
                f"R={s.R} beyond the single-device stack: mask-sharded "
                f"hypercube over {s.mesh or '?'} devices (top "
                f"{max((s.mesh or 2).bit_length() - 1, 1)} mask bits "
                "-> device index)")}
            rejected.append(("wgl_deep_mesh",
                             f"R={s.R} exceeds one device's plane "
                             "stack; replicated one-history-per-device "
                             "layout cannot hold it"))
        else:
            cands = ["wgl_deep_mesh", "wgl_deep_pipeline", "wgl_seg",
                     "wgl", "wgl_cpu"]
            why = {"wgl_deep_mesh": (
                f"one history per device over {s.mesh or '?'} devices, "
                "no collectives")}
    elif s.kind == "batch-many":
        cands = ["wgl_batch", "wgl", "wgl_cpu"]
        rejected = []
        why = {"wgl_batch": "vmap-over-keys frontier kernel"}
    elif s.kind == "elle":
        return plan_elle(n_max=s.n_ops, batch=s.batch, env=env,
                         devices=s.mesh)
    elif s.kind == "live":
        return plan_live(lanes=s.batch, events=s.n_ops,
                         bits=s.R, states=s.Sn or 1, env=env)
    elif s.kind == "lattice":
        return plan_lattice(n_max=s.n_ops, batch=s.batch, env=env,
                            devices=s.mesh)
    else:
        raise ValueError(f"unknown plan kind {shape.kind!r}")

    head = cands[0]
    bucket = _bucket_for(head, s)
    return Plan(engine=head, fallbacks=tuple(cands[1:]),
                why=why.get(head, "eligible"), bucket=bucket,
                rejected=tuple(rejected), shape=s,
                pack_backend=pack_backend_effective(env),
                pack_threads=pack_threads_effective(env),
                **_deep_extras(head, s))


def _deep_extras(engine: str, s: Shape) -> dict:
    """The deep-envelope provenance fields a plan carries when its head
    is a deep variant (deep_variant / shards / exchange_rounds)."""
    if not engine.startswith("wgl_deep"):
        return {}
    R = int(s.R + s.crashes)
    if engine == "wgl_deep_hc":
        d = max(int(s.mesh or 2), 2)
        return {"deep_variant": "hypercube", "shards": d,
                "exchange_rounds": d.bit_length() - 1}
    if engine == "wgl_deep_split" or (
            engine == "wgl_deep_pipeline" and R > DEEP_R_BASE):
        return {"deep_variant": "word-split",
                "shards": deep_split_planes(R)}
    if engine == "wgl_deep_mesh":
        return {"deep_variant": "replicated",
                "shards": int(s.mesh or 0)}
    return {"deep_variant": "plane", "shards": 1}


def _bucket_for(engine: str, s: Shape) -> tuple:
    """The compiled-shape bucket the head engine's executable is cached
    under — the components knowable at plan time; entry points refine
    with exact padded dims once packing has run (`Plan.refine`)."""
    if engine.startswith("wgl_seg") or engine.startswith("wgl_deep"):
        base = (engine, int(s.R + s.crashes), s.Sn, s.U,
                _next_pow2(max(s.batch, 1)))
        # the hypercube shard compiles per mesh size (the device axis
        # IS a kernel dimension there, unlike the replicated layouts)
        return base + (int(s.mesh),) if engine == "wgl_deep_hc" \
            else base
    if engine == "wgl_batch":
        return (engine, _next_pow2(max(s.batch, 1)))
    return (engine,)


# -- elle / live routing ----------------------------------------------------

# txns from which "auto" routes an Elle history to the bit-packed mesh
# tier (`checker.elle.Elle`; `elle_mesh.pad_for_mesh` pads by 1,024 from
# here on)
ELLE_MESH_THRESHOLD = 8192


def plan_elle(n_max: int, batch: int = 1, *, algorithm: str = "auto",
              mesh_threshold: int = ELLE_MESH_THRESHOLD,
              env: Optional[dict] = None,
              devices: Optional[int] = None) -> Plan:
    """Tier chain for the transactional isolation engine:
    elle-mesh -> elle-device -> elle-host, head picked by the strict
    `algorithm` or (auto) the txn count vs `mesh_threshold`.  The
    algorithm argument SELECTS within the base chain (caller intent,
    like a mesh argument)."""
    env = _snapshot_env(env)
    rejected: list = []
    if algorithm == "host":
        chain = ["elle-host"]
        why = "host oracle requested (algorithm='host')"
    elif algorithm == "mesh":
        chain = ["elle-mesh", "elle-host"]
        why = "strict mesh requested; host oracle is the only tier below"
    elif algorithm == "device":
        chain = ["elle-device", "elle-host"]
        why = "strict dense device engine requested"
    else:
        if n_max >= mesh_threshold:
            chain = ["elle-mesh", "elle-device", "elle-host"]
            why = (f"n_max={n_max} >= mesh_threshold={mesh_threshold}: "
                   "bit-packed row-sharded closure"
                   + (f" over {devices} devices" if devices else ""))
        else:
            chain = ["elle-device", "elle-host"]
            why = (f"n_max={n_max} < mesh_threshold={mesh_threshold}: "
                   "dense vmap closure on one device")
            rejected.append(("elle-mesh",
                             f"n_max={n_max} below mesh_threshold"))
    bucket = ("elle", chain[0], _next_pow2(max(n_max, 1)),
              _next_pow2(max(batch, 1)))
    return Plan(engine=chain[0], fallbacks=tuple(chain[1:]), why=why,
                bucket=bucket, rejected=tuple(rejected),
                pack_backend=pack_backend_effective(env),
                pack_threads=pack_threads_effective(env))


def plan_lattice(n_max: int, batch: int = 1, *,
                 algorithm: str = "auto",
                 mesh_threshold: int = 4096,
                 env: Optional[dict] = None,
                 devices: Optional[int] = None) -> Plan:
    """Tier chain for the full-lattice consistency engine (ISSUE 20):
    lattice-mesh -> lattice-device -> lattice-host.  Same selection
    contract as `plan_elle` — `algorithm` is caller intent — but the
    lattice closes seven coupled relations per round
    (Adya pair closure, session pair closure, predicate closure,
    long-fork automaton), so the mesh threshold sits lower: the dense
    8-plane stack outgrows one device sooner than the 5-plane one."""
    env = _snapshot_env(env)
    rejected: list = []
    if algorithm == "host":
        chain = ["lattice-host"]
        why = "host oracle requested (algorithm='host')"
    elif algorithm == "mesh":
        chain = ["lattice-mesh", "lattice-host"]
        why = "strict packed mesh requested; host oracle below"
    elif algorithm == "device":
        chain = ["lattice-device", "lattice-host"]
        why = "strict dense device engine requested"
    else:
        if n_max >= mesh_threshold:
            chain = ["lattice-mesh", "lattice-device", "lattice-host"]
            why = (f"n_max={n_max} >= mesh_threshold={mesh_threshold}: "
                   "bit-packed row-sharded lattice closure"
                   + (f" over {devices} devices" if devices else ""))
        else:
            chain = ["lattice-device", "lattice-host"]
            why = (f"n_max={n_max} < mesh_threshold={mesh_threshold}: "
                   "dense lattice closure on one device")
            rejected.append(("lattice-mesh",
                             f"n_max={n_max} below mesh_threshold"))
    bucket = ("lattice", chain[0], _next_pow2(max(n_max, 1)),
              _next_pow2(max(batch, 1)))
    return Plan(engine=chain[0], fallbacks=tuple(chain[1:]), why=why,
                bucket=bucket, rejected=tuple(rejected),
                pack_backend=pack_backend_effective(env),
                pack_threads=pack_threads_effective(env))


def plan_live(lanes: int, events: int, bits: int, states: int,
              env: Optional[dict] = None) -> Plan:
    """Shape-bucketed plan for one live micro-batch dispatch: the
    bucket IS the compiled-plan cache key the warmed service reuses
    (lanes/events/states pow2-padded exactly as live/engine buckets
    them)."""
    del env
    M = 1 << int(bits)
    bucket = ("live", _next_pow2(max(lanes, 1)),
              max(64, _next_pow2(max(events, 1))), M,
              max(8, _next_pow2(max(states, 1))))
    return Plan(engine="live-jit", fallbacks=("live-host",),
                why=(f"bucketed window scan (T={bucket[1]} "
                     f"E={bucket[2]} M={M} Sn={bucket[4]})"),
                bucket=bucket)


def plan_live_txn(n_pad: int, devices: int = 1,
                  backend: str = "host",
                  env: Optional[dict] = None) -> Plan:
    """Shape-bucketed plan for one live TRANSACTIONAL tenant's warm
    closure update (ISSUE 18): the incremental delta kernel
    (elle_mesh's warm-seeded pair closure) compiled per padded plane
    size, with the numpy warm twin as the unconditional fallback.  The
    bucket keys the compiled-plan cache AND the static trace audit
    (lint/trace_audit registers the `elle-delta` builder)."""
    del env
    n_pad = max(int(n_pad), 1)
    devices = max(int(devices), 1)
    if backend == "device":
        chain = ["elle-delta", "elle-delta-host"]
        why = (f"warm-seeded mesh closure over {devices} devices "
               f"(n_pad={n_pad})")
    else:
        chain = ["elle-delta-host"]
        why = f"numpy warm closure twin (n_pad={n_pad})"
    bucket = ("elle-delta", n_pad, devices)
    return Plan(engine=chain[0], fallbacks=tuple(chain[1:]), why=why,
                bucket=bucket)


def runner_plan(engine_name: str, fallback_name: str = "wgl_cpu",
                why: str = "resilient-runner degradation") -> Plan:
    """The ResilientRunner's own plan for verdicts IT produced
    (quarantines, deadline/backend degradations): the configured engine
    with the runner's per-history fallback below it."""
    return Plan(engine=engine_name, fallbacks=(fallback_name,),
                why=why, bucket=("runner", engine_name))


# ---------------------------------------------------------------------------
# Traceable-callable hook (ISSUE 15): plan -> (fn, example_args, meta)
# ---------------------------------------------------------------------------
#
# The static jaxpr auditor (lint/trace_audit.py) needs to see the
# ClosedJaxpr of every engine a plan can emit WITHOUT running anything:
# engine modules (or the auditor) register a builder per engine name
# that reconstructs the engine's jitted callable and example
# ShapeDtypeStructs from the plan BUCKET alone.  Deriving the trace
# signature from the bucket — and nothing else — is itself one of the
# audited invariants: if two sweeps of one bucket trace different
# shapes, the executable cache under-keys and a recompile storm ships
# as a bench regression instead of a lint failure.

_TRACEABLES: dict = {}


def register_traceable(engine: str, builder) -> None:
    """Register `builder(plan, devices=...) -> (fn, example_args,
    meta) | None` for an engine name.  `fn` must be traceable by
    jax.make_jaxpr over `example_args` (ShapeDtypeStructs); returning
    None means "this bucket is not traceable here" (e.g. a mesh wider
    than the host).  Last registration wins (tests may stub)."""
    _TRACEABLES[engine] = builder


def traceable(plan: Plan, **kw):
    """Resolve a plan's head engine to its registered traceable, or
    None when no builder is registered — the hook is additive, so an
    unregistered engine is unaudited, never an error."""
    b = _TRACEABLES.get(plan.engine)
    return None if b is None else b(plan, **kw)


def traceable_engines() -> list:
    return sorted(_TRACEABLES)


# ---------------------------------------------------------------------------
# Persistent compiled-plan cache
# ---------------------------------------------------------------------------

_COMPILED: dict = {}
_CACHE_LOCK = threading.Lock()
_CACHE_STATS = {"hit": 0, "miss": 0, "compile_s": 0.0}
_PERSISTENT = {"dir": None}


def cache_stats() -> dict:
    """In-process compiled-plan cache counters + the persistent cache
    dir in effect (None = process-local only).  Recorded per tier-1 run
    in store/ci/last-tier1.json so cache regressions diff across
    PRs."""
    with _CACHE_LOCK:
        out = dict(_CACHE_STATS)
    out["persistent_dir"] = _PERSISTENT["dir"]
    return out


def reset_cache_stats() -> None:
    with _CACHE_LOCK:
        _CACHE_STATS["hit"] = _CACHE_STATS["miss"] = 0
        _CACHE_STATS["compile_s"] = 0.0


# The checkout's own compile cache: fixed, so a later process finds what
# an earlier one compiled (the path is part of the cache's key).
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")


def ensure_persistent_cache() -> Optional[str]:
    """Persist compiled programs across processes, by one rule: where
    `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no path
    is set here; otherwise the cache is the fixed `CACHE_DIR`
    (`<checkout>/.cache/jax`).  Either way the size and time thresholds
    are dropped so the many small per-bucket kernels persist too.  A
    fresh process then skips the XLA compile of every shape-bucketed
    executable an earlier one built.  Idempotent; returns the directory
    in effect, or None when the checkout cannot hold one."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        try:
            os.makedirs(CACHE_DIR, exist_ok=True)
        except OSError:         # a read-only checkout: no cache
            return None
        where = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _PERSISTENT["dir"] = where
    return where


def compiled(engine: str, bucket: tuple, builder, *builder_args,
             lower_args: Optional[tuple] = None,
             info: Optional[dict] = None, **builder_kw):
    """The in-process compiled-executable registry: one executable per
    (engine, bucket, jax version, backend) key, built by
    `builder(*builder_args)` on miss and — when `lower_args`
    (ShapeDtypeStructs or example arrays) are given — AOT-compiled via
    `jit(...).lower(...).compile()` so the compile happens HERE, timed
    and charged to `cache_stats()['compile_s']`, not silently inside
    the first dispatch.  AOT-compiled executables also land in the
    persistent JAX compilation cache (ensure_persistent_cache), so the
    next process's miss pays deserialization, not XLA.

    Hit/miss counters feed the telemetry registry
    (`jepsen_plan_cache_total{outcome=...}`) and the tier-1 CI
    artifact."""
    try:
        import jax
        ver = jax.__version__
        backend = jax.default_backend()
    except Exception:           # noqa: BLE001
        ver = backend = "unknown"
    key = (engine, tuple(bucket), ver, backend)
    with _CACHE_LOCK:
        fn = _COMPILED.get(key)
    hit = fn is not None
    if not hit:
        t0 = time.monotonic()
        fn = builder(*builder_args, **builder_kw)
        if lower_args is not None:
            try:
                fn = fn.lower(*lower_args).compile()
            except Exception:   # noqa: BLE001 - AOT is an optimization;
                pass            # the jitted fn compiles on first call
        dt = time.monotonic() - t0
        with _CACHE_LOCK:
            _COMPILED[key] = fn
            _CACHE_STATS["compile_s"] += dt
    with _CACHE_LOCK:
        _CACHE_STATS["hit" if hit else "miss"] += 1
    if info is not None:
        info["hit"] = hit
    try:
        from jepsen_tpu import telemetry
        telemetry.REGISTRY.counter(
            "jepsen_plan_cache_total",
            outcome="hit" if hit else "miss").inc()
    except Exception:           # noqa: BLE001
        pass
    return fn


def clear_compiled() -> None:
    """Drop every in-process executable (tests; the persistent on-disk
    cache is untouched)."""
    with _CACHE_LOCK:
        _COMPILED.clear()


# ---------------------------------------------------------------------------
# Host-side planning (extracted from wgl_seg.py — ISSUE 8 satellite).
# Everything below is the pure host analysis the routing decision feeds
# on: history scanning, quiescent-cut segmentation, slot assignment,
# state enumeration, and the transition-relation decomposition.
# wgl_seg re-exports every name for its long-standing callers.
# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# Host-side planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SegPlan:
    """K segments, each a padded table of return events.  L return
    events per segment, C candidate slots per event, R mask bits,
    Sn states, U distinct ops."""

    ret_slot: np.ndarray    # int32 [K, L]      (-1 = padding)
    cand_slot: np.ndarray   # int32 [K, L, C]
    cand_uop: np.ndarray    # int32 [K, L, C]   (-1 = none)
    legal: np.ndarray       # bool  [U, Sn]
    next_state: np.ndarray  # int32 [U, Sn]
    states: np.ndarray      # int32 [Sn, S] enumerated state table
    seg_end_call: np.ndarray  # int32 [K] call id of last return per segment
    n_calls: int
    max_open: int
    # Diagonal + rank-1 decomposition of the transition relation (set
    # when every distinct op either keeps the state or sends all states
    # to ONE target state — true for the whole register family, cas and
    # mutex): next = diag_w·identity + const_w·(-> t0).  Lets the kernel
    # replace the Sn² one-hot contraction with 3 elementwise passes.
    diag_w: Optional[np.ndarray] = None    # f32 [U, Sn]
    const_w: Optional[np.ndarray] = None   # f32 [U, Sn]
    const_t0: Optional[np.ndarray] = None  # int32 [U]
    # Per-segment flat snapshot arrays (the _fk_arrays form) for the
    # register-delta kernel path; one _FastKey per segment.
    seg_fk: Optional[list] = None


def _encode_calls(calls, spec: DeviceSpec, seen: Optional[dict] = None,
                  rows: Optional[list] = None):
    """Encode each call's op as (f, a, b, ok) and dedupe to U distinct
    rows.  Returns (uops int32[U, 4], call->uop int32[n]).  Pass shared
    `seen`/`rows` to intern across several histories (multi-key batch)."""
    from jepsen_tpu.ops.wgl import _generic_encode_op

    encode_op = getattr(spec, "encode_op", None) or \
        (lambda op: _generic_encode_op(op, spec.f_codes))
    seen = {} if seen is None else seen
    call_uop = np.zeros(len(calls), np.int32)
    rows = [] if rows is None else rows
    # Stage new rows locally and merge only once the whole history
    # encodes: a key that raises Unsupported mid-walk must not leave its
    # ops in the shared tables, where they would grow the enumerated
    # state space for keys that never issue them.
    new_seen: dict = {}
    new_rows: list = []
    for c in calls:
        fc, av, bv, okv = encode_op(c.op)
        if fc < 0:
            raise Unsupported(f"model has no f-code for {c.op.f!r}")
        if not (-2 ** 31 <= av < 2 ** 31 and -2 ** 31 <= bv < 2 ** 31):
            raise Unsupported(
                f"op value {c.op.value!r} exceeds the int32 device range")
        key = (fc, av, bv, okv)
        u = seen.get(key)
        if u is None:
            u = new_seen.get(key)
        if u is None:
            u = new_seen[key] = len(rows) + len(new_rows)
            new_rows.append(key)
        call_uop[c.id] = u
    seen.update(new_seen)
    rows.extend(new_rows)
    return np.asarray(rows, np.int32).reshape(len(rows), 4), call_uop


@functools.lru_cache(maxsize=32)
def _expand_fn(step):
    """Jitted state-space expansion, cached per model step function —
    defining it inside _enumerate_states re-traced and re-compiled on
    EVERY check call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def expand(states, uops):
        # [n, S], [U, 4] -> ([U, n, S] states', [U, n] legal)
        def one(st):
            def per_op(u):
                st2, legal = step(st, u[0], u[1], u[2], u[3] != 0)
                return st2.astype(jnp.int32), legal
            return jax.vmap(per_op)(uops)
        st2, legal = jax.vmap(one)(states)  # [n, U, S], [n, U]
        return st2.transpose(1, 0, 2), legal.transpose(1, 0)

    return expand


def _enumerate_states(spec: DeviceSpec, init_state: np.ndarray,
                      uops: np.ndarray, max_states: int):
    """Close {init} under every distinct op's legal transition.  Returns
    (states int32[Sn, S], legal bool[U, Sn], next int32[U, Sn])."""
    import jax
    import jax.numpy as jnp

    step = spec.step
    U = uops.shape[0]

    # Pinned to CPU: the state space is tiny and the accelerator's
    # compile latency would dwarf the work.
    cpu = jax.devices("cpu")[0]
    base = _expand_fn(step)

    def expand(states):
        return base(states, uops)

    table: dict[bytes, int] = {}
    states: list[np.ndarray] = []

    def intern(row: np.ndarray) -> int:
        key = row.tobytes()
        idx = table.get(key)
        if idx is None:
            idx = table[key] = len(states)
            states.append(row)
        return idx

    intern(np.asarray(init_state, np.int32))
    frontier = 0
    while frontier < len(states):
        if len(states) > max_states:
            raise Unsupported(
                f"model state space exceeds max_states={max_states}")
        batch = np.stack(states[frontier:], 0)
        frontier = len(states)
        with jax.default_device(cpu):
            st2, legal = (np.asarray(x) for x in expand(batch))
        for u in range(U):
            for j in range(st2.shape[1]):
                if legal[u, j]:
                    intern(st2[u, j].astype(np.int32))

    state_arr = np.stack(states, 0).astype(np.int32)
    Sn = state_arr.shape[0]
    with jax.default_device(cpu):
        st2, legal = (np.asarray(x) for x in expand(state_arr))
    next_state = np.zeros((U, Sn), np.int32)
    for u in range(U):
        for s in range(Sn):
            if legal[u, s]:
                next_state[u, s] = table[st2[u, s].astype(np.int32).tobytes()]
    return state_arr, legal.astype(bool), next_state


def plan(prep: PreparedHistory, spec: DeviceSpec, model, *,
         max_states: int = 64, max_open_bits: int = 10,
         target_returns_per_segment: int = 256,
         pad_segments_pow2: bool = True) -> SegPlan:
    calls = prep.calls
    if any(c.is_crashed for c in calls):
        raise Unsupported("history has crashed (:info) calls")
    if prep.max_open > max_open_bits:
        raise Unsupported(
            f"max {prep.max_open} simultaneously-open calls exceeds "
            f"max_open_bits={max_open_bits}")

    uops, call_uop = _encode_calls(calls, spec)
    init = np.asarray(spec.encode(model), np.int32)
    states, legal, next_state = _enumerate_states(
        spec, init, uops, max_states)

    # Quiescent cuts: per-return flags (zero open calls after it) plus
    # the event position just past each return, for segment slicing.
    cut_flags = []
    ret_event_end = []
    open_count = 0
    for i, (_, kind, _) in enumerate(prep.events):
        open_count += 1 if kind == 0 else -1
        if kind == 1:
            cut_flags.append(1 if open_count == 0 else 0)
            ret_event_end.append(i + 1)
    if open_count != 0:
        raise Unsupported("history ends with open calls")  # unreachable:
        # crash-free histories always return every call (prep marks
        # unreturned invokes as crashed, caught above)

    seg_ret_ends = _segment_ends(cut_flags, target_returns_per_segment)
    seg_bounds = [0] + [ret_event_end[r - 1] for r in seg_ret_ends]
    if len(seg_bounds) < 2:
        seg_bounds = [0, len(prep.events)]

    segments = list(zip(seg_bounds[:-1], seg_bounds[1:]))
    K = len(segments)
    seg_tables = []
    L = C = 1
    for lo, hi in segments:
        rets, _, open_calls = _assign_slots(prep.events[lo:hi])
        assert not open_calls, "cut was not quiescent"
        seg_tables.append(rets)
        L = max(L, len(rets))
        C = max(C, max((len(cs) for _, _, cs in rets), default=1))

    if pad_segments_pow2:
        L = _pad_len(L)
        C = _next_pow2(C)

    diag_w, const_w, const_t0 = _decompose(legal, next_state)
    # seg_fk is only consumed by the register-delta kernel — skip the
    # extra per-candidate appends when that path cannot engage.
    want_fk = _regs_eligible(prep.max_open, uops.shape[0],
                             states.shape[0], diag_w is not None)

    ret_slot = np.full((K, L), -1, np.int32)
    cand_slot = np.zeros((K, L, C), np.int32)
    cand_uop = np.full((K, L, C), -1, np.int32)
    seg_end_call = np.zeros(K, np.int32)
    seg_fk = [] if want_fk else None
    for k, rets in enumerate(seg_tables):
        rs_f, cnt_f, cs_f, cu_f = [], [], [], []
        for r, (cid, slot, cands) in enumerate(rets):
            ret_slot[k, r] = slot
            if want_fk:
                rs_f.append(slot)
                cnt_f.append(len(cands))
            for j, (c2, s2) in enumerate(cands):
                cand_slot[k, r, j] = s2
                cand_uop[k, r, j] = call_uop[c2]
                if want_fk:
                    cs_f.append(s2)
                    cu_f.append(call_uop[c2])
        seg_end_call[k] = rets[-1][0] if rets else -1
        if want_fk:
            seg_fk.append(_FastKey(
                None, prep.max_open, len(rets),
                arrays=(np.asarray(rs_f, np.int32),
                        np.asarray(cnt_f, np.int32),
                        np.asarray(cs_f, np.int32),
                        np.asarray(cu_f, np.int32))))

    return SegPlan(ret_slot, cand_slot, cand_uop, legal, next_state,
                   states, seg_end_call, n_calls=len(calls),
                   max_open=prep.max_open,
                   diag_w=diag_w, const_w=const_w, const_t0=const_t0,
                   seg_fk=seg_fk)


def _next_pow2(x: int) -> int:
    b = 1
    while b < x:
        b *= 2
    return b


def _segment_ends(cut_flags: np.ndarray, target: int) -> list:
    """Greedy quiescent-cut segmentation over returns — the ONE
    segmentation policy (shared by plan() and the fast scan path):
    cut_flags[r] marks quiescence after return r; a segment closes at
    the first quiescent return >= `target` returns in, and the last cut
    always closes the tail.  Iterates once per SEGMENT (searchsorted
    over the cut positions), not once per cut — low-concurrency
    histories are quiescent at a large fraction of returns.  target
    clamps to >= 1 (0 used to mean cut-everywhere in the per-cut loop;
    the searchsorted form would re-find the consumed cut forever)."""
    target = max(int(target), 1)
    pos = np.nonzero(np.asarray(cut_flags))[0]
    if not len(pos):
        return []
    last = int(pos[-1])
    ends: list = []
    start = 0
    while True:
        j = np.searchsorted(pos, start + target - 1, side="left")
        if j >= len(pos):
            break
        c = int(pos[j])
        ends.append(c + 1)
        start = c + 1
    if not ends or ends[-1] != last + 1:
        ends.append(last + 1)
    return ends


def _pad_len(x: int) -> int:
    """Event-axis padding: pow2 below 64, 64-multiples above.  The scan
    runs this many serial steps for EVERY lane, so pow2 padding wasted
    up to 2x; 64-granularity keeps the compiled-shape set small without
    the waste."""
    return _next_pow2(x) if x <= 64 else ((x + 63) // 64) * 64


# Crashed-call tolerance of the fast single-history path: each crashed
# call doubles the entry-config axis (J = Sn * 2^nc), so cap it low —
# histories beyond the cap fall back to the serial/CPU engines.
_MAX_CRASHED = 4


class _FastKey:
    """One batchable key, produced by a single fused host pass:
    rets[r] = (slot, [(open_slot, open_uop), ...]) per return event —
    or, from the native scanner, the same data as flat int32 arrays
    (ret_slots, cand_counts, cand_slots, cand_uops).  `cuts[r]` marks
    returns after which the key is QUIESCENT (zero open NORMAL calls) —
    the segmentation points the batch engine parallelizes across.

    Crashed-tolerant scans additionally set `nc` (crashed-call count)
    and `rn` (first crashed slot = max normal open): crashed calls hold
    permanent slots rn..rn+nc-1 and appear in every snapshot from their
    invoke onward."""

    __slots__ = ("rets", "max_open", "n_calls", "arrays", "cuts",
                 "nc", "rn", "deltas", "positions")

    def __init__(self, rets, max_open, n_calls, arrays=None, cuts=None,
                 nc=0, rn=None, deltas=None, positions=None):
        self.rets = rets
        self.max_open = max_open
        self.n_calls = n_calls
        self.arrays = arrays
        self.cuts = cuts
        self.nc = nc
        self.rn = rn
        # From the columnar scanner: (d_counts[nr], d_slots[n_calls],
        # d_uops[n_calls]) — the calls invoked since the previous
        # return, attributed to each return in stream order.  Feeds
        # _pack_regs_single without re-deriving deltas from snapshots.
        self.deltas = deltas
        # int32[n_rets]: original op position of each return (from the
        # native scanners) — lets invalid verdicts slice out JUST the
        # dead segment's ops for witness localization.  None from the
        # pure-Python twin; localization then uses the prefix oracle.
        self.positions = positions

    @property
    def n_rets(self):
        return (len(self.arrays[0]) if self.arrays is not None
                else len(self.rets))


def _native_scan(ops: list, spec, seen: dict, rows: list,
                 max_open_bits: int):
    """The C twin of _fast_scan (native/histscan.c) — ~8x faster on
    the host; returns None for out-of-scope keys just like it."""
    from jepsen_tpu import native

    if getattr(spec, "encode_op", None) is not None:
        return None    # C scanner encodes via f_codes only; slow path
    mod = native.histscan()
    if mod is None:
        return False                 # extension unavailable
    out = mod.fast_scan(ops, spec.f_codes, seen, rows, max_open_bits)
    return _fastkey_from_native(out)


def _fastkey_from_native(out):
    if out is None:
        return None
    n_calls, max_open, rs, counts, cs, cu, cuts, *rest = out
    # Py_BuildValue turns a NULL pointer (empty vec) into None
    deltas = None
    positions = None
    if len(rest) == 1:               # object scan: + ret positions
        positions = np.frombuffer(rest[0] or b"", np.int32)
    elif len(rest) == 4:             # cols scan: + deltas + positions
        dc, dslot, duop, pos = rest
        deltas = (np.frombuffer(dc or b"", np.int32),
                  np.frombuffer(dslot or b"", np.int32),
                  np.frombuffer(duop or b"", np.int32))
        positions = np.frombuffer(pos or b"", np.int32)
    return _FastKey(None, max_open, n_calls,
                    arrays=(np.frombuffer(rs or b"", np.int32),
                            np.frombuffer(counts or b"", np.int32),
                            np.frombuffer(cs or b"", np.int32),
                            np.frombuffer(cu or b"", np.int32)),
                    cuts=np.frombuffer(cuts or b"", np.int32),
                    deltas=deltas, positions=positions)


def _cols_args(packed, spec):
    """The six contiguous column buffers the C columnar scanners take,
    or None when this (packed, spec) pair can't feed them (custom
    encode_op, no packed columns).  vkind==4 gates every out-of-int32
    value before it is read, so the wrapping casts below never reach
    the kernel tables."""
    if getattr(spec, "encode_op", None) is not None:
        return None
    if packed is None or getattr(packed, "vkind", None) is None:
        return None
    if getattr(packed, "key", None) is not None:
        # a keyed history's value columns hold each record's inner
        # value, no single register's: the whole history takes the
        # object walk, which sees the KV values, as it always did
        return None
    nf = len(packed.f_codes)
    fcol = packed.f
    if nf == 0:
        fmap = np.full(len(fcol), -1, np.int32)
    else:
        f2spec = np.full(nf, -1, np.int32)
        for tag, hid in packed.f_codes.items():
            code = spec.f_codes.get(tag)
            if code is not None:
                f2spec[hid] = code
        fmap = np.where((fcol >= 0) & (fcol < nf),
                        f2spec[np.clip(fcol, 0, nf - 1)],
                        np.int32(-1)).astype(np.int32, copy=False)
    # The spec-INDEPENDENT contiguous casts (the int32 value columns
    # are ~2 ms per 100k-op history) are a pure representation
    # transform of the packed journal — cache them on it, like
    # packed_columns() itself; only fmap depends on the spec.  The
    # cache is GUARDED by (packed.version, len(packed)): in-place
    # column mutators bump `version` via History.invalidate_packed()
    # (or PackedHistory directly), and a length change (journal grew
    # between scans) also invalidates — a stale cache here would feed
    # the native scanners columns the Python oracle no longer sees.
    tag = (getattr(packed, "version", 0), len(packed))
    cached = getattr(packed, "_scan_cols", None)
    fixed = cached[1] if cached is not None and cached[0] == tag \
        else None
    if fixed is None:
        fixed = (np.ascontiguousarray(packed.process, dtype=np.int32),
                 np.ascontiguousarray(packed.type, dtype=np.uint8),
                 np.ascontiguousarray(packed.value[:, 0].astype(
                     np.int32)),
                 np.ascontiguousarray(packed.value[:, 1].astype(
                     np.int32)),
                 np.ascontiguousarray(packed.vkind, dtype=np.uint8))
        packed._scan_cols = (tag, fixed)
    return (fixed[0], fixed[1], np.ascontiguousarray(fmap),
            fixed[2], fixed[3], fixed[4])


def _native_scan_cols(packed, spec, seen: dict, rows: list,
                      max_open_bits: int, want_snaps: bool = True,
                      keep=None):
    """Columnar twin of _native_scan: runs the fused C scan over the
    history's native struct-of-arrays representation (built
    incrementally by history.ColumnJournal at journal time, SURVEY.md
    §7) — no per-op Python objects at all, ~25x the object walk.
    `keep` (record positions) scans those records alone, as a
    crash-stripped twin.  Returns False when unavailable (no packed
    columns / no extension), None when out of scope, else a
    _FastKey."""
    from jepsen_tpu import native

    if getattr(spec, "encode_op", None) is not None:
        return None
    mod = native.histscan()
    if mod is None or not hasattr(mod, "fast_scan_cols"):
        return False                 # cheap check BEFORE the casts
    cols = _cols_args(packed, spec)
    if cols is None:
        return False
    if keep is not None:
        cols = [np.take(c, keep) for c in cols]
    out = mod.fast_scan_cols(*cols, seen, rows, max_open_bits,
                             1 if want_snaps else 0)
    return _fastkey_from_native(out)


class _StreamKey:
    """The stream scanner's product: one scanned history already in
    the grouped pipeline's wire layout (I = 1 compact row streams +
    segment cum table) — see native/histscan.c fast_scan_streams.
    Duck-types the _FastKey fields the pipeline reads (n_calls,
    max_open, positions)."""

    __slots__ = ("n_calls", "max_open", "n_rets", "lp_min", "ret32",
                 "islot32", "iuop32", "cum", "seg_ends", "positions")

    def __init__(self, n_calls, max_open, n_rets, lp_min, ret32,
                 islot32, iuop32, cum, seg_ends, positions):
        self.n_calls = n_calls
        self.max_open = max_open
        self.n_rets = n_rets
        self.lp_min = lp_min
        self.ret32 = ret32
        self.islot32 = islot32
        self.iuop32 = iuop32
        self.cum = cum
        self.seg_ends = seg_ends
        self.positions = positions

    @property
    def k(self):
        return len(self.seg_ends)

    @property
    def rtot(self):
        return int(self.cum[-1]) if len(self.cum) else 0


def _native_scan_streams(packed, spec, seen: dict, rows: list,
                         max_open_bits: int, target: int):
    """One fused C pass from packed columns to the grouped pipeline's
    wire layout: scan + quiescent-cut segmentation + I=1 row streams
    (native/histscan.c fast_scan_streams).  Returns False when
    unavailable, None when out of scope, else a _StreamKey."""
    from jepsen_tpu import native

    # Scope check FIRST, mirroring _native_scan_cols: a custom
    # encode_op is out of SCOPE for the C scanners (None — callers
    # must not retry other native forms), not merely unavailable
    # (False).  Checking module availability first conflated the two
    # sentinels whenever the extension was missing (ADVICE r5).
    if getattr(spec, "encode_op", None) is not None:
        return None
    mod = native.histscan()
    if mod is None or not hasattr(mod, "fast_scan_streams"):
        return False                 # cheap check BEFORE the casts
    cols = _cols_args(packed, spec)
    if cols is None:
        return False
    out = mod.fast_scan_streams(*cols, seen, rows, max_open_bits,
                                target)
    if out is None:
        return None
    n_calls, max_open, n_rets, lp_min, rs, isl, iu, cum, se, pos = out
    return _StreamKey(
        n_calls, max_open, n_rets, lp_min,
        np.frombuffer(rs or b"", np.int32),
        np.frombuffer(isl or b"", np.int32),
        np.frombuffer(iu or b"", np.int32),
        np.frombuffer(cum or b"", np.int32),
        np.frombuffer(se or b"", np.int32),
        np.frombuffer(pos or b"", np.int32))


def _fill_block_stream(sk: "_StreamKey", Rp: int, Kp: int, U: int):
    """Pad one _StreamKey into the common wire block (the same layout
    _regs_fill_compact emits): rows u8[Rp] (ret+1 | (islot+1)<<4) ++
    iuop u8|u16[Rp] ++ cum i32[Kp+1]."""
    rtot = sk.rtot
    rows_s = np.zeros(Rp, np.uint8)
    rows_s[:rtot] = ((sk.ret32 + 1)
                     | ((sk.islot32 + 1) << 4)).astype(np.uint8)
    ud = np.uint8 if U <= 255 else np.uint16
    iuop_s = np.zeros(Rp, ud)
    iuop_s[:rtot] = sk.iuop32.astype(ud)
    cum = np.zeros(Kp + 1, np.int32)
    k = sk.k
    cum[1:k + 1] = sk.cum[1:]
    cum[k + 1:] = sk.cum[k]
    return np.concatenate([rows_s, iuop_s.view(np.uint8),
                           cum.view(np.uint8)])


def _fast_scan(history, spec, seen: dict, rows: list,
               max_open_bits: int, max_crashed: int = 0):
    """Fused pairing + slot assignment + op interning for one key —
    ONE pass over the ops instead of prepare() + _assign_slots() +
    _encode_calls() building per-op objects (the host side dominated
    multi-key bench wall time).  Returns a _FastKey, or None when the
    key is outside the batch engine's scope (crashed calls beyond
    `max_crashed`, too-deep concurrency, un-internable ops, custom
    encode_op) — the caller sends those through the slow path.  Shared
    seen/rows are only touched on success.

    With `max_crashed > 0`, up to that many crashed (:info / unpaired)
    calls are tolerated: each holds a permanent slot above the normal
    range (see _FastKey.nc/.rn) and joins every snapshot from its
    invoke onward; quiescent cuts count NORMAL open calls only."""
    if getattr(spec, "encode_op", None) is not None:
        return None                  # custom encodings take the slow path
    ops = history.ops if isinstance(history, History) else \
        History(history).ops
    f_codes = spec.f_codes

    # Pass 1: completion for each invocation position.
    open_by_process: dict = {}
    fate: dict = {}
    n_client = 0
    for pos, o in enumerate(ops):
        p = o.process
        if not (type(p) is int and p >= 0):
            continue
        n_client += 1
        if o.type == "invoke":
            if p in open_by_process:
                # malformed history: send it to the slow path, whose
                # prepare() raises the descriptive ValueError (the C
                # twin does the same)
                return None
            open_by_process[p] = pos
        else:
            ip = open_by_process.pop(p, None)
            if ip is not None:
                fate[ip] = o
    if open_by_process and max_crashed == 0:
        return None                  # unpaired invokes stay open: crashed
    if n_client == 0:
        return _FastKey([], 0, 0)

    # Pass 2: slots + interning + return records.
    new_seen: dict = {}
    new_rows: list = []
    free: list = []
    next_slot = 0
    slot_of: dict = {}
    uop_of: dict = {}
    open_list: list = []
    crashed_list: list = []          # [(temp slot -2-j, uop), ...]
    rets: list = []
    cuts: list = []
    max_open = 0
    n_calls = 0
    INT32 = 2 ** 31
    for pos, o in enumerate(ops):
        p = o.process
        if not (type(p) is int and p >= 0):
            continue
        t = o.type
        if t == "invoke":
            comp = fate.get(pos)
            crashed = comp is None or comp.type == "info"
            if crashed and (max_crashed == 0
                            or len(crashed_list) >= max_crashed):
                return None          # crashed call (or too many)
            if not crashed and comp.type == "fail":
                continue             # the pair never happened: dropped
            v = o.value if (o.value is not None or comp is None) \
                else comp.value
            fc = f_codes.get(o.f, -1)
            if fc < 0:
                return None          # model has no f-code for this op
            # _generic_encode_op, inlined — isinstance (not exact-type)
            # checks so int subclasses (IntEnum, ...) encode by VALUE
            # exactly as the serial engines do
            if isinstance(v, bool):
                av, bv, okv = int(v), 0, True
            elif isinstance(v, int):
                av, bv, okv = v, 0, True
            elif isinstance(v, (list, tuple)) and len(v) == 2 \
                    and isinstance(v[0], int) and isinstance(v[1], int) \
                    and not isinstance(v[0], bool) \
                    and not isinstance(v[1], bool):
                av, bv, okv = v[0], v[1], True
            else:
                av, bv, okv = 0, 0, False
            if not (-INT32 <= av < INT32 and -INT32 <= bv < INT32):
                return None          # outside the int32 device range
            key = (fc, av, bv, okv)
            u = seen.get(key)
            if u is None:
                u = new_seen.get(key)
            if u is None:
                u = new_seen[key] = len(rows) + len(new_rows)
                new_rows.append(key)
            if crashed:
                # permanent pseudo-slot, remapped to rn+j at the end
                crashed_list.append((-2 - len(crashed_list), u))
                n_calls += 1
                continue
            s = free.pop() if free else next_slot
            if s == next_slot:
                next_slot += 1
            slot_of[p] = s
            uop_of[p] = u
            open_list.append(p)
            if len(open_list) > max_open:
                max_open = len(open_list)
                if max_open > max_open_bits:
                    return None      # too many simultaneously-open calls
            n_calls += 1
        elif t == "ok":
            s = slot_of.get(p)
            if s is None:
                continue
            rets.append((s, [(slot_of[q], uop_of[q])
                             for q in open_list] + list(crashed_list)))
            open_list.remove(p)
            del slot_of[p]
            del uop_of[p]
            free.append(s)
            cuts.append(1 if not open_list else 0)

    seen.update(new_seen)
    rows.extend(new_rows)
    nc = len(crashed_list)
    if nc:
        # remap crashed pseudo-slots above the normal range
        rn = max_open
        rets = [(s, [(q if q >= 0 else rn + (-2 - q), u)
                     for q, u in cands]) for s, cands in rets]
        return _FastKey(rets, max_open, n_calls,
                        cuts=np.asarray(cuts, np.int32), nc=nc, rn=rn)
    return _FastKey(rets, max_open, n_calls,
                    cuts=np.asarray(cuts, np.int32))


def _assign_slots(events):
    """Free-list slot assignment over (pos, kind, call_id) events.
    Returns (rets, n_slots, still_open) where each ret is
    (call_id, slot, [(open_call_id, open_slot), ...]) — the open set at
    that return, target included."""
    free: list[int] = []
    next_slot = 0
    slot_of: dict[int, int] = {}
    open_calls: list[int] = []
    rets: list[tuple[int, int, list[tuple[int, int]]]] = []
    for _, kind, cid in events:
        if kind == 0:
            s = free.pop() if free else next_slot
            if s == next_slot:
                next_slot += 1
            slot_of[cid] = s
            open_calls.append(cid)
        else:
            rets.append((cid, slot_of[cid],
                         [(c2, slot_of[c2]) for c2 in open_calls]))
            open_calls.remove(cid)
            free.append(slot_of[cid])
    return rets, next_slot, open_calls


def _decompose(legal: np.ndarray, next_state: np.ndarray):
    """Diagonal + rank-1 decomposition (see SegPlan): decomposable iff
    each op's state-changing transitions all target one state.  Returns
    (diag_w, const_w, const_t0) or (None, None, None)."""
    U, Sn = legal.shape
    diag_w = np.zeros((U, Sn), np.float32)
    const_w = np.zeros((U, Sn), np.float32)
    const_t0 = np.zeros(U, np.int32)
    for u in range(U):
        targets = set()
        for s in range(Sn):
            if not legal[u, s]:
                continue
            if next_state[u, s] == s:
                diag_w[u, s] = 1.0
            else:
                const_w[u, s] = 1.0
                targets.add(int(next_state[u, s]))
        if len(targets) > 1:
            return None, None, None
        if targets:
            const_t0[u] = targets.pop()
    return diag_w, const_w, const_t0


def _segments_from_fk(fk, R: int, seg_ends):
    """Slice one key's scanned return stream at the given segment ends
    (quiescent cuts, from _segment_ends); returns per-segment
    _FastKeys."""
    rs, counts, cs, cu = _fk_arrays(fk)
    cand_off = np.concatenate([[0], np.cumsum(counts)])
    seg_fk = []
    lo = 0
    for hi in seg_ends:
        seg_fk.append(_FastKey(
            None, R, int(hi - lo),
            arrays=(rs[lo:hi], counts[lo:hi],
                    cs[cand_off[lo]:cand_off[hi]],
                    cu[cand_off[lo]:cand_off[hi]])))
        lo = hi
    return seg_fk


def _scan_history(h, ops, spec, seen: dict, rows: list,
                  max_open_bits: int, want_snaps: bool = True, st=None):
    """The one scan-fallback policy shared by every engine entry point:
    columnar C scan when the history carries packed columns, then the
    object C scan, then the pure-Python twin.  Returns a _FastKey or
    None (out of scope — crashed calls, deep concurrency, unencodable
    values); all three scanners are differentially pinned to classify
    identically, so the object scan retries only a key whose columns
    are not complete (_cols_complete).  `ops` may be None: the object
    scan then reads `h.ops`.  want_snaps=False skips candidate-snapshot
    emission for callers that consume only the delta stream (fk.arrays
    then carries empty cand_slots/cand_uops).  With `st` (a
    telemetry.Stages), the scanner that gave the answer is tallied
    under `jepsen_scan_keys_total{path}`: `columnar`, `objects` or
    `python`."""
    path = "columnar"
    packed = h.packed_columns() if isinstance(h, History) else None
    fk = _native_scan_cols(packed, spec, seen, rows, max_open_bits,
                           want_snaps)
    if fk is False or (fk is None and not _cols_complete(packed)):
        path = "objects"
        if ops is None:
            ops = h.ops if isinstance(h, History) else History(h).ops
        fk = _native_scan(ops, spec, seen, rows, max_open_bits)
    if fk is False:
        path = "python"
        fk = _fast_scan(h, spec, seen, rows, max_open_bits)
    if st is not None:
        st.tally(telemetry.SCAN_KEYS, path=path)
    return fk


def _cols_complete(packed) -> bool:
    """Whether the C columnar scan sees all that the object scan sees:
    value kinds present, no key column, and no client id outside int32
    (P_OUT_OF_RANGE, whose real id only the ops hold).  Over complete
    columns a key the columnar scan judges out of scope is out of the
    object scan's scope too, so no op need be read to learn it."""
    return (packed is not None
            and getattr(packed, "vkind", None) is not None
            and getattr(packed, "key", None) is None
            and not (packed.process == P_OUT_OF_RANGE).any())


def _fk_arrays(fk: "_FastKey"):
    """Flat (ret_slots, cand_counts, cand_slots, cand_uops) arrays for
    either scanner form."""
    if fk.arrays is not None:
        return fk.arrays
    rs = np.fromiter((r[0] for r in fk.rets), np.int32,
                     count=len(fk.rets))
    counts = np.fromiter((len(r[1]) for r in fk.rets), np.int32,
                         count=len(fk.rets))
    cs = np.fromiter((s for _, cands in fk.rets for s, _ in cands),
                     np.int32)
    cu = np.fromiter((u for _, cands in fk.rets for _, u in cands),
                     np.int32)
    return rs, counts, cs, cu


# ---------------------------------------------------------------------------
# Native parallel ingest (ISSUE 9): the GIL-released, work-stealing
# scan-and-pack layer (native/packext.c).  The Python packers below
# remain the bit-for-bit differential twin and the total fallback —
# a missing compiler or ANY native-path error lands back on them
# (counted, never a silent wrong pack), and plans record which
# backend ran (Plan.pack_backend / pack_threads).
# ---------------------------------------------------------------------------

def pack_threads_effective(env: Optional[dict] = None) -> int:
    """Thread count for the native ingest layer.  The knob
    JEPSEN_TPU_PACK_THREADS overrides (0 = pure-Python packers);
    default min(8, cpu_count) — the pack is memory-bound past that."""
    env = _snapshot_env(env)
    raw = env.get("JEPSEN_TPU_PACK_THREADS")
    if raw is not None:
        try:
            return max(0, int(raw))
        except ValueError:
            return 0
    return min(8, os.cpu_count() or 1)


def pack_backend_effective(env: Optional[dict] = None) -> str:
    """'native' when the packext extension is buildable/loaded and the
    thread knob admits it, else 'python'.  Like the jax backend, the
    extension's availability is a process-constant capability input —
    plans stay reproducible within a process."""
    if pack_threads_effective(env) <= 0:
        return "python"
    from jepsen_tpu import native
    return "python" if native.packext() is None else "native"


def _count_pack(backend: str, outcome: str) -> None:
    try:
        from jepsen_tpu import telemetry
        telemetry.REGISTRY.counter("jepsen_pack_total",
                                   backend=backend,
                                   outcome=outcome).inc()
    except Exception:           # noqa: BLE001 - counters must not break
        pass


def _native_pack_compact(batch, Kp: int, R: int, U: int):
    """C twin of `_pack_regs(I=1)` + `_compact_many_block` over one
    key chunk: snapshot-delta derivation and compact-stream packing in
    parallel across the key axis, written once into one arena
    (native/packext.c pack_compact_many — bit-identical bytes, pinned
    by tests/test_packext.py).  Returns (buf8 uint8[...], Rp, Lp) or
    None when the native path is unavailable or errored — callers then
    run the Python packers, the total fallback."""
    nt = pack_threads_effective()
    if nt <= 0 or not (0 < R <= 15):
        return None
    from jepsen_tpu import native
    mod = native.packext()
    if mod is None:
        return None
    keys = []
    for _, fk in batch:
        rs, counts, cs, cu = _fk_arrays(fk)
        keys.append((np.ascontiguousarray(rs, np.int32),
                     np.ascontiguousarray(counts, np.int32),
                     np.ascontiguousarray(cs, np.int32),
                     np.ascontiguousarray(cu, np.int32)))
    try:
        buf, Rp, lp_min = mod.pack_compact_many(
            keys, int(Kp), int(R), int(U), int(nt))
    except Exception:           # noqa: BLE001 - degrade, never mis-pack
        _count_pack("native", "error")
        return None
    _count_pack("native", "ok")
    return np.frombuffer(buf, np.uint8), int(Rp), _pad_len(int(lp_min))


def _scan_cols_many(histories, spec, seen: dict, rows: list,
                    max_open_bits: int):
    """Parallel columnar scan over a whole key batch (packext
    scan_cols_many): per-key work on a work-stealing pool, uop ids
    merged in key order so they land exactly where the serial per-key
    ladder would have put them.  Returns {index: _FastKey | None}
    (None = out of the batch engine's scope, same as the serial
    scanners) for the keys that carried packed columns, or None when
    the parallel path shouldn't run — no extension, a custom
    encode_op, or fewer than 2 effective threads (the two-phase
    interning costs one extra pass over the uop columns, a loss on a
    single core; measured on the 1-core CI host)."""
    nt = pack_threads_effective()
    if nt < 2 or getattr(spec, "encode_op", None) is not None:
        return None
    from jepsen_tpu import native
    mod = native.packext()
    if mod is None or not hasattr(mod, "scan_cols_many"):
        return None
    idxs: list = []
    cols_list: list = []
    for i, h in enumerate(histories):
        if not isinstance(h, History):
            continue
        cols = _cols_args(h.packed_columns(), spec)
        if cols is None:
            continue
        idxs.append(i)
        cols_list.append(cols)
    if not cols_list:
        return {}
    try:
        outs = mod.scan_cols_many(cols_list, seen, rows,
                                  int(max_open_bits), int(nt))
    except MemoryError:
        raise
    except Exception:           # noqa: BLE001 - degrade to serial scan
        _count_pack("native-scan", "error")
        return None
    return {i: _fastkey_from_native(o) for i, o in zip(idxs, outs)}


# ---------------------------------------------------------------------------
# Host-side table packing (extracted from wgl_seg.py with the planning
# section — the 'pack' half of the plan+pack host wall the overlap
# executor hides; wgl_seg re-exports every name) plus the crash-split
# and transfer-composition host analyses.
# ---------------------------------------------------------------------------

def _pack_uop_tables(legal: np.ndarray, next_state: np.ndarray,
                     diag_w, const_w, const_t0, sn_words: int = 1):
    """[U]-indexed transition tables for the register kernel — the same
    decomposed / nibble forms _pack_cand_tables gathers on host, left
    un-gathered for device-side lookup.  With sn_words = W > 1 the
    decomposed state bitmasks come back as [U, W] uint32 (state s ->
    word s // 32, bit s % 32) for the wide-state relaxed tier."""
    U, Sn = legal.shape
    if sn_words > 1:
        assert diag_w is not None
        a1 = np.zeros((U, sn_words), np.uint32)
        a2 = np.zeros((U, sn_words), np.uint32)
        for sw in range(sn_words):
            lo, hi = sw * 32, min((sw + 1) * 32, Sn)
            pw = (1 << np.arange(hi - lo, dtype=np.uint64)) \
                .astype(np.uint64)
            a1[:, sw] = ((diag_w[:, lo:hi] > 0).astype(np.uint64)
                         * pw).sum(1).astype(np.uint32)
            a2[:, sw] = ((const_w[:, lo:hi] > 0).astype(np.uint64)
                         * pw).sum(1).astype(np.uint32)
        return a1, a2, const_t0.astype(np.int32)
    pow2 = (1 << np.arange(Sn, dtype=np.uint64)).astype(np.uint64)
    if diag_w is not None:
        aux1 = ((diag_w > 0).astype(np.uint64) * pow2).sum(1)
        aux2 = ((const_w > 0).astype(np.uint64) * pow2).sum(1)
        t0 = const_t0.astype(np.int32)
    else:
        aux1 = (legal.astype(np.uint64) * pow2).sum(1)
        nib = (1 << (4 * np.arange(Sn, dtype=np.uint64))).astype(np.uint64)
        aux2 = (next_state.astype(np.uint64) * nib).sum(1)
        t0 = np.zeros(U, np.int32)
    return (aux1.astype(np.uint32), aux2.astype(np.uint32), t0)


def _pack_regs(batch, Kp: int, R: int, U: int, I: int):
    """Delta-encode the whole batch for _build_kernel_regs: per return,
    only the calls invoked since the previous return (derived from
    consecutive candidate snapshots — between two returns a slot hosts
    at most one new occupant, so a changed (slot -> uop) cell IS the new
    invoke; an unchanged cell re-registers identical aux words, a
    no-op).  Bursts beyond I spill into virtual rows (ret -1) BEFORE
    their return's row.  Returns (ret_t [L', K], islot_t, iuop_t
    [L', K, I], L')."""
    # --- flatten all keys' snapshots ----------------------------------
    rs_parts, cnt_parts, cs_parts, cu_parts, nr_parts = [], [], [], [], []
    for _, fk in batch:
        rs, counts, cs, cu = _fk_arrays(fk)
        rs_parts.append(rs)
        cnt_parts.append(counts)
        cs_parts.append(cs)
        cu_parts.append(cu)
        nr_parts.append(len(rs))
    rs_all = np.concatenate(rs_parts)
    cnt_all = np.concatenate(cnt_parts)
    cs_all = np.concatenate(cs_parts).astype(np.int64)
    cu_all = np.concatenate(cu_parts)
    nr_all = np.asarray(nr_parts, np.int64)
    NR = len(rs_all)
    ret_key = np.repeat(np.arange(len(batch)), nr_all)
    key_start = np.concatenate([[0], np.cumsum(nr_all)[:-1]])
    first_ret = key_start                       # global idx of row 0 per key

    # dense snapshot matrix M[r, slot] = uop at return r, -1 empty
    M = np.full((NR, R), -1, np.int64)
    rowidx = np.repeat(np.arange(NR), cnt_all)
    M[rowidx, cs_all] = cu_all
    # previous snapshot with the returning slot freed
    Oprev = np.full_like(M, -1)
    Oprev[1:] = M[:-1]
    idx = np.arange(1, NR)
    Oprev[idx, rs_all[:-1].astype(np.int64)] = -1
    Oprev[first_ret] = -1
    D = (M != -1) & (M != Oprev)
    c = D.sum(1).astype(np.int64)               # deltas per return

    # --- row layout with virtual spill rows ---------------------------
    e = np.maximum(0, (c + I - 1) // I - 1)     # virtual rows per return
    ecum = np.cumsum(e)
    ebase = np.concatenate([[0], ecum])[key_start]   # e-cumsum before key
    r_local = np.arange(NR) - key_start[ret_key]
    rho = r_local + (ecum - ebase[ret_key])     # local row of return r
    rows_per_key = np.zeros(len(batch), np.int64)
    np.maximum.at(rows_per_key, ret_key, rho + 1)
    Lp = int(rows_per_key.max())
    Lp = _pad_len(Lp)

    ret_slot = np.full((Kp, Lp), -1, np.int8)
    ret_slot[ret_key, rho] = rs_all.astype(np.int8)

    # --- scatter delta entries into (row, col) ------------------------
    ent_ret, ent_slot = np.nonzero(D)           # ordered by (ret, slot)
    ent_uop = M[ent_ret, ent_slot]
    starts = np.cumsum(c) - c
    j = np.arange(len(ent_ret)) - starts[ent_ret]
    from_end = c[ent_ret] - 1 - j
    row = rho[ent_ret] - from_end // I
    col = from_end % I
    uop_dtype = np.int8 if U <= 127 else np.int16
    inv_slot = np.full((Kp, Lp, I), -1, np.int8)
    inv_uop = np.full((Kp, Lp, I), -1, uop_dtype)
    inv_slot[ret_key[ent_ret], row, col] = ent_slot.astype(np.int8)
    inv_uop[ret_key[ent_ret], row, col] = ent_uop.astype(uop_dtype)

    ret_t = np.ascontiguousarray(ret_slot.T)
    islot_t = np.ascontiguousarray(inv_slot.transpose(1, 0, 2))
    iuop_t = np.ascontiguousarray(inv_uop.transpose(1, 0, 2))
    return ret_t, islot_t, iuop_t, Lp


class _RegsLayout:
    """Row/column placement of one scanned key's delta stream across
    its segments — everything _regs_fill needs to scatter the tables,
    plus the minimal (Lp, K) shape.  Computing layouts for a whole
    pipeline batch first lets every history fill DIRECTLY at the
    common padded shape (no per-history np.pad / transpose copies)."""

    __slots__ = ("ret_key", "rho", "rs", "ent_key", "row", "col",
                 "dslot", "duop", "lp_min", "k", "rows_per_key")

    def __init__(self, fk, seg_ends, I: int):
        rs = _fk_arrays(fk)[0]
        dc, dslot, duop = fk.deltas
        NR = len(rs)
        K = len(seg_ends)
        nr_all = np.diff(np.concatenate([[0], seg_ends]))
        key_end = np.cumsum(nr_all)
        ret_key = np.repeat(np.arange(K), nr_all)
        key_start = np.concatenate([[0], key_end[:-1]])
        c = dc.astype(np.int64)
        e = np.maximum(0, (c + I - 1) // I - 1)
        ecum = np.cumsum(e)
        ebase = np.concatenate([[0], ecum])[key_start]
        r_local = np.arange(NR) - key_start[ret_key]
        rho = r_local + (ecum - ebase[ret_key])
        ent_ret = np.repeat(np.arange(NR), c)
        starts = np.cumsum(c) - c
        j = np.arange(len(dslot)) - starts[ent_ret]
        from_end = c[ent_ret] - 1 - j
        self.ret_key = ret_key
        self.rho = rho
        self.rs = rs
        self.ent_key = ret_key[ent_ret]
        self.row = rho[ent_ret] - from_end // I
        self.col = from_end % I
        self.dslot = dslot
        self.duop = duop
        # rho is monotone within a segment, so each segment's row count
        # sits at its LAST return — no np.maximum.at (whose buffered
        # scatter was the single hottest line of the pipeline's host
        # side at ~3 ms per 100k-op history)
        self.rows_per_key = (rho[key_end - 1] + 1 if NR and K
                             else np.zeros(K, np.int64))
        self.lp_min = int(self.rows_per_key.max()) if K and NR else 0
        self.k = K


def _regs_fill(lay: "_RegsLayout", Lp: int, K: int, U: int, I: int):
    """Scatter one layout into [Lp, K(, I)] tables (already in the
    kernel's transposed orientation — no copies).  Padding rows/lanes
    beyond the layout's own shape are exact no-ops (ret -1, no
    invokes)."""
    ret_t = np.full((Lp, K), -1, np.int8)
    ret_t[lay.rho, lay.ret_key] = lay.rs.astype(np.int8)
    uop_dtype = np.int8 if U <= 127 else np.int16
    islot_t = np.full((Lp, K, I), -1, np.int8)
    iuop_t = np.full((Lp, K, I), -1, uop_dtype)
    islot_t[lay.row, lay.ent_key, lay.col] = lay.dslot.astype(np.int8)
    iuop_t[lay.row, lay.ent_key, lay.col] = lay.duop.astype(uop_dtype)
    return ret_t, islot_t, iuop_t


def _regs_fill_compact(lay: "_RegsLayout", Rp: int, Kp: int, U: int):
    """Pack one layout (I = 1) into the COMPACT wire block the grouped
    pipeline ships: segment-major row streams with NO [Lp, K] padding —
    rows u8[Rp] (low nibble ret+1, high nibble islot+1; 0 = the -1
    sentinel, so a slot id s rides as s+1 <= 15 — the R <= 14 gate
    guarantees the fit) ++ iuop u8[Rp] (2-byte LE when U > 255) ++
    cum i32[Kp + 1].  cum[k] is segment k's start row in the streams;
    the device rebuilds the padded [L, K] tables with a masked gather
    (see _build_kernel_regs_group_c), so the transfer carries ~10x
    fewer bytes than the padded tables did.  Rows beyond a segment's
    count and rows in cum[lay.k]..Rp are sentinel (0 nibbles): exact
    no-ops in the kernel."""
    cum = np.zeros(Kp + 1, np.int32)
    np.cumsum(lay.rows_per_key, out=cum[1:lay.k + 1])
    cum[lay.k + 1:] = cum[lay.k]
    rtot = int(cum[lay.k])
    rows_s = np.zeros(Rp, np.uint8)
    base = cum[lay.ret_key]
    rows_s[base + lay.rho] = (lay.rs + 1).astype(np.uint8)
    idx = cum[lay.ent_key] + lay.row
    rows_s[idx] |= ((lay.dslot + 1).astype(np.uint8) << 4)
    if U <= 255:
        iuop_s = np.zeros(Rp, np.uint8)
        iuop_s[idx] = lay.duop.astype(np.uint8)
        iu8 = iuop_s
    else:
        iuop_s = np.zeros(Rp, np.uint16)
        iuop_s[idx] = lay.duop.astype(np.uint16)
        iu8 = iuop_s.view(np.uint8)
    return np.concatenate([rows_s, iu8, cum.view(np.uint8)]), rtot

def _compact_many_block(ret_t, islot_t, iuop_t, Kp: int, U: int):
    """Compress _pack_regs' I=1 padded tables into the key-major
    compact stream block _build_kernel_regs_many_c consumes.  Each
    lane's live rows are a contiguous prefix (returns + spills in
    stream order, padding after), so the block is one ragged gather."""
    Lp = ret_t.shape[0]
    valid = (ret_t != -1) | (islot_t[:, :, 0] != -1)    # [Lp, Kp]
    n_rows = np.where(valid, np.arange(Lp)[:, None] + 1, 0) \
        .max(axis=0).astype(np.int64)                   # [Kp]
    cum = np.zeros(Kp + 1, np.int32)
    np.cumsum(n_rows, out=cum[1:])
    total = int(cum[-1])
    Rp = ((total + 8191) // 8192) * 8192
    key_of = np.repeat(np.arange(Kp), n_rows)
    row_of = np.arange(total) - np.repeat(cum[:-1].astype(np.int64),
                                          n_rows)
    rows_s = np.zeros(Rp, np.uint8)
    rows_s[:total] = (
        (ret_t[row_of, key_of].astype(np.int32) + 1)
        | ((islot_t[row_of, key_of, 0].astype(np.int32) + 1)
           << 4)).astype(np.uint8)
    ud = np.uint8 if U <= 255 else np.uint16
    iuop_s = np.zeros(Rp, ud)
    iuop_s[:total] = np.maximum(
        iuop_t[row_of, key_of, 0].astype(np.int32), 0).astype(ud)
    return np.concatenate([rows_s, iuop_s.view(np.uint8),
                           cum.view(np.uint8)]), Rp


def _pack_regs_single(fk, seg_ends: np.ndarray, R: int, U: int, I: int):
    """Delta-encode ONE scanned key split at `seg_ends` — the fast twin
    of _pack_regs for the single-history path.  The columnar scanner
    already emitted the invoke-delta stream (fk.deltas), so no dense
    snapshot matrices are rebuilt here: segment boundaries sit at
    quiescent cuts where nothing is open, which is exactly why the
    per-return delta stream is valid for ANY such segmentation (the
    first return of a segment registers precisely the calls invoked
    since the cut).  Layout math (virtual spill rows before their
    return) is identical to _pack_regs."""
    lay = _RegsLayout(fk, seg_ends, I)
    Lp = _pad_len(lay.lp_min)
    ret_t, islot_t, iuop_t = _regs_fill(lay, Lp, lay.k, U, I)
    return ret_t, islot_t, iuop_t, Lp


def _pack_cand_tables(cand_uop: np.ndarray, legal: np.ndarray,
                      next_state: np.ndarray, diag_w, const_w, const_t0):
    """Host-side packing of per-candidate transition tables into the
    uint32 bitmask form _build_kernel_bits consumes (aux1, aux2, t0 —
    all shaped like cand_uop).  Decomposed: aux1/aux2 = diag/const
    state-bitmasks.  Non-decomposed (Sn <= 8): aux1 = legality bitmask,
    aux2 = next-state nibble-pack."""
    U, Sn = legal.shape
    ju = np.clip(cand_uop, 0, None)
    live = cand_uop >= 0
    pow2 = (1 << np.arange(Sn, dtype=np.uint64)).astype(np.uint64)
    # Narrowest bitmask dtype that holds Sn bits: host->device transfer
    # of these [L, K, C] tables dominates large batches.
    bm_dtype = (np.uint8 if Sn <= 8 else
                np.uint16 if Sn <= 16 else np.uint32)
    if diag_w is not None:
        diag_u = ((diag_w > 0).astype(np.uint64) * pow2).sum(1)
        const_u = ((const_w > 0).astype(np.uint64) * pow2).sum(1)
        aux1 = (diag_u[ju] * live).astype(bm_dtype)
        aux2 = (const_u[ju] * live).astype(bm_dtype)
        t0 = const_t0[ju].astype(np.int8)
    else:
        legal_u = (legal.astype(np.uint64) * pow2).sum(1)
        nib = (1 << (4 * np.arange(Sn, dtype=np.uint64))).astype(np.uint64)
        next_u = (next_state.astype(np.uint64) * nib).sum(1)
        aux1 = (legal_u[ju] * live).astype(bm_dtype)
        aux2 = (next_u[ju] * live).astype(np.uint32)
        t0 = np.zeros_like(cand_uop, dtype=np.int8)
    return aux1, aux2, t0

def _compose_transfer(T: np.ndarray, Sn: int) -> int:
    """Compose transfer matrices left-to-right from entry state 0
    (K tiny matvecs); returns the first dead segment or -1."""
    v = np.zeros(Sn, bool)
    v[0] = True
    for k in range(T.shape[0]):
        v = v @ T[k]
        if not v.any():
            return k
    return -1


def _split_crashed(ops):
    """One host pass over a key's ops: find crashed client calls
    (:info completion, or invoke with no completion).  Returns
    (drop bool[n], crashed) where drop marks crashed invokes and their
    :info completions and crashed lists (inv_pos, info_pos | -1, op) in
    invocation order — or None for malformed histories (double invoke),
    which the slow path's prepare() rejects with the descriptive
    error."""
    open_by_process: dict = {}
    info_of: dict = {}
    for pos, o in enumerate(ops):
        p = o.process
        if not (type(p) is int and p >= 0):
            continue
        if o.type == "invoke":
            if p in open_by_process:
                return None
            open_by_process[p] = pos
        else:
            ip = open_by_process.pop(p, None)
            if ip is not None and o.type == "info":
                info_of[ip] = pos
    crashed_pos = sorted(set(open_by_process.values()) | set(info_of))
    drop = np.zeros(len(ops), bool)
    crashed = []
    for ip in crashed_pos:
        cp = info_of.get(ip, -1)
        drop[ip] = True
        if cp >= 0:
            drop[cp] = True
        crashed.append((ip, cp, ops[ip]))
    return drop, crashed


def _split_crashed_cols(packed):
    """Column twin of _split_crashed over complete columns
    (_cols_complete): (the same drop mask, the number of crashed
    calls), or None for a double invoke.  Each client invoke pairs
    with the next record of its process: the records are grouped by
    process, in history order within each group, with no per-record
    Python."""
    proc = packed.process
    order = np.argsort(proc, kind="stable")
    p = proc[order]
    lo = int(np.searchsorted(p, 0))         # clients: process >= 0
    order, p = order[lo:], p[lo:]
    t = packed.type[order]
    inv = t == TYPE_CODE[INVOKE]
    follows = inv[:-1] & (p[1:] == p[:-1])  # record j+1 answers invoke j
    if (follows & (t[1:] == TYPE_CODE[INVOKE])).any():
        return None
    info = follows & (t[1:] == TYPE_CODE[INFO])
    crashed = inv.copy()
    crashed[:-1] &= ~follows | info
    drop = np.zeros(len(proc), bool)
    drop[order[crashed]] = True
    drop[order[1:][info]] = True
    return drop, int(np.count_nonzero(crashed))
