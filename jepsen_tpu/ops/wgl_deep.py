"""Deep-overlap linearizability megakernel — one Pallas program walks
the whole history with the frontier resident in VMEM.

Scope: the regime the reference's own tutorial names as THE cost cliff
— many simultaneously-open calls ("the search is exponential in the
number of concurrent operations", `doc/tutorial/06-refining.md:7-10`;
"difficulty goes like ~n!", `doc/tutorial/07-parameters.md:148-152`).
The segment engine (`ops.wgl_seg`) covers shallow overlap (R <= 6 on
the register-delta kernel); beyond that its candidate-table fallback
walks a dense 2^R config plane as *hundreds of XLA ops per event*, so
the per-op dispatch overhead, not FLOPs, bounds it.

This module removes the dispatch overhead instead of the plane: the
frontier is a bit-packed boolean tensor `fr[Sn, 2^R / 32]` uint32
(state rows x mask words — a few KB even at R = 14), held in VMEM
scratch for the entire event walk.  One `pl.pallas_call` processes the
whole history: the grid streams fixed-size event blocks into SMEM, and
each event is ~a hundred vector instructions on 1-8 vregs, with no
XLA op boundaries, no scan carry round-trips, and a closure
`while_loop` whose early exit costs one on-core reduction instead of a
host-visible sync.

Semantics are just-in-time linearization, identical to `ops.wgl` /
`ops.wgl_seg` (Lowe / knossos :linear, `checker.clj:141-145`):

  * at the return of call t, configurations lacking t are closed under
    linearizing any currently-open call (to fixpoint — expansion
    sources are restricted to configs still lacking t, exact by the
    deferral argument in `ops.wgl._build_kernel`), then pruned to
    those containing t, and t's slot is retired;
  * a *pure* returning op (never changes state, e.g. a read) that is
    directly legal on every config still lacking it short-circuits the
    closure entirely — the same fast path as `ops.wgl`, and the common
    case for register workloads;
  * fixpoint in <= R rounds (round k unions every config reachable by
    <= k linearizations; at most R calls are open — the exactness
    argument of `wgl_seg._build_kernel_bits`).

Crashed (:info) calls cost NOTHING structurally here: a crashed call
is an open slot that never returns (registered, never retired), and
the 2^R plane *is* the powerset of open calls — so any history with
`max_open_normal + n_crashed <= deep_r_max(...)` is checked exactly —
word-split/hypercube included (ISSUE 10) — where the
reference's knossos "can make the difference between seconds and days"
on a couple of crashed processes (`doc/tutorial/06-refining.md:12-19`).

Verdicts are exact in both directions (the plane has no capacity to
overflow).  On invalid, the kernel reports the exact failing event; the
host maps it to the returning op — the same witness `ops.wgl_cpu`
reports (differentially tested).

Transition model: the diagonal + rank-1 decomposition of
`wgl_seg._decompose` (each op either keeps the state or sends every
legal state to ONE target) with Sn <= 32 states — the whole register /
cas / mutex family.  Out-of-scope models keep their existing engines.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any

import numpy as np

from jepsen_tpu.errors import BackendUnavailable, CheckError

# Intra-word "lacks bit b" patterns: bit i set iff mask-index i has
# bit b clear (shared constant with ops.frontier._INTRA).
_INTRA = (0x55555555, 0x33333333, 0x0F0F0F0F, 0x00FF00FF, 0x0000FFFF)
_FULL = 0xFFFFFFFF

from jepsen_tpu.ops import planner

R_BASE = planner.DEEP_R_BASE   # depth ONE resident [Sn, 512]-word
                               # plane covers; the full envelope is
                               # planner.deep_r_max(backend, n_devices)
                               # — word-split sub-plane stacks to 16 on
                               # one device, the hypercube mask shard
                               # to 14 + log2(D) on a mesh (ISSUE 10)
EB = 512            # event rows per grid step (SMEM block budget)


def supported(R: int, Sn: int, U: int, decomposed: bool,
              backend: str, n_devices: int | None = None) -> bool:
    """Gate shared with the wgl_seg dispatcher — now owned by the one
    engine planner (`planner.deep_supported`, ISSUE 8) so the routing
    decision and this kernel's self-description cannot drift; kept as
    a thin delegate for the long-standing callers.  See
    planner.deep_supported for the scope and the
    JEPSEN_TPU_DEEP_INTERPRET backend-capability semantics;
    `n_devices` widens the boundary to the hypercube-mesh envelope."""
    return planner.deep_supported(R, Sn, U, decomposed, backend,
                                  n_devices=n_devices)


def _snp(Sn: int) -> int:
    return 8 if Sn <= 8 else 16 if Sn <= 16 else 32


@functools.lru_cache(maxsize=32)
def _build(G: int, I: int, Wd: int, SnP: int, R: int, UP: int,
           P: int, interpret: bool):
    """kern(evbuf i32[G, EB*(1+2I)], auxbuf u32[1, 3*UP+16])
    -> i32[1, 2] (alive, first-dead-row | -1).

    `P` is the WORD-SPLIT factor (ISSUE 10): the 2^R-mask plane lives
    as a stack of P sub-planes of Wd words each, laid out contiguously
    along the sublane axis ([P*SnP, Wd] VMEM scratch) — sub-plane s
    holds full-plane words [s*Wd, (s+1)*Wd).  P = 1 is the classic
    single resident plane (bit-identical to the pre-split kernel: all
    the split arms below are unreachable).  Slot-bit geography:
    bits < 5 are intra-word, [5, 5+log2(Wd)) shift along the word
    (lane) axis, and [5+log2(Wd), R) — the split bits — move WHOLE
    sub-planes along the sublane axis.  Every per-op tile the VPU sees
    stays [<=32, Wd]-shaped regardless of R; only the stack height
    grows, which is what buys R = 15/16 on one device with no semantic
    change.

    evbuf row layout per event row r of a block:
      [r]                      return slot (-1 = registration-only row)
      [EB + r*I + i]           newly-invoked slot i (-1 = none)
      [EB + EB*I + r*I + i]    its uop index
    auxbuf: diag-mask[UP] ++ const-mask[UP] ++ t0[UP] ++ intra[16]
    (intra[b] = lacks-bit-b pattern for b < 5, FULL above — so the
    dynamic-slot target pattern needs no per-bit dispatch)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    u32 = jnp.uint32
    EBW = EB * (1 + 2 * I)
    H = P * SnP                  # stacked sub-plane rows
    LOG_SNP = SnP.bit_length() - 1
    LW = Wd.bit_length() - 1     # log2 words per sub-plane
    assert P == 1 or (Wd * P) << 5 == (1 << R), (P, Wd, R)

    def popsum(x):
        return jax.lax.population_count(x).astype(jnp.int32).sum()

    def msk(cond):
        return jnp.where(cond, jnp.asarray(np.uint32(_FULL), u32),
                         jnp.asarray(np.uint32(0), u32))

    # static per-slot patterns over the [H, Wd] stack
    def lackpat(b, w_iota):
        """FULL where the mask index lacks slot bit b (w_iota is the
        FULL-plane word index, so one test covers word and split
        bits)."""
        if b < 5:
            return jnp.full((H, Wd), np.uint32(_INTRA[b]), u32)
        return msk(((w_iota >> (b - 5)) & 1) == 0)

    def shift_set(x, b):
        """Move configs (already masked to bit-b-clear) to mask|bit."""
        if b < 5:
            return x << (1 << b)
        d = 1 << (b - 5)
        if d < Wd:
            return jnp.concatenate(
                [jnp.zeros((H, d), u32), x[:, :Wd - d]], axis=1)
        rs = (d // Wd) * SnP     # whole-sub-plane move down the stack
        return jnp.concatenate(
            [jnp.zeros((rs, Wd), u32), x[:H - rs, :]], axis=0)

    def shift_unset(x, b):
        """Move configs (already masked to bit-b-set) to mask&~bit."""
        if b < 5:
            return x >> (1 << b)
        d = 1 << (b - 5)
        if d < Wd:
            return jnp.concatenate(
                [x[:, d:], jnp.zeros((H, d), u32)], axis=1)
        rs = (d // Wd) * SnP
        return jnp.concatenate(
            [x[rs:, :], jnp.zeros((rs, Wd), u32)], axis=0)

    def or_rows(x):
        """OR-fold over the state (sublane) axis WITHIN each sub-plane,
        broadcast back."""
        sh = 1
        while sh < SnP:
            if P == 1:
                x = x | jnp.roll(x, sh, axis=0)
            else:
                x = x | jnp.concatenate(
                    [jnp.roll(x[p * SnP:(p + 1) * SnP], sh, axis=0)
                     for p in range(P)], axis=0)
            sh *= 2
        return x

    # LAZY BIT RETIREMENT: retiring a slot never shifts the plane.  A
    # vacant slot's bit carries no information, so the prune at a
    # return keeps the linearized (bit-set) configs AND LEAVES THE BIT
    # SET — one AND, no cross-lane shift (the hardware only supports
    # those at static amounts).  The obligation moves to registration:
    # when a slot is (re)occupied, the two bit-halves are merged onto
    # the bit-clear side (exact — configs differing only in a
    # meaningless bit are the same config), so every occupant starts
    # from uniform bit 0.  First occupancy merges an all-zero half
    # (identity); crashed slots are registered once and never retired.

    def kernel(ev_ref, aux_ref, out_ref, fr,
               a1r, a2r, t0r, openr, flags):
        g = pl.program_id(0)
        g_iota = jax.lax.broadcasted_iota(jnp.int32, (H, Wd), 0)
        l_iota = jax.lax.broadcasted_iota(jnp.int32, (H, Wd), 1)
        # state row within a sub-plane, and the FULL-plane word index
        # (sub-plane offset folded in) — for P = 1 these reduce to the
        # classic s_iota / l_iota exactly
        s_iota = g_iota & (SnP - 1)
        w_iota = ((g_iota >> LOG_SNP) << LW) | l_iota

        @pl.when(g == 0)
        def _init():
            # initial state is index 0 (interned first) at mask 0
            fr[...] = jnp.where((g_iota == 0) & (l_iota == 0),
                                jnp.asarray(np.uint32(1), u32),
                                jnp.asarray(np.uint32(0), u32))
            for b in range(R):
                a1r[b] = jnp.uint32(0)
                a2r[b] = jnp.uint32(0)
                t0r[b] = 0
                openr[b] = 0
            flags[0] = 0
            flags[1] = -1

        def slot_pattern(sl):
            """Lacks-bit-sl pattern for a DYNAMIC slot: intra-word part
            from the aux table tail, word/split part from the
            full-plane word index."""
            ipat = aux_ref[0, 3 * UP + sl]
            sh = jnp.maximum(sl - 5, 0)
            wsel = (sl < 5) | (((w_iota >> sh) & 1) == 0)
            return jnp.where(wsel, ipat, jnp.asarray(np.uint32(0), u32))

        def expand_round(ltpv):
            """One Gauss-Seidel closure round: per open slot, linearize
            it on every config still lacking the target, accumulating
            straight into fr — later slots see earlier slots' children
            within the same round, so chains resolve in fewer rounds
            (monotone union either way; same fixpoint)."""
            for b in range(R):
                @pl.when(openr[b] == 1)
                def _(b=b):
                    f0 = fr[...]
                    src = (f0 & ltpv) & lackpat(b, w_iota)
                    a1b = a1r[b]
                    a2b = a2r[b]
                    dsel = msk(((a1b >> s_iota.astype(u32))
                                & jnp.uint32(1)) == 1)
                    moved = src & dsel
                    csel = msk(((a2b >> s_iota.astype(u32))
                                & jnp.uint32(1)) == 1)
                    red = or_rows(src & csel)
                    moved = moved | (red & msk(s_iota == t0r[b]))
                    fr[...] = f0 | shift_set(moved, b)

        def event(r, carry):
            @pl.when(flags[0] == 0)
            def _ev():
                # --- register the row's new invokes -------------------
                for i in range(I):
                    sl = ev_ref[0, 0, EB + r * I + i]

                    @pl.when(sl >= 0)
                    def _reg():
                        u = ev_ref[0, 0, EB + EB * I + r * I + i]
                        a1r[sl] = aux_ref[0, u]
                        a2r[sl] = aux_ref[0, UP + u]
                        t0r[sl] = aux_ref[0, 2 * UP + u].astype(jnp.int32)
                        openr[sl] = 1
                        # lazy-retirement merge: normalize the slot's
                        # (meaningless) bit to 0 across the plane
                        lp = slot_pattern(sl)
                        frv_i = fr[...]
                        low = frv_i & lp
                        high = frv_i & ~lp

                        @pl.when(sl < 5)
                        def _intra():
                            fr[...] = low | (
                                high >> (jnp.uint32(1)
                                         << jnp.minimum(sl, 4)
                                         .astype(u32)))

                        for b in range(5, R):
                            @pl.when(sl == b)
                            def _(b=b):
                                fr[...] = low | shift_unset(high, b)

                rs = ev_ref[0, 0, r]

                @pl.when(rs >= 0)
                def _ret():
                    # closure to fixpoint with early exit; a pure op
                    # directly legal on every lacking config is the
                    # identity on the plane (set-then-lazy-retire moves
                    # nothing) and cannot empty the frontier.
                    ltpv = slot_pattern(rs)
                    a2t = a2r[rs]
                    frv = fr[...]
                    lt = frv & ltpv
                    a1t = a1r[rs]
                    dselt = msk(((a1t >> s_iota.astype(u32))
                                 & jnp.uint32(1)) == 1)
                    n_lt = popsum(lt)
                    n_ill = popsum(lt & ~dselt)
                    fast = (a2t == jnp.uint32(0)) & (n_ill == 0)

                    @pl.when(jnp.logical_not(fast))
                    def _slow():
                        def cond(c):
                            prog, _, lack = c
                            return prog & (lack > 0)

                        def body(c):
                            _, prev, _ = c
                            expand_round(ltpv)
                            f1 = fr[...]
                            cnt = popsum(f1)
                            lack = popsum(f1 & ltpv)
                            return cnt > prev, cnt, lack

                        _, cnt, lack = jax.lax.while_loop(
                            cond, body,
                            (jnp.bool_(True), jnp.int32(-1), n_lt))
                        # prune configs that never linearized rs (bit
                        # stays set -- lazy retirement); the death test
                        # is FREE: the pruned count is cnt - lack from
                        # the last closure round
                        fr[...] = fr[...] & ~ltpv

                        @pl.when((cnt >= 0) & (cnt == lack))
                        def _dead():
                            flags[0] = 1
                            flags[1] = g * EB + r

                    openr[rs] = 0

            return carry

        jax.lax.fori_loop(0, EB, event, 0)
        out_ref[0, 0] = 1 - flags[0]
        out_ref[0, 1] = flags[1]

    def kern(evbuf, auxbuf):
        return pl.pallas_call(
            kernel,
            grid=(G,),
            in_specs=[
                # 3D with a unit middle axis: Mosaic requires the
                # block's last two dims to equal the array's
                pl.BlockSpec((1, 1, EBW), lambda g: (g, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 3 * UP + 16), lambda g: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, 2), lambda g: (0, 0),
                                   memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((1, 2), np.int32),
            scratch_shapes=[
                pltpu.VMEM((H, Wd), np.uint32),     # fr (P sub-planes)
                pltpu.SMEM((R,), np.uint32),        # a1r
                pltpu.SMEM((R,), np.uint32),        # a2r
                pltpu.SMEM((R,), np.int32),         # t0r
                pltpu.SMEM((R,), np.int32),         # openr
                pltpu.SMEM((2,), np.int32),         # flags
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(evbuf, auxbuf)

    return jax.jit(kern)


def _pad_g(g: int) -> int:
    """Grid-size bucketing (compiled-shape control): pow2 to 16, then
    8-multiples."""
    if g <= 1:
        return 1
    b = 1
    while b < g and b < 16:
        b *= 2
    return b if g <= 16 else ((g + 7) // 8) * 8


def pack_events(ret_t: np.ndarray, islot_t: np.ndarray,
                iuop_t: np.ndarray) -> tuple[np.ndarray, int]:
    """[Lp, 1] + [Lp, 1, I] register-delta tables (wgl_seg._pack_regs
    with K=1) -> (evbuf i32[G, EB*(1+2I)], G)."""
    Lp = ret_t.shape[0]
    I = islot_t.shape[2]
    G = _pad_g((Lp + EB - 1) // EB)
    L2 = G * EB
    ret = np.full(L2, -1, np.int32)
    ret[:Lp] = ret_t[:, 0]
    islot = np.full((L2, I), -1, np.int32)
    islot[:Lp] = islot_t[:, 0, :]
    iuop = np.zeros((L2, I), np.int32)
    iuop[:Lp] = iuop_t[:, 0, :]
    evbuf = np.concatenate(
        [ret.reshape(G, EB),
         islot.reshape(G, EB * I),
         iuop.reshape(G, EB * I)], axis=1)
    return np.ascontiguousarray(evbuf[:, None, :]), G


def pack_events_compact(ret_t: np.ndarray, islot_t: np.ndarray,
                        iuop_t: np.ndarray,
                        g_min: int = 1) -> tuple[np.ndarray, int]:
    """Compact wire twin of pack_events: the same event stream as a
    uint8 buffer — ret+1 u8[L2] (0 = the -1 sentinel; slot+1 <=
    deep_r_max+1 = 18, comfortably u8) ++ islot+1 u8[L2*I] ++ iuop u16-LE bytes[2*L2*I] — ~3.6x
    fewer bytes than the int32 form at I=2, rebuilt into the kernel's
    evbuf on device by _build_c's unpack prologue.  Padding iuops are
    clamped to 0: the kernel reads a row's uop only where its islot
    >= 0 (registration gate), so the clamp is unobservable.  `g_min`
    lets check_mesh pack a whole batch at one common grid size (the
    sentinel rows are exact no-ops)."""
    Lp = ret_t.shape[0]
    I = islot_t.shape[2]
    G = max(_pad_g((Lp + EB - 1) // EB), g_min)
    L2 = G * EB
    ret = np.zeros(L2, np.uint8)
    ret[:Lp] = (ret_t[:, 0].astype(np.int32) + 1).astype(np.uint8)
    islot = np.zeros((L2, I), np.uint8)
    islot[:Lp] = (islot_t[:, 0, :].astype(np.int32) + 1).astype(
        np.uint8)
    iuop = np.zeros((L2, I), np.uint16)
    iuop[:Lp] = np.maximum(
        iuop_t[:, 0, :].astype(np.int32), 0).astype(np.uint16)
    return np.concatenate([ret, islot.ravel(),
                           iuop.ravel().view(np.uint8)]), G


@functools.lru_cache(maxsize=32)
def _build_c(G: int, I: int, Wd: int, SnP: int, R: int, UP: int,
             P: int, interpret: bool):
    """Compact-wire wrapper around _build: jit-unpacks the uint8 event
    buffer of pack_events_compact back into the int32 evbuf on device
    (a few fused casts/reshapes, free next to the event walk) and runs
    the megakernel — the transfer carries the compact form.  `P` is the
    word-split sub-plane count (_build)."""
    import jax
    import jax.numpy as jnp

    kern = _build(G, I, Wd, SnP, R, UP, P, interpret)
    L2 = G * EB

    def fn(cbuf, auxbuf):
        ret = cbuf[:L2].astype(jnp.int32) - 1
        isl = cbuf[L2:L2 * (1 + I)].astype(jnp.int32) - 1
        pairs = cbuf[L2 * (1 + I):].reshape(L2 * I, 2)
        iu = (pairs[:, 0].astype(jnp.int32)
              | (pairs[:, 1].astype(jnp.int32) << 8))
        evbuf = jnp.concatenate(
            [ret.reshape(G, EB),
             isl.reshape(G, EB * I),
             iu.reshape(G, EB * I)], axis=1)[:, None, :]
        return kern(evbuf, auxbuf)

    return jax.jit(fn)


def pack_aux(a1t: np.ndarray, a2t: np.ndarray, t0t: np.ndarray,
             UP: int) -> np.ndarray:
    """[U] uop tables (wgl_seg._pack_uop_tables) -> u32[1, 3*UP+16]."""
    U = a1t.shape[0]
    aux = np.zeros((1, 3 * UP + 16), np.uint32)
    aux[0, :U] = a1t
    aux[0, UP:UP + U] = a2t
    aux[0, 2 * UP:2 * UP + U] = t0t.astype(np.uint32)
    for b in range(16):
        aux[0, 3 * UP + b] = _INTRA[b] if b < 5 else _FULL
    return aux


def _pad_u(u: int) -> int:
    b = 8
    while b < u:
        b *= 2
    return b


def dispatch_tables(ret_t, islot_t, iuop_t, a1t, a2t, t0t,
                    R: int, Sn: int, stats=None):
    """Asynchronously dispatch the deep kernel on pre-packed
    register-delta tables; returns the UN-FETCHED i32[1, 2] device
    verdict (alive, first-dead-row | -1).  A result fetch costs a fixed
    round trip that bounds any single-shot check from below, so
    steady-state callers dispatch many histories back-to-back and
    fetch once — the same pipelined formulation wgl_seg.check_pipeline
    uses."""
    import jax

    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise BackendUnavailable(
            f"no deep-kernel lowering for {backend}", backend=backend)
    I = islot_t.shape[2]
    UP = _pad_u(a1t.shape[0])
    cbuf, G = pack_events_compact(ret_t, islot_t, iuop_t)
    auxbuf = pack_aux(a1t, a2t, t0t, UP)
    if stats is not None:           # measured wire traffic (telemetry)
        stats["wire_bytes"] = (stats.get("wire_bytes", 0)
                               + cbuf.nbytes + auxbuf.nbytes)
    # past R_BASE the plane word-splits into P base-sized sub-planes
    # (ISSUE 10) — same kernel, factored mask axis
    P = planner.deep_split_planes(R)
    Wd = max(1, (1 << R) // 32 // P)
    kern = planner.compiled(
        "wgl_deep", (G, I, Wd, _snp(Sn), R, UP, P, backend),
        _build_c, G, I, Wd, _snp(Sn), R, UP, P,
        interpret=(backend == "cpu"))
    return kern(cbuf, auxbuf), G


def check_tables(ret_t, islot_t, iuop_t, a1t, a2t, t0t,
                 R: int, Sn: int) -> dict[str, Any]:
    """Run the deep kernel on pre-packed register-delta tables and
    fetch the verdict.  Returns {"valid?": bool, "failed_row":
    int | None, ...}; failed_row indexes ret_t's rows (callers map it
    to the returning op)."""
    dev, G = dispatch_tables(ret_t, islot_t, iuop_t, a1t, a2t, t0t,
                             R, Sn)
    out = np.asarray(dev)
    alive = bool(out[0, 0])
    res = {"valid?": alive,
           "failed_row": None if alive else int(out[0, 1]),
           "grid": G}
    P = planner.deep_split_planes(R)
    if P > 1:
        res["deep_variant"] = "word-split"
        res["shards"] = P
    return res


def map_witness(ret_t, fk, ops, failed_row):
    """Map a kernel-reported failing event row to the failing call's
    INVOKE op — the witness the oracle names (differentially pinned).
    Returns (op, op_index, return_position) or None when the scan
    carried no positions (pure-Python crash scans).  The ONE
    definition, shared by wgl_seg._check_deep and check_pipeline so
    the padded-row -> return-ordinal -> op arithmetic cannot drift."""
    if failed_row is None or fk.positions is None \
            or not len(fk.positions):
        return None
    ordinal = int((ret_t[:failed_row + 1, 0] >= 0).sum()) - 1
    if not (0 <= ordinal < len(fk.positions)):
        return None
    pos = int(fk.positions[ordinal])
    p = ops[pos].process
    inv = pos
    while inv >= 0 and not (ops[inv].process == p
                            and ops[inv].type == "invoke"):
        inv -= 1
    op = ops[max(inv, 0)]
    return op, (op.index if op.index is not None else max(inv, 0)), pos


def check_pipeline(model, histories, *, max_open_bits=None,
                   max_states: int = 64, stats=None,
                   mesh=None) -> list:
    """Steady-state deep-overlap checking: scan + pack every history on
    host, dispatch ALL kernels asynchronously, stack the [1, 2]
    verdicts ON DEVICE and fetch them in ONE round trip — the fixed D2H
    latency bounds any single-shot check from below, and this amortizes
    it over
    the batch exactly like wgl_seg.check_pipeline does for the shallow
    regime.  Verdict-identical to wgl_seg.check per history
    (differential battery).

    R <= planner.DEEP_R_BASE rides the classic resident plane; past it
    (to deep_r_max's single-device boundary) the SAME kernel runs with
    the plane word-split into base-sized sub-planes, so R = 15/16
    histories stay on-device instead of degrading to the serial chain
    (ISSUE 10).  `max_open_bits` defaults to that boundary.

    Histories OUTSIDE the kernel's scope (R past the boundary, crashed
    scans, undecomposable growth) do not poison the batch: with a
    `mesh`, stragglers within the hypercube envelope
    (R <= deep_r_max(backend, D)) verdict on the mask-sharded mesh
    engine first; past every device tier they ride wgl_seg.check's own
    fallback chain after the in-scope verdicts are fetched — so a
    mixed-depth batch (e.g. one R = 18 history among R <= 16 ones)
    still returns one correct verdict per history.  A device OOM on
    one history's dispatch demotes THAT history to the straggler chain
    (counted, never a poisoned batch or a silent wrong verdict).

    `stats`, when given a dict, receives the per-stage host-time
    decomposition (scan / tables / pack / dispatch / fetch / assemble
    seconds), mirroring wgl_seg.check_pipeline's."""
    import jax

    from jepsen_tpu import errors as errors_mod
    from jepsen_tpu.ops import wgl_seg

    spec = model.device_spec()
    if spec is None:
        raise BackendUnavailable(f"model {model!r} has no device spec")
    from jepsen_tpu import telemetry

    st = telemetry.Stages(stats)      # always collected
    stats = st.seconds
    backend = jax.default_backend()
    n_mesh = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    if max_open_bits is None:
        # scan up to everything ANY device tier can take; the serial
        # chain owns whatever scans out past that
        max_open_bits = planner.deep_r_max(backend, n_mesh)
    pend = []
    strag = []
    oom_demoted = 0
    results: list = [None] * len(histories)
    # shared interning across the batch: state enumeration, the
    # decomposition, and the uop tables are (re)built only when a
    # history grows the alphabet — not once per history
    seen: dict = {}
    rows: list = []
    U_at = -1
    Sn = 0
    tables = None            # (a1t, a2t, t0t)
    init = np.asarray(spec.encode(model), np.int32)
    for i, h in enumerate(histories):
        ops = h.ops
        with st.span("scan"):
            fk = wgl_seg._scan_history(h, ops, spec, seen, rows,
                                       max_open_bits, want_snaps=False,
                                       st=st)
        if not fk:
            strag.append(i)
            continue
        R = int(fk.max_open)
        with st.span("tables"):
            grown = False
            if len(rows) != U_at:
                uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
                try:
                    states, legal, next_state = \
                        wgl_seg._enumerate_states(spec, init, uops,
                                                  max_states)
                except wgl_seg.Unsupported:
                    # the alphabet (and with it the state space) only
                    # grows: everything from here on is a straggler —
                    # already-dispatched in-scope verdicts stay valid
                    telemetry.count_fallback("wgl_deep_pipeline",
                                             "state-space")
                    grown = True
                if not grown:
                    Sn = states.shape[0]
                    dw, cw, t0c = wgl_seg._decompose(legal, next_state)
                    # undecomposable models only grow less decomposable
                    grown = dw is None
                if not grown:
                    tables = wgl_seg._pack_uop_tables(legal, next_state,
                                                      dw, cw, t0c)
                    U_at = len(rows)
        if grown:
            strag.extend(range(i, len(histories)))
            break
        if not supported(R, Sn, len(rows), True, backend):
            strag.append(i)   # e.g. R past deep_r_max: straggler tiers
            continue
        I = min(2, R) if R else 1
        with st.span("pack"):
            if fk.deltas is not None:
                ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs_single(
                    fk, [fk.n_rets], R, len(rows), I)
            else:
                ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs(
                    [(0, fk)], 1, R, len(rows), I)
            a1t, a2t, t0t = tables
        try:
            with st.span("dispatch"):
                dev, G = dispatch_tables(ret_t, islot_t, iuop_t, a1t,
                                         a2t, t0t, R, Sn, stats=stats)
        except Exception as e:       # noqa: BLE001 - classified below
            if not errors_mod.is_oom(e):
                raise
            # a sub-plane stack this device cannot hold degrades THIS
            # history to the straggler chain — counted, never a
            # poisoned batch (ISSUE 10: no silent wrong verdict)
            oom_demoted += 1
            strag.append(i)
            continue
        pend.append((dev, i, fk, ret_t, ops, R, Sn, G))

    if pend:
        with st.span("fetch"):
            stacked = wgl_seg._build_stack(len(pend))(
                *[d for d, *_ in pend])
            outs = np.asarray(stacked)                # ONE fetch
        with st.span("assemble"):
            for j, (dev, i, fk, ret_t, ops, R, Sn_i, G) \
                    in enumerate(pend):
                alive = bool(outs[j, 0, 0])
                res = {"valid?": alive, "op_count": fk.n_calls,
                       "backend": backend, "engine": "wgl_deep",
                       "max_open": R, "states": Sn_i, "pipelined": True}
                P_i = planner.deep_split_planes(R)
                if P_i > 1:
                    res["deep_variant"] = "word-split"
                    res["shards"] = P_i
                if not alive:
                    res["anomaly"] = "nonlinearizable"
                    w = map_witness(ret_t, fk, ops, int(outs[j, 0, 1]))
                    if w is not None:
                        res["op"] = w[0].to_dict()
                        res["op_index"] = w[1]
                results[i] = res
    # in-scope verdicts carry the deep pipeline's plan + stage
    # decomposition BEFORE the stragglers run, so the serial chain's
    # verdicts keep their own engines' records
    from jepsen_tpu import telemetry as telemetry_mod
    R_pend = max(p[5] for p in pend) if pend else 0
    pipe_plan = planner.plan_engines(
        planner.Shape(kind="deep-pipeline", R=R_pend,
                      Sn=Sn or None, U=len(rows) or None,
                      decomposed=True, batch=len(histories),
                      mesh=n_mesh if mesh is not None else None,
                      max_states=max_states,
                      max_open_bits=max_open_bits),
        backend=backend)
    telemetry_mod.attach_dispatch(
        results,
        pipe_plan.record(engine="wgl_deep",
                         R=R_pend or None,
                         batch=len(histories),
                         stragglers=len(strag) or None,
                         oom_demoted=oom_demoted or None),
        stages=stats)
    st.flush()
    if oom_demoted:
        try:
            telemetry_mod.REGISTRY.counter(
                "jepsen_deep_oom_demotions_total").inc(oom_demoted)
        except Exception:       # noqa: BLE001 - telemetry is advisory
            pass
    for i in strag:
        if mesh is not None:
            # straggler tier 1 (ISSUE 10): the hypercube mask shard —
            # R past one device's stack but within 14 + log2(D)
            try:
                results[i] = check_hypercube(
                    model, [histories[i]], mesh,
                    max_states=max_states)[0]
                continue
            except CheckError:
                # out of the mesh envelope too: serial
                telemetry_mod.count_fallback("wgl_deep_hc",
                                             "mesh-envelope")
        try:
            results[i] = wgl_seg.check(model, histories[i],
                                       max_states=max_states,
                                       max_open_bits=max_open_bits)
            continue
        except wgl_seg.Unsupported:
            # beyond every batched gate (R past deep_r_max): the
            # serial frontier engine has no overlap-depth limit
            telemetry_mod.count_fallback("wgl_deep", "beyond-gates")
            why = ("deep straggler beyond every batched gate "
                   "(serial frontier engine)")
        except Exception as e:   # noqa: BLE001 - OOM-only degradation
            if not errors_mod.is_oom(e):
                raise
            # the single-history retry OOM'd again (wgl_seg routed it
            # back onto the deep kernel): the serial chain is the
            # total fallback, not a re-raise
            why = ("deep straggler after device OOM "
                   "(serial frontier engine)")
        from jepsen_tpu.ops import wgl
        results[i] = wgl.check(model, histories[i])
        telemetry_mod.attach_dispatch(
            [results[i]],
            telemetry_mod.dispatch_record(
                results[i].get("engine", "wgl"), why=why,
                fallback_chain=["wgl_cpu"], batch=1))
    return results


def check_mesh(model, histories, mesh, *, mesh_axis: str = "hists",
               max_open_bits=None,
               max_states: int = 64) -> list:
    """Deep-overlap scale-out over a jax.sharding.Mesh — TWO layouts
    behind one entry point (ISSUE 10):

      * histories within one device's plane stack (R <= the
        single-device deep_r_max, word-split included): one history
        per device (SURVEY.md §2.5), the embarrassingly parallel
        layout — every history's packed event buffer is padded to one
        common grid shape, stacked on a leading axis sharded over
        `mesh_axis`, and shard_map runs the kernel once per device
        with NO collectives (verdicts are independent; the [D, 2]
        output gathers on fetch).  Grid-padding rows are ret = -1 /
        islot = -1 no-op rows — exact, as in the pipelined path.
      * histories DEEPER than one device's stack (R up to
        14 + log2(D)): the batch routes to `check_hypercube`, which
        mask-shards each history's 2^R configuration plane across the
        whole mesh (any batch length; histories run one at a time,
        each using every device).

    Verdict-identical to check_pipeline per history; histories must
    all be in deep scope (callers route stragglers through
    check_pipeline instead)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu.ops import shard_map_compat, wgl_seg

    spec = model.device_spec()
    if spec is None:
        raise BackendUnavailable(f"model {model!r} has no device spec")
    backend = jax.default_backend()
    n_dev = int(np.prod(mesh.devices.shape))
    if max_open_bits is None:
        max_open_bits = planner.deep_r_max(backend, n_dev)
    r_dev = planner.deep_r_max(backend, 1)
    # Cheap pre-scan for the routing depth (the real scan below shares
    # interning); prep.max_open is exact and costs one host pass.
    from jepsen_tpu.ops import prep as prep_mod
    try:
        R_probe = max(prep_mod.prepare(h).max_open for h in histories)
    except Exception:            # noqa: BLE001 - scan decides below
        R_probe = 0
    if R_probe > r_dev:
        return check_hypercube(model, histories, mesh,
                               max_states=max_states,
                               max_open_bits=max_open_bits)
    if len(histories) != n_dev:
        raise CheckError(f"one history per device: got "
                         f"{len(histories)} histories, {n_dev} devices",
                         batch_size=len(histories), backend=backend)
    seen: dict = {}
    rows: list = []
    init = np.asarray(spec.encode(model), np.int32)
    fks = []
    for d, h in enumerate(histories):
        fk = wgl_seg._scan_history(h, h.ops, spec, seen, rows,
                                   max_open_bits, want_snaps=False)
        if not fk:
            raise CheckError("history out of deep-kernel scope (scan)",
                             history_index=d, backend=backend)
        fks.append(fk)
    uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
    states, legal, next_state = wgl_seg._enumerate_states(
        spec, init, uops, max_states)
    Sn = states.shape[0]
    dw, cw, t0c = wgl_seg._decompose(legal, next_state)
    if dw is None:
        raise CheckError("model not decomposable", backend=backend)
    a1t, a2t, t0t = wgl_seg._pack_uop_tables(legal, next_state,
                                             dw, cw, t0c)
    R = max(int(fk.max_open) for fk in fks)
    if not supported(R, Sn, len(rows), True, backend):
        raise CheckError(
            f"batch out of deep-kernel scope (R={R}, Sn={Sn})",
            backend=backend)
    I = min(2, R) if R else 1
    UP = _pad_u(a1t.shape[0])
    auxbuf = pack_aux(a1t, a2t, t0t, UP)
    tabs, rets = [], []
    for fk in fks:
        if fk.deltas is not None:
            ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs_single(
                fk, [fk.n_rets], R, len(rows), I)
        else:
            ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs(
                [(0, fk)], 1, R, len(rows), I)
        tabs.append((ret_t, islot_t, iuop_t))
        rets.append(ret_t)
    # one common grid size, then the COMPACT wire form per history
    # (sentinel rows are exact no-ops) — the mesh path ships the same
    # ~3.6x-smaller buffers as the pipelined path
    G_max = max(_pad_g((rt.shape[0] + EB - 1) // EB)
                for rt, _, _ in tabs)
    cbufs = [pack_events_compact(rt, it, ut, g_min=G_max)[0]
             for rt, it, ut in tabs]
    ev_all = np.stack(cbufs)                     # [D, nbytes] u8
    P = planner.deep_split_planes(R)
    Wd = max(1, (1 << R) // 32 // P)
    kern = _build_c(G_max, I, Wd, _snp(Sn), R, UP, P,
                    interpret=(backend == "cpu"))
    pspec = PartitionSpec(mesh_axis)
    _body = lambda ev, aux: kern(ev[0], aux)[None]  # noqa: E731
    # pallas_call's out_shape carries no varying-mesh-axes info and the
    # per-device program is trivially independent (no collectives), so
    # the vma check must be skipped rather than threaded through
    # the kernel builder (shard_map_compat).
    fn = shard_map_compat(_body, mesh=mesh,
                          in_specs=(pspec, PartitionSpec()),
                          out_specs=pspec)
    ev_sharded = jax.device_put(
        ev_all, NamedSharding(mesh, pspec))
    outs = np.asarray(fn(ev_sharded, jnp.asarray(auxbuf)))  # [D, 1, 2]
    results = []
    for d, fk in enumerate(fks):
        alive = bool(outs[d, 0, 0])
        res = {"valid?": alive, "op_count": fk.n_calls,
               "backend": backend, "engine": "wgl_deep",
               "max_open": int(fk.max_open), "states": int(Sn),
               "sharded": True}
        if P > 1:
            res["deep_variant"] = "word-split"
            res["shards"] = P
        if not alive:
            res["anomaly"] = "nonlinearizable"
            w = map_witness(rets[d], fk, histories[d].ops,
                            int(outs[d, 0, 1]))
            if w is not None:
                res["op"] = w[0].to_dict()
                res["op_index"] = w[1]
        results.append(res)
    from jepsen_tpu import telemetry as telemetry_mod
    mesh_plan = planner.plan_engines(
        planner.Shape(kind="deep-mesh", R=R, Sn=int(Sn),
                      U=len(rows), decomposed=True,
                      batch=len(histories), mesh=n_dev,
                      max_states=max_states),
        backend=backend)
    telemetry_mod.attach_dispatch(
        results,
        mesh_plan.record(
            engine="wgl_deep",
            R=R, batch=len(histories),
            mesh=dict(zip(mesh.axis_names, mesh.devices.shape))))
    return results


# ---------------------------------------------------------------------------
# Hypercube mask shard (ISSUE 10): one history's 2^R configuration
# plane partitioned across the device mesh by its TOP mask bits.
# ---------------------------------------------------------------------------

def _pad_events_flat(ret_t: np.ndarray, islot_t: np.ndarray,
                     iuop_t: np.ndarray):
    """Register-delta tables -> the flat int32 event arrays the
    hypercube engine walks (ret[L2], islot[L2, I], iuop[L2, I]),
    64-padded with ret = -1 / islot = -1 no-op rows (exact, as in
    pack_events)."""
    Lp = ret_t.shape[0]
    I = islot_t.shape[2]
    L2 = max(64, ((Lp + 63) // 64) * 64)
    ret = np.full(L2, -1, np.int32)
    ret[:Lp] = ret_t[:, 0]
    islot = np.full((L2, I), -1, np.int32)
    islot[:Lp] = islot_t[:, 0, :]
    iuop = np.zeros((L2, I), np.int32)
    iuop[:Lp] = np.maximum(iuop_t[:, 0, :].astype(np.int32), 0)
    return ret, islot, iuop, L2


def _build_hc(L2: int, I: int, Wdl: int, SnP: int, R: int, UP: int,
              devs: tuple, mesh_axis: str):
    """The hypercube-sharded deep engine: the SAME just-in-time
    linearization walk as `_build`, expressed as an XLA program under
    `shard_map` so the 2^R mask plane can span the mesh.  Device d
    holds full-plane words [d*Wdl, (d+1)*Wdl) — i.e. the top log2(D)
    mask bits ARE the device index.  Slot-bit geography per device:
    bits < 5 intra-word, [5, 5+log2(Wdl)) local word shifts, and
    [5+log2(Wdl), R) — the device bits — one deterministic pairwise
    `ppermute` with the hypercube partner d XOR 2^k per event round
    (shard_map_compat.hypercube_exchange).  The closure while_loop
    early-exits on the mesh-wide frontier counts (psum — every trip
    decision is uniform across devices, so the collectives inside the
    loop always rendezvous), exactly as `elle_mesh` detects its
    fixpoint.

    Trade disclosed in docs/deep-engine.md: events step at the XLA
    level (no Pallas megakernel fusion), so per-event overhead is
    higher than the resident-plane kernel — this variant exists for
    the R that does NOT FIT one device, not to race it.

    kern(ret i32[L2], islot i32[L2, I], iuop i32[L2, I],
         a1 u32[UP], a2 u32[UP], t0 i32[UP]) -> i32[D, 3]
    (alive, first-dead-row | -1, pairwise exchanges carried out)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    from jepsen_tpu.ops.shard_map_compat import (hypercube_exchange,
                                                 shard_map_compat)

    D = len(devs)
    SB = D.bit_length() - 1          # device (high) mask bits
    LW = Wdl.bit_length() - 1        # log2 words per device
    SUB0 = 5 + LW                    # first device bit
    assert (Wdl * D) << 5 == (1 << R), (Wdl, D, R)
    u32 = jnp.uint32
    FULL = np.uint32(_FULL)
    intra_np = np.array(list(_INTRA) + [_FULL], np.uint32)

    def body(ret, islot, iuop, a1, a2, t0):
        d = jax.lax.axis_index(mesh_axis).astype(jnp.int32)
        intra_tab = jnp.asarray(intra_np)
        s_iota = jax.lax.broadcasted_iota(jnp.int32, (SnP, Wdl), 0)
        l_iota = jax.lax.broadcasted_iota(jnp.int32, (SnP, Wdl), 1)
        w_iota = d * Wdl + l_iota    # FULL-plane word index

        def msk(c):
            return jnp.where(c, jnp.asarray(FULL, u32), jnp.uint32(0))

        def popsum(x):
            return jax.lax.population_count(x).astype(jnp.int32).sum()

        def gsum(x):
            return jax.lax.psum(x, mesh_axis)

        def lackpat(b):
            if b < 5:
                return jnp.full((SnP, Wdl), np.uint32(_INTRA[b]), u32)
            return msk(((w_iota >> (b - 5)) & 1) == 0)

        def shift_set(x, b):
            """Pre-masked to bit-b-clear configs -> mask|bit.  Device
            bits leave via the pairwise exchange (the sender masked
            the other side to zero, so the receive IS the move)."""
            if b < 5:
                return x << (1 << b)
            dd = 1 << (b - 5)
            if dd < Wdl:
                return jnp.concatenate(
                    [jnp.zeros((SnP, dd), u32), x[:, :Wdl - dd]],
                    axis=1)
            return hypercube_exchange(x, mesh_axis, b - SUB0, D)

        def shift_unset(x, b):
            if b < 5:
                return x >> (1 << b)
            dd = 1 << (b - 5)
            if dd < Wdl:
                return jnp.concatenate(
                    [x[:, dd:], jnp.zeros((SnP, dd), u32)], axis=1)
            return hypercube_exchange(x, mesh_axis, b - SUB0, D)

        def or_rows(x):
            sh = 1
            while sh < SnP:
                x = x | jnp.roll(x, sh, axis=0)
                sh *= 2
            return x

        def slot_pattern(sl):
            """Lacks-bit-sl for a DYNAMIC (traced) slot index."""
            ipat = intra_tab[jnp.minimum(jnp.maximum(sl, 0), 5)]
            sh = jnp.maximum(sl - 5, 0)
            wsel = (sl < 5) | (((w_iota >> sh) & 1) == 0)
            return jnp.where(wsel, ipat, jnp.uint32(0))

        fr0 = jnp.where((w_iota == 0) & (s_iota == 0),
                        jnp.uint32(1), jnp.uint32(0))

        def event(r, st):
            fr, a1r, a2r, t0r, openr, f0, f1, ex = st
            alive = f0 == 0
            # --- register the row's new invokes (lazy-retirement
            # merge normalizes the slot's meaningless bit to 0; for a
            # device bit that is one pairwise exchange) ---------------
            for i in range(I):
                sl = islot[r, i]
                do = alive & (sl >= 0)
                slc = jnp.maximum(sl, 0)
                u = iuop[r, i]
                a1r = a1r.at[slc].set(jnp.where(do, a1[u], a1r[slc]))
                a2r = a2r.at[slc].set(jnp.where(do, a2[u], a2r[slc]))
                t0r = t0r.at[slc].set(jnp.where(do, t0[u], t0r[slc]))
                openr = openr.at[slc].set(
                    jnp.where(do, 1, openr[slc]))
                lp = slot_pattern(sl)
                low = fr & lp
                high = fr & ~lp
                m = jnp.where(
                    do & (sl < 5),
                    low | (high >> (jnp.uint32(1)
                                    << jnp.minimum(slc, 4)
                                    .astype(u32))), fr)
                for b in range(5, SUB0):
                    m = jnp.where(do & (sl == b),
                                  low | shift_unset(high, b), m)
                for b in range(SUB0, R):
                    hit = do & (sl == b)
                    # the exchange itself runs unconditionally (every
                    # device must rendezvous); non-matching slots send
                    # zeros and discard the result
                    merged = hypercube_exchange(
                        jnp.where(hit, high, jnp.uint32(0)),
                        mesh_axis, b - SUB0, D)
                    ex = ex + jnp.where(hit, 1, 0)
                    m = jnp.where(hit, low | merged, m)
                fr = m

            # --- the row's return: closure to fixpoint + prune -------
            rs = ret[r]
            rsc = jnp.maximum(rs, 0)
            do_ret = alive & (rs >= 0)
            ltpv = slot_pattern(rsc)
            a1t_ = a1r[rsc]
            a2t_ = a2r[rsc]
            dselt = msk(((a1t_ >> s_iota.astype(u32))
                         & jnp.uint32(1)) == 1)
            lt = fr & ltpv
            n_lt = gsum(popsum(lt))
            n_ill = gsum(popsum(lt & ~dselt))
            fast = (a2t_ == jnp.uint32(0)) & (n_ill == 0)
            do_slow = do_ret & jnp.logical_not(fast)

            def expand(frv, exv):
                """One Gauss-Seidel closure round (the _build
                expand_round, device bits exchanged)."""
                for b in range(R):
                    opn = openr[b] == 1
                    f0v = frv
                    src = (f0v & ltpv) & lackpat(b)
                    a1b = a1r[b]
                    a2b = a2r[b]
                    dsel = msk(((a1b >> s_iota.astype(u32))
                                & jnp.uint32(1)) == 1)
                    moved = src & dsel
                    csel = msk(((a2b >> s_iota.astype(u32))
                                & jnp.uint32(1)) == 1)
                    red = or_rows(src & csel)
                    moved = moved | (red & msk(s_iota == t0r[b]))
                    if b >= SUB0:
                        contrib = hypercube_exchange(
                            jnp.where(opn, moved, jnp.uint32(0)),
                            mesh_axis, b - SUB0, D)
                        exv = exv + jnp.where(opn, 1, 0)
                        frv = frv | contrib
                    else:
                        frv = jnp.where(opn,
                                        f0v | shift_set(moved, b), frv)
                return frv, exv

            def cond(c):
                _, prog, _, lack, _ = c
                return prog & (lack > 0)

            def round_(c):
                frv, _, prev, _, exv = c
                frv, exv = expand(frv, exv)
                cnt = gsum(popsum(frv))
                lack = gsum(popsum(frv & ltpv))
                return frv, cnt > prev, cnt, lack, exv

            frv, _, cnt, lack, ex = jax.lax.while_loop(
                cond, round_,
                (fr, do_slow, jnp.int32(-1), n_lt, ex))
            # prune configs that never linearized rs (bit stays set —
            # lazy retirement); a fast (pure, everywhere-legal) return
            # is the identity on the plane, exactly as in _build
            fr = jnp.where(do_slow, frv & ~ltpv, frv)
            dead = do_slow & (cnt >= 0) & (cnt == lack)
            f1 = jnp.where((f0 == 0) & dead, r, f1)
            f0 = jnp.where(dead, 1, f0)
            openr = openr.at[rsc].set(
                jnp.where(do_ret, 0, openr[rsc]))
            return fr, a1r, a2r, t0r, openr, f0, f1, ex

        st = jax.lax.fori_loop(
            0, L2, event,
            (fr0, jnp.zeros(R, u32), jnp.zeros(R, u32),
             jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32),
             jnp.int32(0), jnp.int32(-1), jnp.int32(0)))
        f0, f1, ex = st[5], st[6], st[7]
        return jnp.stack([1 - f0, f1, ex])[None]

    mesh = Mesh(np.array(list(devs)), (mesh_axis,))
    rep = PartitionSpec()
    fn = shard_map_compat(body, mesh=mesh, in_specs=(rep,) * 6,
                          out_specs=PartitionSpec(mesh_axis))
    return jax.jit(fn)


def check_hypercube(model, histories, mesh, *,
                    mesh_axis: str = "cfg",
                    max_states: int = 64,
                    max_open_bits=None) -> list:
    """Verdict histories whose 2^R configuration plane exceeds one
    device's stack by mask-sharding it across `mesh`: the top log2(D)
    mask bits become the device index (D a power of two).  Each
    history runs as ONE sharded program over the whole mesh (histories
    at this depth are individually the bottleneck; the batch axis is a
    host loop).  Verdicts and witnesses are bit-identical to the
    serial-chain oracle (differential battery); `exchange_rounds` on
    each verdict counts the pairwise hypercube exchanges that carried
    data — the wire bill of the top-bit transitions."""
    import jax

    from jepsen_tpu.ops import wgl_seg

    spec = model.device_spec()
    if spec is None:
        raise BackendUnavailable(f"model {model!r} has no device spec")
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise BackendUnavailable(
            f"no deep-kernel lowering for {backend}", backend=backend)
    devs = list(mesh.devices.reshape(-1))
    D = len(devs)
    if D < 2 or (D & (D - 1)):
        raise CheckError(
            f"hypercube mask shard needs a power-of-2 device count "
            f">= 2, got {D}", backend=backend)
    rmax = planner.deep_r_max(backend, D)
    if max_open_bits is None:
        max_open_bits = rmax
    seen: dict = {}
    rows: list = []
    init = np.asarray(spec.encode(model), np.int32)
    fks = []
    for d, h in enumerate(histories):
        fk = wgl_seg._scan_history(h, h.ops, spec, seen, rows,
                                   max_open_bits, want_snaps=False)
        if not fk:
            raise CheckError("history out of deep-kernel scope (scan)",
                             history_index=d, backend=backend)
        fks.append(fk)
    uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
    try:
        states, legal, next_state = wgl_seg._enumerate_states(
            spec, init, uops, max_states)
    except wgl_seg.Unsupported as e:
        raise CheckError(str(e), backend=backend) from e
    Sn = states.shape[0]
    dw, cw, t0c = wgl_seg._decompose(legal, next_state)
    if dw is None:
        raise CheckError("model not decomposable", backend=backend)
    a1t, a2t, t0t = wgl_seg._pack_uop_tables(legal, next_state,
                                             dw, cw, t0c)
    R = max(int(fk.max_open) for fk in fks)
    if not supported(R, Sn, len(rows), True, backend, n_devices=D):
        raise CheckError(
            f"batch out of the hypercube deep envelope "
            f"(R={R}, Sn={Sn}, D={D})", backend=backend)
    if (1 << R) < 32 * D:
        raise CheckError(
            f"R={R} too shallow for a {D}-device mask shard "
            f"(need 2^R >= 32*D words)", backend=backend)
    Wdl = (1 << R) // 32 // D
    I = min(2, R) if R else 1
    UP = _pad_u(a1t.shape[0])
    U = a1t.shape[0]
    a1p = np.zeros(UP, np.uint32)
    a1p[:U] = a1t
    a2p = np.zeros(UP, np.uint32)
    a2p[:U] = a2t
    t0p = np.zeros(UP, np.int32)
    t0p[:U] = t0t
    from jepsen_tpu import telemetry as telemetry_mod
    hc_plan = planner.plan_engines(
        planner.Shape(kind="deep-mesh", R=R, Sn=int(Sn), U=len(rows),
                      decomposed=True, batch=len(histories), mesh=D,
                      max_states=max_states),
        backend=backend)
    if hc_plan.engine != "wgl_deep_hc":
        # hypercube forced BELOW the single-device boundary (caller
        # intent — differential batteries, explicit mesh routing): the
        # record names what actually ran, not the auto route
        hc_plan = hc_plan.refine(
            engine="wgl_deep_hc", deep_variant="hypercube", shards=D,
            exchange_rounds=D.bit_length() - 1,
            why=(f"hypercube mask shard forced over {D} devices "
                 "(caller intent; R within the single-device "
                 "envelope)"))
    results = []
    for hidx, fk in enumerate(fks):
        if fk.deltas is not None:
            ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs_single(
                fk, [fk.n_rets], R, len(rows), I)
        else:
            ret_t, islot_t, iuop_t, _ = wgl_seg._pack_regs(
                [(0, fk)], 1, R, len(rows), I)
        ret, islot, iuop, L2 = _pad_events_flat(ret_t, islot_t,
                                                iuop_t)
        t1 = time.monotonic()
        kern = planner.compiled(
            "wgl_deep_hc",
            (L2, I, Wdl, _snp(Sn), R, UP, tuple(devs)),
            _build_hc, L2, I, Wdl, _snp(Sn), R, UP,
            tuple(devs), mesh_axis)
        out = np.asarray(kern(ret, islot, iuop, a1p, a2p, t0p))
        alive = bool(out[0, 0])
        res = {"valid?": alive, "op_count": fk.n_calls,
               "backend": backend, "engine": "wgl_deep",
               "max_open": int(fk.max_open), "states": int(Sn),
               "sharded": True, "deep_variant": "hypercube",
               "shards": D, "exchange_rounds": int(out[0, 2]),
               "time_kernel_s": time.monotonic() - t1}
        if not alive:
            res["anomaly"] = "nonlinearizable"
            w = map_witness(ret_t, fk, histories[hidx].ops,
                            int(out[0, 1]))
            if w is not None:
                res["op"] = w[0].to_dict()
                res["op_index"] = w[1]
        results.append(res)
    telemetry_mod.attach_dispatch(
        results,
        hc_plan.record(
            engine="wgl_deep", R=R, batch=len(histories),
            deep_variant="hypercube", shards=D,
            mesh=dict(zip(mesh.axis_names, mesh.devices.shape))))
    return results
