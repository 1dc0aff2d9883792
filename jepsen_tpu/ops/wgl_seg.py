"""Segment-parallel linearizability check — the TPU-shaped fast path.

The serial frontier kernel (`ops.wgl`) walks return events one at a time
inside a `lax.while_loop`; its wall-clock is bounded by *serial depth*
(~one loop iteration per return event), which no accelerator can hide.
This module removes that bound for the common case — crash-free
histories over models with a small enumerable state space (registers,
mutexes: exactly the models behind `checker/linearizable` register
workloads, `tests/linearizable_register.clj:33`, `etcd.clj:157`) — by
reformulating the check as three data-parallel stages:

1. **Enumerate** the model's reachable states `Q` (|Q| = Sn) by closing
   the initial state under every distinct op in the history, and tabulate
   the transition relation `next[u, s] -> s'`, `legal[u, s]` for the U
   distinct ops.

2. **Cut** the history at *quiescent points* — moments with zero open
   calls — into K segments.  Linearizability is compositional across
   such cuts: every call is invoked and returned within one segment, so
   the only information flowing across a cut is the model state.  Each
   segment therefore defines a boolean *transfer matrix*
   `T_k[s0, s1] = "state s1 reachable at the cut after segment k, having
   entered with state s0"`.  All K×Sn transfer rows are computed **in
   parallel** (`vmap` over segments × start states): per (segment,
   start), the frontier is not a sorted list of configurations but a
   dense boolean tensor `fr[mask, state]` over (open-call bitmask ×
   model state) — per-event expansion, dedupe, pruning and slot
   retirement are O(2^R × Sn) masked gathers and tiny matmuls with *no
   sorting*.  Serial depth drops from #events to #events / K.

3. **Compose** the K transfer matrices left-to-right (K boolean
   matvecs): the history is linearizable iff a state survives all cuts.

Semantics are just-in-time linearization (Lowe / knossos :linear), same
as `ops.wgl`:

  * at the return of call `t`, the frontier is closed under linearizing
    any currently-open calls (to fixpoint — exact, monotone), then
    pruned to configurations containing `t`, then `t`'s slot is retired;
  * closure only at return events is complete: a window only closes at
    a return, so any linearization between returns can be deferred to
    the closure of the next return event.

Crashed (`:info`) calls — the reference's worst cost driver ("a couple
crashed processes can make the difference between seconds and days",
`doc/tutorial/06-refining.md:12-19`, `doc/tutorial/07-parameters.md:150-152`)
— are handled in three exact tiers (see _check_crashed_fast): inert
crashed calls (identity + always-legal, e.g. reads) are dropped
outright; up to `_MAX_CRASHED` remaining crashed calls ride the kernel
as permanent mask slots with a `J = Sn * 2^nc` entry-configuration axis
(cuts count open NORMAL calls only — "quiescent modulo crashed");
beyond the bound, a valid verdict on the crash-stripped history proves
validity at full speed (crashed calls carry no obligation).  Only the
residual case — many effect-bearing crashes on a history the stripped
pass cannot prove valid — falls back to the serial engines.

Scope guard: models whose state space does not close within
`max_states` (and the residual crash case above) raise `Unsupported`,
and callers fall back to `ops.wgl` / `ops.wgl_cpu`.

Verdict trust: both verdicts are exact (no frontier capacity exists to
overflow — the bitmap covers the whole configuration space).  On
invalid, the failing op is localized by re-running the CPU oracle on
the prefix through the first dead segment.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Optional

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu.history import History
from jepsen_tpu.ops.prep import PreparedHistory, prepare
from jepsen_tpu.ops.frontier import (make_plane_ops as _bit_ops,
                                     reshape_shift as _reshape_shift)

# Host-side planning (scanning, segmentation, slot assignment, state
# enumeration, decomposition) and the engine-routing decision live in
# ops.planner (ISSUE 8); every name is re-exported here for the
# long-standing `wgl_seg.<name>` callers and the differential
# batteries.  This module keeps the device kernels and entry points.
from jepsen_tpu.ops import planner
from jepsen_tpu.ops.planner import (  # noqa: F401 - re-exports
    _MAX_CRASHED, SegPlan, Unsupported, _FastKey, _RegsLayout,
    _StreamKey, _assign_slots, _cols_args, _compact_many_block,
    _compose_transfer, _decompose, _encode_calls, _enumerate_states,
    _expand_fn, _fast_scan, _fastkey_from_native, _fill_block_stream,
    _fk_arrays, _native_scan, _native_scan_cols, _native_scan_streams,
    _next_pow2, _pack_cand_tables, _pack_regs, _pack_regs_single,
    _pack_uop_tables, _pad_len, _regs_eligible, _regs_fill,
    _regs_fill_compact, _scan_history, _segment_ends,
    _segments_from_fk, _split_crashed, plan)

#: Events per scan-loop iteration in the fixed-round kernels (the
#: scan's pipelining depth).
SCAN_UNROLL = 4
#: Histories per check_pipeline dispatch group.
PIPE_GROUP = 4
#: Closure rounds check_pipeline's speculative pass runs (clamped to
#: the group's R); a death under fewer than R rounds re-runs exactly.
SPEC_ROUNDS = 2
#: Keys per double-buffered check_many dispatch chunk.
OVERLAP_CHUNK = 1024


# ---------------------------------------------------------------------------
# Device kernel — bit-packed mask axis
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_kernel_bits(K: int, L: int, C: int, Wd: int, Sn: int, R: int,
                       decomposed: bool, J: int, rounds: int = 0,
                       unroll: int = 1):
    """Bit-packed variant of the frontier kernel: the 2^R mask axis
    lives in the BITS of `Wd = max(1, 2^R/32)` uint32 words, so the
    frontier is `fr[Wd, Sn, J, K]` uint32 — 16-32x smaller than the
    dense 0/1 tensor, and every mask operation is a constant-pattern
    bitwise op:

      * configs lacking slot b (b<5):   x & _INTRA[b]
      * linearize slot b (set bit):     (x & _INTRA[b]) << 2^b
      * retire slot b (prune+clear):    (x & ~_INTRA[b]) >> 2^b
      * slots b>=5 shift whole words along the word axis instead.

    State transitions use the diagonal + rank-1 decomposition when
    available (any Sn), else an unrolled s->t select-OR (Sn <= 16);
    callers fall back to the dense bf16 kernel otherwise.

    `rounds > 0` replaces the dynamic closure `while_loop` with exactly
    `rounds` statically-unrolled expansion rounds.  `rounds = R` is
    EXACT: a closure sequence linearizes each open call at most once
    (its slot bit is set and never cleared until retirement), at most R
    calls are open, and round k unions in every config reachable by <= k
    linearizations — so the fixpoint is reached by round R.  Removing
    the data-dependent loop lets XLA fuse the whole event step and
    pipeline the scan (`unroll` events per loop iteration), which on a
    latency-bound chip beats early exit: the dynamic loop's per-round
    popcount condition costs more than the 2-3 "wasted" rounds."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    lacking, set_slot, retire_slot, sel32 = _bit_ops(Wd, R)

    def popcount(x):
        return jax.lax.population_count(x).astype(jnp.int32).sum()

    def wgl_seg_bits(ret_slot, cand_slot, cand_aux1, cand_aux2, cand_t0):
        # fr[w, s, j, k]; bit i of word w = mask index w*32+i.
        # Decomposed: aux1/aux2 = uint32 per-candidate state-bitmasks of
        # the diag/const weights (bit s set iff weight[s]); no device
        # gathers — all tables are host-precomputed per event.
        # Non-decomposed: aux1/aux2 = uint32 bitmasks of legality and a
        # packed next-state nibble table (4 bits per state, Sn <= 8) —
        # callers gate accordingly.
        if J == Sn:
            fr0 = jnp.zeros((Wd, Sn, J, K), u32).at[0].set(
                (jnp.eye(Sn, dtype=u32)[:, :, None]
                 * jnp.ones((1, 1, K), u32)))
        else:
            fr0 = jnp.zeros((Wd, Sn, J, K), u32).at[0, 0, 0, :].set(1)

        s_iota = jnp.arange(Sn, dtype=jnp.int32)

        def event(fr, ev):
            # Tables travel host->device in the narrowest dtype that fits
            # (int8 slots, uint8/16/32 bitmasks — fewer bytes to move);
            # upcast the per-event slices here.
            rs, cslot, aux1, aux2, ct0 = ev           # [K], then [K,C]x4
            rs = rs.astype(jnp.int32)
            cslot = cslot.astype(jnp.int32)
            aux1 = aux1.astype(u32)
            aux2 = aux2.astype(u32)
            ct0 = ct0.astype(jnp.int32)

            def expand_candidate(fr, c):
                """All legal single-linearizations of candidate c."""
                slot_kc = cslot[:, c]                  # [K]
                # contrib: configs lacking c's slot (select static slot
                # variant per segment/key)
                contrib = jnp.zeros_like(fr)
                for b in range(R):
                    contrib = contrib | (
                        lacking(fr, b) & sel32(slot_kc == b))
                # state transition s -> t
                if decomposed:
                    # [Sn, K] selects from per-candidate bitmasks
                    dsel = sel32(((aux1[:, c][None, :]
                                   >> s_iota[:, None]) & 1) == 1)
                    moved = contrib & dsel[None, :, None, :]  # identity
                    csel = sel32(((aux2[:, c][None, :]
                                   >> s_iota[:, None]) & 1) == 1)
                    red = contrib & csel[None, :, None, :]
                    # OR over s, place at t0
                    red = jax.lax.reduce(
                        red, np.uint32(0), jax.lax.bitwise_or, (1,))
                    at_t0 = sel32(s_iota[:, None] == ct0[None, :, c])
                    moved = moved | (red[:, None, :, :]
                                     & at_t0[None, :, None, :])
                else:
                    lsel = sel32(((aux1[:, c][None, :]
                                   >> s_iota[:, None]) & 1) == 1)
                    nxt = (aux2[:, c][None, :]
                           >> (4 * s_iota[:, None])) & 15   # [Sn, K]
                    moved = jnp.zeros_like(fr)
                    for s in range(Sn):
                        src = contrib[:, s] & lsel[None, s, None, :]
                        for t in range(Sn):
                            m_t = src & sel32(nxt[s] == t)[None, None, :]
                            moved = moved.at[:, t].set(moved[:, t] | m_t)
                # set the slot bit
                out = jnp.zeros_like(fr)
                for b in range(R):
                    out = out | (set_slot(moved, b) & sel32(slot_kc == b))
                return out

            # lacking-target pattern (zero for pad rows -> no rounds)
            def lack_target(fr):
                lt = jnp.zeros_like(fr)
                for b in range(R):
                    lt = lt | (lacking(fr, b) & sel32(rs == b))
                return lt & sel32(rs >= 0)[None, None, None, :]

            def one_round(fr):
                add = jnp.zeros_like(fr)
                for c in range(C):
                    add = add | expand_candidate(fr, c)
                return fr | add

            if rounds > 0:
                for _ in range(rounds):
                    fr = one_round(fr)
            else:
                def round_(carry):
                    fr, _, prev = carry
                    fr2 = one_round(fr)
                    cnt = popcount(fr2)
                    return (fr2,
                            (cnt > prev) & (popcount(lack_target(fr2)) > 0),
                            cnt)

                fr, _, _ = jax.lax.while_loop(
                    lambda cy: cy[1], round_,
                    (fr, popcount(lack_target(fr)) > 0, jnp.int32(-1)))

            # prune + retire the returning slot
            cleared = jnp.zeros_like(fr)
            for b in range(R):
                cleared = cleared | (retire_slot(fr, b) & sel32(rs == b))
            fr = jnp.where((rs >= 0)[None, None, None, :], cleared, fr)
            return fr, None

        fr, _ = jax.lax.scan(
            event, fr0, (ret_slot, cand_slot, cand_aux1, cand_aux2, cand_t0),
            unroll=unroll)
        # mask 0 = bit 0 of word 0
        return (fr[0] & 1).transpose(2, 1, 0)          # [K, J, Sn]

    return jax.jit(wgl_seg_bits)


@functools.lru_cache(maxsize=32)
def _build_kernel_regs(K: int, L: int, I: int, Wd: int, Sn: int, R: int,
                       decomposed: bool, rounds: int, unroll: int,
                       J: int = 1, nc: int = 0, rn: int = 0,
                       compose: bool = False, crash_closure: bool = False,
                       death_row: bool = False, sn_words: int = 1):
    """Register-delta variant of the bit-packed batch kernel (J=1 for
    independent whole histories; J=Sn computes per-segment transfer
    matrices for the single-history path, one lane per segment).

    The candidate-table kernel ships the FULL open-call set per return
    ([L, K, C] x 4 tables, ~23 MB for the 1M-op bench) even though the
    open set changes by ~one call per return, so the host->device
    transfer can outweigh the compute.  Here the device maintains the
    open set itself in per-slot registers (aux words [R, K] carried
    through the scan), and the host ships only the NEWLY-INVOKED calls
    per return row — at most I per row, with bursts spilling into
    virtual rows (ret_slot = -1: closure still runs there — a monotone
    union of configs the return row reaches anyway — but nothing is
    pruned or retired).  Transfer drops to [L', K] x (1 + 2I) bytes
    (~5.5 MB for the same bench).

    A second win falls out: candidates are now indexed BY slot, so the
    closure's per-candidate 2R slot-select masks disappear — slot b's
    expansion uses its static bit patterns directly.

    Closure semantics and the rounds=R exactness argument are identical
    to _build_kernel_bits (see its docstring); this builder only
    supports fixed rounds (callers gate R <= 6 to the candidate-table
    dynamic loop).  Transition tables are [U]-indexed on device (tiny
    per-step gathers) in the same decomposed / nibble forms.

    With `sn_words = W > 1` (the crash-relaxed tier's wide-state lift,
    VERDICT r3 #5): every per-state bitmask — the decomposed aux
    tables/registers, the epsilon-closure rows, the death_row seed —
    becomes W uint32 words, supporting Sn <= 32*W states; state row s
    reads word s // 32, bit s % 32.  W = 1 keeps the legacy
    single-word shapes bit-for-bit.

    With `death_row` (J = 1, one extra runtime arg `seed_mask`
    u32[W]):
    the frontier is seeded with the SET of states in seed_mask at mask
    0 (the composed verdict's reachable-entry mask) and the scan
    additionally reports the first row index at which the frontier
    empties (-1 = survives) — the per-return death localization the
    crash-relaxed refutation tier uses to name an exact witness op
    without any oracle.

    With `nc > 0` (crashed-call support, J = Sn * 2^nc): crashed calls
    hold permanent slots rn..rn+nc-1 — registered like invokes, never
    retired, free to linearize at any return's closure or never.  Lane
    entry/exit configurations become (crashed-linearized-mask x state)
    pairs: fr0 seeds one entry config per J index (j = cm * Sn + s,
    mask = cm << rn), and the output reads the 2^nc crashed-mask planes
    at zero normal bits, giving [K, J, 2^nc * Sn] transfer matrices.
    This removes the reference's worst scaling cliff — knossos treats a
    crashed op as concurrent with the entire rest of the history
    (doc/tutorial/06-refining.md:12-19); here it costs 2^nc extra
    frontier width instead of exponential search."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    lacking, set_slot, retire_slot, sel32 = _bit_ops(Wd, R)
    b_iota = np.arange(R, dtype=np.int32)[:, None]          # [R, 1]

    def wgl_seg_regs_scan(ret_slot, inv_slot, inv_uop, aux1_tab, aux2_tab,
                          t0_tab, *closure_args):
        # ret_slot [L, K] i8; inv_slot/inv_uop [L, K, I] i8/i16;
        # aux1_tab/aux2_tab [U] u32, t0_tab [U] i32.  With
        # crash_closure: closure_args = (crow i32 [L, K] row index,
        # ctab u32 [nC, Sn]) — per-state next-masks, reflexively and
        # transitively closed ON HOST, applied between expansion rounds
        # (see _relaxed_refute for the exactness argument).
        seed_mask = None
        if death_row:
            *closure_args, seed_mask = closure_args
        if crash_closure:
            crow_all, ctab = closure_args

            def close_states(fr, nm):
                # nm [K, Sn, W] u32: bit t%32 of word t//32 of
                # nm[k, s] = s->t allowed.  One gather + shift builds
                # the full [K, s2, t] allow tensor and a single
                # OR-reduction contracts the source-state axis — O(1)
                # HLO ops instead of the Sn^2 unrolled select-ORs that
                # made wide-state (Sn ~ 40) compiles take minutes.
                t_i = np.arange(Sn)
                words = nm[:, :, t_i // 32]          # [K, s2, t]
                sel = sel32(((words >> jnp.asarray(
                    t_i % 32, jnp.uint32)) & 1) == 1)
                contrib = (fr[:, :, None, :, :]
                           & sel.transpose(1, 2, 0)[None, :, :, None, :])
                return jax.lax.reduce(contrib, np.uint32(0),
                                      jax.lax.bitwise_or, (1,))
        if J > 1:
            # one lane per (segment, entry config): j = cm * Sn + s with
            # mask cm << rn (cm = 0 when nc = 0, reducing to the eye)
            fr0_np = np.zeros((Wd, 32, Sn, J), np.uint32)
            for cm in range(1 << nc):
                m0 = cm << rn
                for s in range(Sn):
                    fr0_np[m0 // 32, m0 % 32, s, cm * Sn + s] = 1
            fr0_np = (fr0_np << np.arange(32, dtype=np.uint32)
                      [None, :, None, None]).sum(1, dtype=np.uint32)
            fr0 = jnp.asarray(fr0_np)[..., None] * jnp.ones((K,), u32)
        elif death_row:
            # seed the single lane with every state in seed_mask
            # (u32[W]) at mask index 0 (bit 0 of word 0)
            si = np.arange(Sn)
            sm = jnp.asarray(seed_mask, u32)
            sb = ((sm[si // 32] >> jnp.asarray(si % 32, u32))
                  & 1).astype(u32)
            fr0 = jnp.zeros((Wd, Sn, 1, K), u32).at[0, :, 0, :].set(
                sb[:, None] * jnp.ones((K,), u32))
        else:
            fr0 = jnp.zeros((Wd, Sn, 1, K), u32).at[0, 0, 0, :].set(1)
        aw = (R, K) if sn_words == 1 else (R, K, sn_words)
        reg0 = (jnp.zeros(aw, u32), jnp.zeros(aw, u32),
                jnp.zeros((R, K), jnp.int32), jnp.zeros((R, K), bool))
        s_iota = jnp.arange(Sn, dtype=jnp.int32)

        def event(carry, ev):
            if death_row:
                fr, a1r, a2r, t0r, openr, row, dead = carry
            else:
                fr, a1r, a2r, t0r, openr = carry
            if crash_closure:
                rs, isl, iu, cr = ev
                nm = ctab[cr.astype(jnp.int32)]           # [K, Sn]
            else:
                rs, isl, iu = ev
            rs = rs.astype(jnp.int32)
            isl = isl.astype(jnp.int32)
            iu = iu.astype(jnp.int32)

            # --- register the row's new invokes -----------------------
            for i in range(I):
                u = iu[:, i]
                uc = jnp.clip(u, 0, None)
                m = (u >= 0)[None, :] & (isl[:, i][None, :] == b_iota)
                ma = m if sn_words == 1 else m[..., None]
                a1r = jnp.where(ma, aux1_tab[uc][None], a1r)
                a2r = jnp.where(ma, aux2_tab[uc][None], a2r)
                t0r = jnp.where(m, t0_tab[uc][None, :], t0r)
                openr = openr | m
            if crash_closure:
                # jumps BEFORE any linearization at this return
                fr = close_states(fr, nm)

            # --- closure: rounds x per-slot expansion -----------------
            for _ in range(rounds):
                add = jnp.zeros_like(fr)
                for b in range(R):
                    contrib = (lacking(fr, b)
                               & sel32(openr[b])[None, None, None, :])
                    if decomposed:
                        if sn_words == 1:
                            a1b = a1r[b][None, :]        # [1, K]
                            a2b = a2r[b][None, :]
                            sh = s_iota[:, None]
                        else:
                            # state row s reads word s//32, bit s%32
                            si = np.arange(Sn)
                            a1b = a1r[b].T[si // 32]     # [Sn, K]
                            a2b = a2r[b].T[si // 32]
                            sh = jnp.asarray(si % 32)[:, None]
                        dsel = sel32(((a1b >> sh) & 1) == 1)
                        moved = contrib & dsel[None, :, None, :]
                        csel = sel32(((a2b >> sh) & 1) == 1)
                        red = jax.lax.reduce(
                            contrib & csel[None, :, None, :],
                            np.uint32(0), jax.lax.bitwise_or, (1,))
                        at_t0 = sel32(s_iota[:, None] == t0r[b][None, :])
                        moved = moved | (red[:, None, :, :]
                                         & at_t0[None, :, None, :])
                    else:
                        lsel = sel32(((a1r[b][None, :]
                                       >> s_iota[:, None]) & 1) == 1)
                        nxt = (a2r[b][None, :]
                               >> (4 * s_iota[:, None])) & 15   # [Sn, K]
                        moved = jnp.zeros_like(fr)
                        for s in range(Sn):
                            src = contrib[:, s] & lsel[None, s, None, :]
                            for t in range(Sn):
                                m_t = src & sel32(nxt[s] == t)[None, None, :]
                                moved = moved.at[:, t].set(moved[:, t] | m_t)
                    add = add | set_slot(moved, b)
                fr = fr | add
                if crash_closure:
                    # jumps between consecutive linearizations
                    fr = close_states(fr, nm)

            # --- prune + retire the returning slot --------------------
            cleared = jnp.zeros_like(fr)
            for b in range(R):
                cleared = cleared | (retire_slot(fr, b) & sel32(rs == b))
            fr = jnp.where((rs >= 0)[None, None, None, :], cleared, fr)
            openr = openr & ~(rs[None, :] == b_iota)
            if death_row:
                alive = jax.lax.population_count(fr).astype(
                    jnp.int32).sum()
                dead = jnp.where((dead < 0) & (alive == 0), row, dead)
                return (fr, a1r, a2r, t0r, openr, row + 1, dead), None
            return (fr, a1r, a2r, t0r, openr), None

        xs = (ret_slot, inv_slot, inv_uop)
        if crash_closure:
            xs = xs + (closure_args[0],)
        carry0 = (fr0,) + reg0
        if death_row:
            carry0 = carry0 + (jnp.int32(0), jnp.int32(-1))
        (fr, *rest), _ = jax.lax.scan(event, carry0, xs,
                                      unroll=unroll)
        if death_row:
            return rest[-1]
        if nc == 0:
            out = (fr[0] & 1).transpose(2, 1, 0)       # [K, J, Sn]
        else:
            # read the 2^nc crashed-mask planes at zero normal bits
            planes = []
            for cm in range(1 << nc):
                m = cm << rn
                planes.append((fr[m // 32] >> np.uint32(m % 32)) & 1)
            outp = jnp.stack(planes)                   # [2^nc, Sn, J, K]
            out = outp.transpose(3, 2, 0, 1).reshape(
                K, J, (1 << nc) * Sn)                  # j' = cm*Sn + s
        if not compose:
            return out
        # On-device composition (single-history path): prefix products
        # of the per-segment transfer matrices via an associative scan
        # — log2(K) levels of batched [J, J] matmuls on the MXU —
        # instead of downloading [K, J, J] matrices and composing on
        # host.  The verdict comes back as SIX int32 words
        # (valid, first-dead-segment, 128-bit entry-config mask of the
        # dead segment): one fixed-latency fetch.  Exactness: boolean
        # matrix product is associative; `alive` is monotone (the empty
        # state set is absorbing), so sum(alive) IS the first dead
        # index; the entry mask = reachable configs at the cut BEFORE
        # the dead segment, which witness localization replays from.
        Tm = out.astype(jnp.float32)                   # [K, J, J]
        P = jax.lax.associative_scan(
            lambda a, b: (jnp.einsum("kij,kjl->kil", a, b) > 0)
            .astype(jnp.float32), Tm, axis=0)
        alive = (P[:, 0, :] > 0).any(axis=1)           # entry config 0
        valid = alive[-1]
        dead = jnp.where(valid, jnp.int32(-1),
                         jnp.sum(alive.astype(jnp.int32)))
        Jw = out.shape[1]
        reach = P[jnp.clip(dead - 1, 0, K - 1), 0, :] > 0   # [J]
        entry0 = jnp.zeros((Jw,), bool).at[0].set(True)
        entry = jnp.where(valid, False,
                          jnp.where(dead > 0, reach, entry0))
        em = [jnp.uint32(0)] * 4
        for j in range(min(Jw, 128)):
            em[j // 32] = em[j // 32] | (
                entry[j].astype(jnp.uint32) << np.uint32(j % 32))
        return jnp.stack(
            [valid.astype(jnp.int32), dead]
            + [jax.lax.bitcast_convert_type(w, jnp.int32) for w in em])

    return jax.jit(wgl_seg_regs_scan)


def _unpack_transfer_bufs(buf8, buf32, B: int, L: int, K: int, I: int,
                          U: int, wide_uop: bool):
    """Device-side unpack of the two transfer buffers into the six
    kernel tables (shared by the single-history and grouped builders —
    the buffer layout and the little-endian int16 reassembly live
    ONLY here).  buf8 holds B consecutive per-history blocks, each
    ret[L,K] i8 ++ islot[L,K,I] i8 ++ iuop[L,K,I] i8|i16; with B > 1
    the histories concatenate on the lane axis (ret [L, B*K], ...).
    buf32 = a1[U] ++ a2[U] ++ t0[U]."""
    import jax
    import jax.numpy as jnp

    n_ret = L * K
    n_islot = L * K * I
    n_iuop = L * K * I * (2 if wide_uop else 1)
    per = n_ret + n_islot + n_iuop
    blocks = buf8.reshape(B, per)

    def lanes(x):                    # [B, L, ...] -> [L, B*K, ...]
        x = jnp.moveaxis(x, 0, 1)
        return x.reshape((L, B * K) + x.shape[3:])

    ret = lanes(jax.lax.bitcast_convert_type(
        blocks[:, :n_ret], jnp.int8).reshape(B, L, K))
    islot = lanes(jax.lax.bitcast_convert_type(
        blocks[:, n_ret:n_ret + n_islot], jnp.int8).reshape(B, L, K, I))
    raw = blocks[:, n_ret + n_islot:per]
    if wide_uop:                     # little-endian int16 from 2 bytes
        pairs = raw.reshape(B, L, K, I, 2)
        lo = pairs[..., 0].astype(jnp.int32)
        hi = jax.lax.bitcast_convert_type(
            pairs[..., 1], jnp.int8).astype(jnp.int32)
        iuop = lanes(lo | (hi << 8))
    else:
        iuop = lanes(jax.lax.bitcast_convert_type(
            raw, jnp.int8).reshape(B, L, K, I))
    a1 = buf32[:U]
    a2 = buf32[U:2 * U]
    t0 = jax.lax.bitcast_convert_type(buf32[2 * U:3 * U], jnp.int32)
    return ret, islot, iuop, a1, a2, t0


@functools.lru_cache(maxsize=16)
def _build_kernel_regs_relaxed(K: int, L: int, I: int, Wd: int,
                               Sn: int, R: int, decomposed: bool,
                               rounds: int, unroll: int, U: int,
                               wide_uop: bool, nC: int,
                               sn_words: int = 1):
    """Packed composed kernel under RELAXED crash semantics: crashed
    ops are position-dependent epsilon-transitions whose reflexive-
    transitive closures ride as a [nC, Sn] uint32 table (appended to
    buf32); each event row carries an i16 index into it (appended to
    buf8).  nC is bucket-padded by the caller so shapes recompile
    rarely.  Output = the same int32[6] composed verdict."""
    import jax
    import jax.numpy as jnp

    kern = _build_kernel_regs(K, L, I, Wd, Sn, R, decomposed,
                              rounds=rounds, unroll=unroll, J=Sn,
                              nc=0, rn=0, compose=True,
                              crash_closure=True, sn_words=sn_words)
    n_crow = L * K * 2               # i16
    W = sn_words

    def wgl_seg_regs_relaxed(buf8, buf32):
        base = len(buf8) - n_crow
        if W == 1:
            tabs = _unpack_transfer_bufs(buf8[:base], buf32[:3 * U],
                                         1, L, K, I, U, wide_uop)
        else:
            # wide-state aux layout: a1[U,W] ++ a2[U,W] ++ t0[U]
            na = U * W
            t8 = _unpack_transfer_bufs(
                buf8[:base],
                jnp.zeros(3 * U, jnp.uint32), 1, L, K, I, U, wide_uop)
            tabs = t8[:3] + (
                buf32[:na].reshape(U, W),
                buf32[na:2 * na].reshape(U, W),
                jax.lax.bitcast_convert_type(
                    buf32[2 * na:2 * na + U], jnp.int32))
        pairs = buf8[base:].reshape(L, K, 2)
        lo = pairs[..., 0].astype(jnp.int32)
        hi = jax.lax.bitcast_convert_type(
            pairs[..., 1], jnp.int8).astype(jnp.int32)
        crow = lo | (hi << 8)
        aux_n = 3 * U if W == 1 else 2 * U * W + U
        ctab = buf32[aux_n:].reshape(nC, Sn, W)
        return kern(*tabs, crow, ctab)

    return jax.jit(wgl_seg_regs_relaxed)


@functools.lru_cache(maxsize=32)
def _build_kernel_regs_packed(K: int, L: int, I: int, Wd: int, Sn: int,
                              R: int, decomposed: bool, rounds: int,
                              unroll: int, J: int, nc: int, rn: int,
                              U: int, wide_uop: bool):
    """Packed-transfer wrapper around the composed register kernel: the
    six host tables travel as TWO buffers (one uint8 for the [L, K(, I)]
    event tables, one uint32 for the [U] transition tables) instead of
    six separate device_puts, each of which pays a fixed latency.
    Unpacking is
    free on device (bitcasts + reshapes fused into the kernel)."""
    import jax

    kern = _build_kernel_regs(K, L, I, Wd, Sn, R, decomposed,
                              rounds=rounds, unroll=unroll, J=J, nc=nc,
                              rn=rn, compose=True)

    def wgl_seg_regs(buf8, buf32):
        return kern(*_unpack_transfer_bufs(buf8, buf32, 1, L, K, I, U,
                                           wide_uop))

    return jax.jit(wgl_seg_regs)




@functools.lru_cache(maxsize=32)
def _build_kernel_regs_group_c(B: int, K: int, L: int, Wd: int,
                               Sn: int, R: int, decomposed: bool,
                               rounds: int, unroll: int, U: int,
                               Rp: int):
    """Grouped composed kernel over the COMPACT wire format (I = 1):
    B histories' blocks travel as ONE uint8 buffer, each carrying the
    segment-major row STREAMS of _regs_fill_compact instead of padded
    [L, K] tables; the padded tables are rebuilt on device with one
    masked gather per table (table[l, k] = stream[cum[k] + l] where
    l < rows_k, sentinel otherwise) — a few fused [L, K] gathers, free
    next to the event scan, while the transfer carries ~10x fewer bytes
    than padded tables would.  The
    per-segment transfer matrices are composed per history by a
    batched associative scan; output is int32 [B, 6] (valid,
    first-dead, 128-bit entry mask)."""
    import jax
    import jax.numpy as jnp

    J = Sn
    ub = 1 if U <= 255 else 2
    per = Rp * (1 + ub) + 4 * (K + 1)
    kern = _build_kernel_regs(B * K, L, 1, Wd, Sn, R, decomposed,
                              rounds=rounds, unroll=unroll, J=J,
                              nc=0, rn=0, compose=False)
    l_iota = np.arange(L, dtype=np.int32)[:, None]      # [L, 1]

    def wgl_seg_pipeline_regs(buf8, buf32):
        blocks = buf8.reshape(B, per)
        cum = jax.lax.bitcast_convert_type(
            blocks[:, Rp * (1 + ub):].reshape(B, K + 1, 4),
            jnp.int32)                                   # [B, K+1]
        start = cum[:, :K]                               # [B, K]
        nrows = cum[:, 1:] - start                       # [B, K]
        idx = jnp.clip(start[:, None, :] + l_iota[None], 0, Rp - 1)
        live = l_iota[None] < nrows[:, None, :]          # [B, L, K]
        b_ix = jnp.arange(B)[:, None, None]
        rows8 = jnp.where(live, blocks[:, :Rp][b_ix, idx],
                          jnp.uint8(0)).astype(jnp.int32)
        ret = (rows8 & 15) - 1
        islot = (rows8 >> 4) - 1
        if ub == 1:
            iu = blocks[:, Rp:2 * Rp].astype(jnp.int32)
        else:
            pairs = blocks[:, Rp:3 * Rp].reshape(B, Rp, 2)
            iu = (pairs[..., 0].astype(jnp.int32)
                  | (pairs[..., 1].astype(jnp.int32) << 8))
        iuop = jnp.where(live, iu[b_ix, idx], jnp.int32(0))
        # liveness rides islot's -1 sentinel (the kernel registers a
        # slot only where islot == b), so iuop needs no sentinel

        def lanes(x):                    # [B, L, K] -> [L, B*K, 1]
            return jnp.moveaxis(x, 0, 1).reshape(L, B * K, 1)

        a1 = buf32[:U]
        a2 = buf32[U:2 * U]
        t0 = jax.lax.bitcast_convert_type(buf32[2 * U:3 * U], jnp.int32)
        out = kern(lanes(ret)[..., 0], lanes(islot), lanes(iuop),
                   a1, a2, t0)                           # [B*K, J, J]
        Tm = out.reshape(B, K, J, J).astype(jnp.float32)
        P = jax.lax.associative_scan(
            lambda a, b: (jnp.einsum("bkij,bkjl->bkil", a, b) > 0)
            .astype(jnp.float32), Tm, axis=1)
        alive = (P[:, :, 0, :] > 0).any(axis=-1)     # [B, K]
        valid = alive[:, -1]
        dead = jnp.where(valid, jnp.int32(-1),
                         jnp.sum(alive.astype(jnp.int32), axis=1))
        idx2 = jnp.clip(dead - 1, 0, K - 1)          # [B]
        reach = P[jnp.arange(B), idx2, 0, :] > 0     # [B, J]
        entry0 = jnp.zeros((B, J), bool).at[:, 0].set(True)
        entry = jnp.where(valid[:, None], False,
                          jnp.where((dead > 0)[:, None], reach, entry0))
        em = jnp.zeros((B, 4), jnp.uint32)
        for j in range(min(J, 128)):
            em = em.at[:, j // 32].set(
                em[:, j // 32]
                | (entry[:, j].astype(jnp.uint32) << np.uint32(j % 32)))
        return jnp.concatenate(
            [valid.astype(jnp.int32)[:, None], dead[:, None],
             jax.lax.bitcast_convert_type(em, jnp.int32)], axis=1)

    return jax.jit(wgl_seg_pipeline_regs)


@functools.lru_cache(maxsize=32)
def _build_kernel_regs_many_c(K: int, L: int, Wd: int, Sn: int, R: int,
                              decomposed: bool, rounds: int,
                              unroll: int, U: int, Rp: int,
                              donate: bool = False):
    """Compact-wire twin of check_many's J=1 register kernel (I = 1):
    the whole key batch travels as ONE uint8 buffer of key-major row
    streams (rows u8[Rp]: ret+1 | (islot+1)<<4; iuop u8|u16[Rp]; cum
    i32[K+1]) and the padded [L, K] tables are rebuilt on device by
    masked gathers — the multi-key bench's padded tables were ~3x the
    stream bytes.  Output
    [K, 1, Sn] like the padded form.

    `donate=True` donates the per-chunk event buffer (arg 0) to the
    executable so the double-buffered executor's chunk k buffer is
    reclaimed as chunk k+1 transfers — every dispatch re-packs a fresh
    host buffer, so an OOM retry never touches a consumed donation.
    (Callers gate it off the 'cpu' backend, where XLA ignores donation
    with a warning.)"""
    import jax
    import jax.numpy as jnp

    kern = _build_kernel_regs(K, L, 1, Wd, Sn, R, decomposed,
                              rounds=rounds, unroll=unroll, J=1,
                              nc=0, rn=0, compose=False)
    ub = 1 if U <= 255 else 2
    l_iota = np.arange(L, dtype=np.int32)[:, None]      # [L, 1]

    def wgl_seg_batch_regs(buf8, buf32):
        cum = jax.lax.bitcast_convert_type(
            buf8[Rp * (1 + ub):].reshape(K + 1, 4), jnp.int32)
        start = cum[:K]
        nrows = cum[1:] - start
        idx = jnp.clip(start[None, :] + l_iota, 0, Rp - 1)  # [L, K]
        live = l_iota < nrows[None, :]
        rows8 = jnp.where(live, buf8[:Rp][idx],
                          jnp.uint8(0)).astype(jnp.int32)
        ret = (rows8 & 15) - 1
        islot = ((rows8 >> 4) - 1)[:, :, None]
        if ub == 1:
            iu_s = buf8[Rp:2 * Rp].astype(jnp.int32)
        else:
            pairs = buf8[Rp:3 * Rp].reshape(Rp, 2)
            iu_s = (pairs[:, 0].astype(jnp.int32)
                    | (pairs[:, 1].astype(jnp.int32) << 8))
        iuop = jnp.where(live, iu_s[idx], jnp.int32(0))[:, :, None]
        a1 = buf32[:U]
        a2 = buf32[U:2 * U]
        t0 = jax.lax.bitcast_convert_type(buf32[2 * U:3 * U],
                                          jnp.int32)
        return kern(ret, islot, iuop, a1, a2, t0)

    return (jax.jit(wgl_seg_batch_regs, donate_argnums=(0,)) if donate
            else jax.jit(wgl_seg_batch_regs))




# ---------------------------------------------------------------------------
# Device kernel — dense bf16 (fallback for huge non-decomposable models)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_kernel(K: int, L: int, C: int, M: int, Sn: int, R: int,
                  decomposed: bool = False, J: Optional[int] = None):
    """Transfer-matrix kernel: [K, Sn, Sn] from padded segment tables.

    Manually batched for TPU vector units — no nested vmap:

      * the frontier is ONE tensor `fr[M, Sn, J, K]` over (open-call
        bitmask, model state, start state, segment), with the largest
        axis (segments) trailing so elementwise work vectorizes across
        the 128-lane VPU;
      * the dynamic mask-bit shifts (linearize-candidate, retire-slot)
        are decomposed into R statically-unrolled reshape shifts
        selected per segment/candidate — no device gathers;
      * closure uses Lowe's early-stop rule: expand only while some
        configuration still lacks the returning call AND the frontier
        grew; exact at quiescent cuts because every call's own return
        forces its linearization decision within the segment.

    All ops are 0/1 floats (union = saturating add, intersect =
    multiply); the state transition is a (k,c)-batched one-hot matmul.
    """
    import jax
    import jax.numpy as jnp

    # J = Sn computes full transfer matrices (segment rows of one long
    # history); J = 1 tracks only the model's initial state (independent
    # whole histories, one per row — the multi-key batch mode).
    if J is None:
        J = Sn
    Mhalf = [(M >> (b + 1), 1 << b) for b in range(R)]  # (hi, lo) per bit

    def shift_set_bit(x, b):
        """x[..., m, s, j, k] -> y where y[m | 1<<b] = x[m], y[m w/o bit]=0."""
        return _reshape_shift(x, *Mhalf[b], set_bit=True)

    def shift_clear_bit(x, b):
        """x -> y where y[m w/o bit] = x[m | 1<<b], y[m with bit] = 0."""
        return _reshape_shift(x, *Mhalf[b], set_bit=False)

    bf16 = jnp.bfloat16  # 0/1 indicator tensors and small-int sums only

    def wgl_seg_dense(ret_slot, cand_slot, cand_uop, legal, next_state,
                      diag_w, const_w, const_t0):
        # ret_slot [L, K]; cand_slot/cand_uop [L, K, C];
        # legal [U, Sn] bool; next_state [U, Sn] i32;
        # diag_w/const_w f32 [U, Sn], const_t0 i32 [U] (decomposed only)
        legal_t = legal.astype(bf16)
        if decomposed:
            diag_t = diag_w.astype(bf16)
            cw_t = const_w.astype(bf16)
            onehot0_t = jax.nn.one_hot(const_t0, Sn, dtype=bf16)  # [U, Sn]
        else:
            trans_t = (jax.nn.one_hot(next_state, Sn, dtype=bf16)
                       * legal_t[..., None])                 # [U, Sn, Sn]

        # fr[m, s, j, k]: start state j reaches (mask m, state s) in seg k
        if J == Sn:
            eye = jnp.eye(Sn, dtype=bf16)
            fr0 = jnp.zeros((M, Sn, J, K), bf16).at[0].set(
                eye[:, :, None] * jnp.ones((1, 1, K), bf16))
        else:
            # single start: the model's initial state (index 0 by
            # construction — _enumerate_states interns it first)
            fr0 = jnp.zeros((M, Sn, J, K), bf16).at[0, 0, 0, :].set(1)

        def event(fr, ev):
            rs, cslot, cuop = ev                             # [K], [K,C], [K,C]
            ju = jnp.clip(cuop, 0, None)
            live = (cuop >= 0).astype(bf16)                  # [K, C]
            legal_c = legal_t[ju] * live[..., None]          # [K, C, Sn]

            miota = jnp.arange(M, dtype=jnp.int32)
            bitc = jnp.int32(1) << jnp.clip(cslot, 0, None)  # [K, C]
            # lacks[c, m, k]: mask m lacks candidate c's slot (seg k)
            lacks = ((miota[None, :, None] & bitc.T[:, None, :]) == 0
                     ).astype(bf16)                          # [C, M, K]

            bt = jnp.int32(1) << jnp.clip(rs, 0, None)       # [K]
            # live target only for real events: pad rows do zero rounds
            lack_t = (((miota[:, None] & bt[None, :]) == 0) &
                      (rs >= 0)[None, :]).astype(jnp.float32)  # [M, K]

            def lacking_any(fr):
                return (fr.astype(jnp.float32).sum(axis=(1, 2))
                        * lack_t).sum()

            def round_(carry):
                fr, _, prev = carry
                # contrib[c, m, s, j, k] — legality folded into the
                # transition weights below
                contrib = fr[None] * lacks[:, :, None, None, :]
                if decomposed:
                    # moved = diag part + rank-1 part (all transitions
                    # with a changed state target one state t0 per op)
                    a = (diag_t[ju] * live[..., None]).transpose(1, 2, 0)
                    b_ = (cw_t[ju] * live[..., None]).transpose(1, 2, 0)
                    o0 = onehot0_t[ju].transpose(1, 2, 0)    # [C, Sn, K]
                    diag_part = contrib * a[:, None, :, None, :]
                    red = (contrib * b_[:, None, :, None, :]).sum(axis=2)
                    const_part = (red[:, :, None, :, :]
                                  * o0[:, None, :, None, :])
                    moved = diag_part + const_part           # [C,M,Sn,J,K]
                else:
                    contrib = contrib * legal_c.transpose(1, 2, 0)[
                        :, None, :, None, :]
                    trans_c = trans_t[ju]                    # [K, C, Sn, Sn]
                    if Sn <= 16:
                        # Unrolled select-add stays in the elementwise
                        # pipeline — the batched-einsum form forces large
                        # transposes every closure round.
                        cols = []
                        for t in range(Sn):
                            acc_t = None
                            for s in range(Sn):
                                w = trans_c[:, :, s, t].T[:, None, None, :]
                                term = contrib[:, :, s] * w  # [C, M, J, K]
                                acc_t = term if acc_t is None else acc_t + term
                            cols.append(acc_t)
                        moved = jnp.stack(cols, axis=2)      # [C,M,Sn,J,K]
                    else:
                        moved = jnp.einsum("cmsjk,kcst->cmtjk",
                                           contrib, trans_c)
                # Set candidate c's bit.  Shifts are linear, so select the
                # candidates for each bit FIRST (sum over c), then do one
                # static shift per bit.
                add = jnp.zeros_like(fr)
                for b in range(R):
                    sel = (cslot == b).astype(bf16)          # [K, C]
                    moved_b = (moved
                               * sel.T[:, None, None, None, :]).sum(0)
                    add = add + shift_set_bit(moved_b, b)
                fr2 = jnp.minimum(fr + add, jnp.asarray(1, bf16))
                cnt = fr2.astype(jnp.float32).sum()
                return fr2, (cnt > prev) & (lacking_any(fr2) > 0), cnt

            fr, _, _ = jax.lax.while_loop(
                lambda c: c[1], round_,
                (fr, lacking_any(fr) > 0, jnp.float32(-1.0)))

            # prune configs that never linearized the returning call and
            # retire its slot: keep only has-bit rows, moved to the
            # cleared index (shift_clear_bit does both at once)
            cleared = jnp.zeros_like(fr)
            for b in range(R):
                sel = (rs == b).astype(bf16)                 # [K]
                cleared = cleared + shift_clear_bit(fr, b) * sel
            fr = jnp.where((rs >= 0)[None, None, None, :], cleared, fr)
            return fr, None

        fr, _ = jax.lax.scan(event, fr0, (ret_slot, cand_slot, cand_uop))
        # At a quiescent cut every slot is retired: only mask 0 is live.
        return fr[0].transpose(2, 1, 0)                      # [K, J, Sn]

    return jax.jit(wgl_seg_dense)


def _dispatch_kernel(K, L, C, M, Sn, R, J, ret_t, cslot_t, cuop_t,
                     legal, next_state, diag_w, const_w, const_t0):
    """Pick the kernel flavour — uint32 bitmap (decomposable or tiny
    state spaces) vs dense bf16 — build it, and assemble its argument
    list.  Shared by check() and check_many() so the gating and the
    argument plumbing cannot diverge.  Returns (kern, args, n_sharded):
    args[0] is [L, K], args[1:n_sharded] are [L, K, C] (key axis
    shardable over a mesh); the rest are replicated tables."""
    decomposed = diag_w is not None
    use_bits = (decomposed and Sn <= 32) or (not decomposed and Sn <= 8)
    if use_bits:
        # Fixed-round unrolled closure + scan pipelining (see
        # _build_kernel_bits: rounds=R is exact).  Deep-concurrency
        # batches (R beyond typical workload concurrency) keep the
        # dynamic while_loop: the static body is O(R * C * R)
        # full-tensor ops per round x R rounds x unroll, which at R near
        # max_open_bits compiles huge HLO and wastes rounds the early
        # exit would skip.
        rounds, unroll = (0, 1) if R > 6 else (int(R), SCAN_UNROLL)
        kern = _build_kernel_bits(K, int(L), int(C), max(1, M // 32),
                                  int(Sn), int(R), decomposed, J=J,
                                  rounds=rounds, unroll=unroll)
        aux1, aux2, t0c = _pack_cand_tables(
            cuop_t, legal, next_state, diag_w, const_w, const_t0)
        return kern, [ret_t.astype(np.int8), cslot_t.astype(np.int8),
                      aux1, aux2, t0c], 5
    kern = _build_kernel(K, int(L), int(C), int(M), int(Sn), int(R),
                         decomposed, J=J)
    U = legal.shape[0]
    dummy2 = np.zeros((U, Sn), np.float32)
    dummy1 = np.zeros(U, np.int32)
    return kern, [ret_t, cslot_t, cuop_t, legal, next_state,
                  diag_w if decomposed else dummy2,
                  const_w if decomposed else dummy2,
                  const_t0 if decomposed else dummy1], 3


def _shard_args(mesh, mesh_axis: str, args: list, n_sharded: int):
    """Shard _dispatch_kernel's argument list over the mesh: args[0] is
    [L, K], args[1:n_sharded] are [L, K, C] (K = lane axis), the rest
    replicated tables.  One definition so the layout contract cannot
    diverge between check() and check_many()."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shard_k = NamedSharding(mesh, P(None, mesh_axis))
    shard_kc = NamedSharding(mesh, P(None, mesh_axis, None))
    repl = NamedSharding(mesh, P())
    shardings = ([shard_k] + [shard_kc] * (n_sharded - 1)
                 + [repl] * (len(args) - n_sharded))
    return [jax.device_put(a, sh) for a, sh in zip(args, shardings)]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _device_call(st: telemetry.Stages, engine: str, fn, args: list, *,
                 lanes: int, padded: int, put=None) -> np.ndarray:
    """Call `fn` on the host arrays `args` and fetch its result: the
    trace-only parts of the caller's open `kernel` span.  With `put`
    (e.g. a mesh sharding) the arrays go to the device first, as
    `kernel.put`; without it the call transfers them itself, which
    measured ~0.2 ms a request cheaper than an explicit put on a v5e
    host.  Tallies the call, its used and padded lanes and its wire
    bytes on `st`."""
    if put is not None:
        with telemetry.trace("kernel.put"):
            args_in = put(args)
    else:
        args_in = args
    with telemetry.trace("kernel.call"):
        out = fn(*args_in)
    with telemetry.trace("kernel.fetch"):
        out = np.asarray(out)
    st.tally(telemetry.DEVICE_CALLS, engine=engine)
    st.tally(telemetry.LANES, lanes, engine=engine, kind="used")
    st.tally(telemetry.LANES, padded, engine=engine, kind="padded")
    st.tally(telemetry.WIRE_BYTES, sum(a.nbytes for a in args),
             engine=engine, direction="h2d")
    st.tally(telemetry.WIRE_BYTES, out.nbytes, engine=engine,
             direction="d2h")
    return out


def _run_seg_regs(seg_fk: list, K: int, R: int, U: int, Sn: int, M: int,
                  legal, next_state, diag_w, const_w, const_t0,
                  mesh, mesh_axis, nc: int = 0, rn: int = 0,
                  compose: bool = True, tables=None,
                  st: Optional[telemetry.Stages] = None):
    """Run the register-delta kernel over per-segment lanes with
    J = Sn * 2^nc entry configurations (nc = crashed-call count).
    Returns (T, lanes, sharded, dead_segment) — shared by the
    plan()-based and fast-scan single-history paths.  Stage seconds go
    to `st`: `tables` (uop tables), `pack` (lane tables and the wire
    buffers), `kernel` (the device call and its fetch).

    Unsharded with compose=True (the default), the per-segment transfer
    matrices are composed ON DEVICE and only (valid, first-dead) come
    back — T is None and dead_segment is set (-1 = valid).  Sharded
    runs keep the host composition (T comes back, dead_segment None):
    every device computes its segment slice and only the [K, J, J]
    matrices cross the ICI/host boundary.  (Multi-history pipelining
    does not come through here — check_pipeline has its own grouped
    dispatch.)"""
    st = telemetry.Stages() if st is None else st
    sharded = False
    K_run = K
    if mesh is not None and mesh_axis is not None:
        # pad the segment axis up to a mesh-size multiple: all-padding
        # lanes (ret -1, no invokes) are identity transfer matrices
        m = int(mesh.shape[mesh_axis])
        K_run = ((K + m - 1) // m) * m
        sharded = True
    I = min(2, R) if R else 1
    decomposed = diag_w is not None
    with st.span("tables"):
        a1t, a2t, t0t = _pack_uop_tables(
            legal, next_state, diag_w, const_w, const_t0)
    with st.span("pack"):
        if tables is not None and not sharded and K_run == K:
            ret_t, islot_t, iuop_t, Lp = tables
        else:
            ret_t, islot_t, iuop_t, Lp = _pack_regs(
                [(k, fk) for k, fk in enumerate(seg_fk)], K_run, R,
                int(U), I)
        if not sharded and compose:
            # two transfer buffers instead of six tables (one fixed
            # put latency each): u8 event tables ++ u32 uop tables
            args = [np.concatenate([ret_t.view(np.uint8).ravel(),
                                    islot_t.view(np.uint8).ravel(),
                                    iuop_t.view(np.uint8).ravel()]),
                    np.concatenate([a1t, a2t, t0t.view(np.uint32)])]
        else:
            args = [ret_t, islot_t, iuop_t, a1t, a2t, t0t]
    with st.span("kernel"):
        if not sharded and compose:
            fn = _build_kernel_regs_packed(
                int(K_run), int(Lp), I, max(1, M // 32), int(Sn), R,
                decomposed, R, SCAN_UNROLL, int(Sn) << nc, nc, rn,
                int(a1t.shape[0]), iuop_t.dtype == np.int16)
            # [6]: valid, dead, entry mask x4
            vd = _device_call(st, "wgl_seg", fn, args, lanes=K,
                              padded=0)
            return None, K_run, False, vd
        kern = _build_kernel_regs(K_run, int(Lp), I, max(1, M // 32),
                                  int(Sn), R, decomposed,
                                  rounds=R, unroll=SCAN_UNROLL,
                                  J=int(Sn) << nc, nc=nc, rn=rn)
        put = None
        if sharded:
            def put(a):
                return _shard_args(mesh, mesh_axis, a, 3)
        T = _device_call(st, "wgl_seg", kern, args, lanes=K,
                         padded=K_run - K, put=put)[:K] > 0.5  # [K, J, J]
    return T, K_run, sharded, None


@functools.lru_cache(maxsize=8)
def _build_stack(n: int):
    import jax
    import jax.numpy as jnp

    def wgl_seg_stack(*xs):
        return jnp.stack(xs)

    return jax.jit(wgl_seg_stack)


def _localize_segment(model, spec, ops, fk, seg_ends, dead: int,
                      mask_words, states) -> Optional[dict]:
    """Exact witness localization confined to the DEAD segment: replay
    only that segment's ops through the CPU oracle, once per reachable
    entry state (the device's composed verdict carries the entry-config
    mask).  The quiescent-cut composition argument makes this exact:
    configs before the cut are summarized entirely by the reachable
    state set, so the first op at which EVERY entry-state replay has
    died is the global witness (the union config set empties there).
    Returns the oracle result of the last-surviving replay (its op /
    op_index / final-paths ARE the analysis artifacts), or None when
    out of scope (no positions, no decode, crashed-path J-configs) —
    callers fall back to the whole-prefix oracle."""
    if fk.positions is None or getattr(spec, "decode", None) is None:
        return None
    from jepsen_tpu.ops import wgl_cpu

    end_ret = int(seg_ends[dead]) - 1
    start_pos = (int(fk.positions[int(seg_ends[dead - 1]) - 1]) + 1
                 if dead > 0 else 0)
    end_pos = int(fk.positions[end_ret])
    # Quiescent cuts count OK-open calls only, so FAIL pairs may
    # straddle either boundary; an unpaired half inside the slice
    # would read to the oracle as a crashed (maybe-linearizable) call
    # and could shift the witness.  A failed call is never linearized,
    # so dropping the stray halves is exact.
    seg_ops = []
    open_at: dict = {}               # process -> seg_ops index of its
    for o in ops[start_pos:end_pos + 1]:    # currently-open invoke
        p = o.process
        if type(p) is int and p >= 0:
            if o.type == "invoke":
                open_at[p] = len(seg_ops)
            elif p not in open_at:
                continue             # completion of a pre-slice invoke
            else:
                del open_at[p]
        seg_ops.append(o)
    if open_at:                      # invokes completing post-slice:
        drop = set(open_at.values())  # drop exactly those invokes
        seg_ops = [o for i, o in enumerate(seg_ops) if i not in drop]
    Sn = states.shape[0]
    entry = [j for j in range(Sn)
             if (int(mask_words[j // 32]) >> (j % 32)) & 1]
    if not entry:
        return None
    # ONE union walk seeded with every reachable entry state: its
    # witness (the first return at which the union config set empties)
    # is the whole-history witness by construction — separate
    # per-entry-state replays would die at different RETURN events and
    # picking among them by op_index (an INVOKE index) is wrong.
    o = wgl_cpu.check(None, History(seg_ops),
                      initial_models=[spec.decode(states[j])
                                      for j in entry])
    if o.get("valid?") is not False:
        return None              # disagreement with the device verdict
    return o




def _relaxed_refute(model, spec, history, ops, drop, crashed,
                    crash_uop, inert, seen, rows, states, legal,
                    next_state, *, max_open_bits,
                    target_returns_per_segment, backend_name,
                    localize, st):
    """Tier 4 — SOUND REFUTATION under relaxed crash semantics.

    Over-approximate every crashed call as an unlimited-use epsilon
    transition available from its invoke position onward: any true
    linearization uses each crashed op at most once at some point
    after its invoke, and each such use is one allowed jump — so the
    relaxed config set contains the true one at every index, and
    RELAXED-INVALID implies truly invalid.  (Relaxed-valid proves
    nothing; callers fall through to the exact serial engines.)

    This closes the reference's worst asymmetry: knossos's cost
    explodes with crashed-op count precisely when refuting
    (doc/tutorial/06-refining.md:12-19), while here availability is a
    FUNCTION OF POSITION, not config state — availability only grows,
    so the host precomputes one reflexive-transitive closure matrix
    per crash-prefix and the kernel applies the row's closure between
    expansion rounds.  Cost: +Sn^2 selects per round, zero extra
    config width, any number of crashes.

    Witness: the composed verdict localizes the dead segment; its last
    return's original index is reported as `witness_bound_index` (the
    true witness is at or before it — the relaxed config set dies no
    earlier than the true one).  With localize=True a capped oracle
    attempt upgrades the bound to the exact op when it finishes."""
    Sn = states.shape[0]
    if Sn > 64:
        return None                  # closure masks cap at two words
    W = 1 if Sn <= 32 else 2         # uint32 words per state bitmask
    eff = [(ip, u) for (ip, cp, o), ine, u in
           zip(crashed, inert, crash_uop) if not ine]
    if any(u < 0 for _, u in eff):
        return None                  # unencodable crashed op
    if not eff:
        return None                  # nothing non-inert: not our tier
    if len(eff) > 32767:
        return None                  # crow rides as int16

    # Stripped history as columns (cheap when the run journaled them)
    packed = history.packed_columns() if isinstance(history, History) \
        else None
    keep = np.nonzero(~drop)[0]
    if packed is not None and packed.vkind is not None:
        stripped_pk = packed.take(keep)
    else:
        from jepsen_tpu.history import pack_history
        stripped_pk = pack_history(
            History([ops[i] for i in keep]))
    U0 = len(rows)
    fk = _native_scan_cols(stripped_pk, spec, seen, rows,
                           max_open_bits)
    if not fk or fk.n_calls == 0 or fk.deltas is None \
            or len(rows) != U0:
        return None
    R = int(fk.max_open)
    diag_w, const_w, const_t0 = _decompose(legal, next_state)
    # one shared gate, width-aware: the wide (W=2) lift is decomposed-
    # only, so the nibble form never widens
    if not _regs_eligible(R, U0, Sn, diag_w is not None,
                          sn_cap=32 * W) \
            or (W > 1 and diag_w is None):
        return None
    cuts = np.asarray(fk.cuts, np.int32)
    if len(cuts) != fk.n_rets or cuts[-1] != 1:
        return None
    seg_ends = _segment_ends(cuts, target_returns_per_segment)
    I = min(2, R) if R else 1
    lay = _RegsLayout(fk, seg_ends, I)
    Lp = _pad_len(lay.lp_min)
    K = lay.k
    ret_t, islot_t, iuop_t = _regs_fill(lay, Lp, K, U0, I)

    # Availability: #effective crashes invoked before each return's
    # ORIGINAL position -> index into the prefix-closure table.
    crash_pos = np.asarray([ip for ip, _ in eff], np.int64)
    orig_ret_pos = keep[np.asarray(fk.positions, np.int64)]
    crow_ret = np.searchsorted(crash_pos, orig_ret_pos,
                               side="left").astype(np.int16)
    crow_t = np.zeros((Lp, K), np.int16)
    crow_t[lay.rho, lay.ret_key] = crow_ret

    # Prefix reflexive-transitive closures (numpy boolean matmuls).
    nC = len(eff) + 1
    C = np.eye(Sn, dtype=bool)
    ctab_rows = [C]
    for _, u in eff:
        rel = np.zeros((Sn, Sn), bool)
        lg = legal[u].astype(bool)
        rel[np.arange(Sn)[lg], next_state[u][lg]] = True
        C = C | rel
        while True:
            C2 = C | (C @ C)
            if (C2 == C).all():
                break
            C = C2
        ctab_rows.append(C)
    nC_pad = _pad_len(nC)
    ctab = np.zeros((nC_pad, Sn, W), np.uint32)

    def _rows_to_words(M):
        out = np.zeros((Sn, W), np.uint32)
        for sw in range(W):
            lo, hi = sw * 32, min((sw + 1) * 32, Sn)
            pw = (1 << np.arange(hi - lo, dtype=np.uint64)) \
                .astype(np.uint64)
            out[:, sw] = (M[:, lo:hi].astype(np.uint64)
                          * pw).sum(1).astype(np.uint32)
        return out

    ctab[:] = _rows_to_words(np.eye(Sn, dtype=bool))  # padding: identity
    for c, M in enumerate(ctab_rows):
        ctab[c] = _rows_to_words(M)

    a1t, a2t, t0t = _pack_uop_tables(
        legal, next_state, diag_w, const_w, const_t0, sn_words=W)
    # unroll=1: the closure adds Sn^2 selects per round and the scan
    # body would otherwise blow up XLA compile time; the refutation
    # path runs once per suspect history, not in the steady-state loop
    unroll = 1
    wide = iuop_t.dtype == np.int16
    buf8 = np.concatenate([ret_t.view(np.uint8).ravel(),
                           islot_t.view(np.uint8).ravel(),
                           iuop_t.view(np.uint8).ravel(),
                           crow_t.view(np.uint8).ravel()])
    buf32 = np.concatenate([a1t.ravel(), a2t.ravel(),
                            t0t.view(np.uint32), ctab.ravel()])
    with st.span("kernel"):
        fn = _build_kernel_regs_relaxed(
            K, int(Lp), I, max(1, (1 << R) // 32), int(Sn), R,
            diag_w is not None, R, unroll, U0, wide, int(nC_pad),
            sn_words=W)
        vd = _device_call(st, "wgl_seg_relaxed", fn, [buf8, buf32],
                          lanes=K, padded=0)
    if int(vd[0]) == 1:
        return None                  # relaxed-valid: proves nothing
    dead = int(vd[1])
    seg_lo = int(seg_ends[dead - 1]) if dead > 0 else 0
    # Exact relaxed-death localization, NO oracle (VERDICT r3 #3):
    # re-run the relaxed kernel over the dead segment ALONE (its table
    # columns already exist), seeded with the composed verdict's
    # reachable entry-state mask, tracking the first row at which the
    # frontier empties.  Exactness of the point: the relaxed config
    # set OVER-approximates the true one at every index, so the return
    # it names is the first op at which even the relaxation is
    # impossible — the true witness is at or before it, and on
    # violations that are not themselves crash-explainable (e.g. a
    # value no write OR crashed write ever carried) the two coincide
    # (differentially asserted).
    bound_pos = int(orig_ret_pos[int(seg_ends[dead]) - 1])
    loc_kern = _build_kernel_regs(
        1, int(Lp), I, max(1, (1 << R) // 32), int(Sn), R,
        diag_w is not None, rounds=R, unroll=1, J=1,
        compose=False, crash_closure=True, death_row=True,
        sn_words=W)
    seed = (vd[2:2 + max(W, 2)].astype(np.int64)
            & 0xFFFFFFFF).astype(np.uint32)[:W] \
        if W > 1 else np.asarray(
            [np.int64(vd[2]) & 0xFFFFFFFF], np.uint32)
    with st.span("kernel"):
        drow = int(_device_call(
            st, "wgl_seg_relaxed", loc_kern,
            [np.ascontiguousarray(ret_t[:, dead:dead + 1]),
             np.ascontiguousarray(islot_t[:, dead:dead + 1]),
             np.ascontiguousarray(iuop_t[:, dead:dead + 1]), a1t, a2t,
             t0t, np.ascontiguousarray(crow_t[:, dead:dead + 1]), ctab,
             seed], lanes=1, padded=0))
    if drow >= 0:
        local = int((ret_t[:drow + 1, dead] >= 0).sum()) - 1
        g = seg_lo + local
        if 0 <= g < len(orig_ret_pos):
            bound_pos = int(orig_ret_pos[g])
    p = ops[bound_pos].process
    inv = bound_pos
    while inv >= 0 and not (ops[inv].process == p
                            and ops[inv].type == "invoke"):
        inv -= 1
    bound_op = ops[max(inv, 0)]
    bound_idx = (bound_op.index if bound_op.index is not None
                 else max(inv, 0))
    result: dict[str, Any] = {
        "valid?": False,
        "op_count": fk.n_calls + len(crashed),
        "backend": backend_name,
        "engine": "wgl_seg",
        "anomaly": "nonlinearizable",
        "refutation": "crash-relaxed",
        "crashed": len(crashed),
        "dead_segment": dead,
        "op": bound_op.to_dict(),
        "op_index": bound_idx,
        "witness": "relaxed-exact" if drow >= 0 else "segment-bound",
        "witness_bound_index": bound_idx,
    }
    if localize:
        # the capped oracle now only upgrades ARTIFACTS (final-paths /
        # configs) and, when it finishes, the true minimal witness —
        # the exact relaxed-death op above is always reportable
        from jepsen_tpu.ops import wgl_cpu
        with telemetry.checker_stage("wgl_seg", "witness"):
            oracle = wgl_cpu.check(model, history,
                                   **wgl_cpu.ARTIFACT_CAPS)
        if oracle.get("valid?") is False:
            for key in ("op", "op_index", "final-paths", "configs"):
                if key in oracle:
                    result[key] = oracle[key]
        else:
            result["final-paths-error"] = wgl_cpu.artifacts_error(oracle)
    return result


def _check_crashed_fast(model, spec, history, *, max_states,
                        max_open_bits, target_returns_per_segment,
                        localize, mesh, mesh_axis, backend_name, st):
    """Crash-bearing histories on the segment-parallel engine, in three
    exact tiers (a crashed call may be linearized at any point after
    its invoke, or never — `doc/tutorial/06-refining.md:12-19`):

      1. *Inert-crash dropping.*  A crashed call whose op is identity
         and always-legal on every reachable state (e.g. a read: its
         result is unknown, so it constrains nothing) can be removed
         outright — linearizing it changes no configuration, and no
         witness is obliged to linearize it.  Exact in both directions.
      2. *Bounded crash kernel.*  If <= _MAX_CRASHED non-inert crashed
         calls remain, the register-delta kernel carries them as
         permanent mask slots (J = Sn * 2^nc entry configurations; see
         _build_kernel_regs).  Exact.
      3. *Crash-stripped validity proof.*  Beyond the bound, check the
         history with ALL crashed calls removed: crashed calls carry no
         obligation, so a linearization that never linearizes one is a
         linearization of the full history — stripped-valid => valid,
         at full engine speed for ANY number of crashes.  A stripped-
         invalid verdict proves nothing (a crashed write may need to
         take effect), so it returns None and callers fall back to the
         serial engines, which handle crashes exactly.
    """
    from jepsen_tpu.ops.wgl import _generic_encode_op

    ops = history.ops if isinstance(history, History) else \
        History(history).ops
    split = _split_crashed(ops)
    if split is None:
        return None
    drop, crashed = split
    if not crashed:
        return None              # scan failed for a non-crash reason

    stripped = [o for pos, o in enumerate(ops) if not drop[pos]]
    seen: dict = {}
    rows: list = []
    with st.span("scan"):
        fk = _native_scan(stripped, spec, seen, rows, max_open_bits)
        path = "objects"
        if fk is False:
            path = "python"
            fk = _fast_scan(stripped, spec, seen, rows, max_open_bits)
    st.tally(telemetry.SCAN_KEYS, path=path)
    if fk is None:
        return None              # stripped key still out of scope

    # Intern the crashed ops alongside the stripped key's ops so the
    # state space closes over BOTH, then classify inertness.
    crash_uop = []
    INT32 = 2 ** 31
    for _, _, o in crashed:
        fc, av, bv, okv = _generic_encode_op(o, spec.f_codes)
        if fc < 0 or not (-INT32 <= av < INT32 and -INT32 <= bv < INT32):
            crash_uop.append(-1)     # unencodable: never inert
            continue
        key = (fc, av, bv, okv)
        u = seen.get(key)
        if u is None:
            u = seen[key] = len(rows)
            rows.append(key)
        crash_uop.append(u)
    uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
    init = np.asarray(spec.encode(model), np.int32)
    try:
        with st.span("tables"):
            states, legal, next_state = _enumerate_states(
                spec, init, uops, max_states)
    except Unsupported:
        telemetry.count_fallback("wgl_seg_crash_fast", "state-space")
        return None
    eye = np.arange(legal.shape[1])
    inert = [u >= 0 and bool(legal[u].all())
             and bool((next_state[u] == eye).all())
             for u in crash_uop]

    n_inert = sum(inert)
    if len(crashed) - n_inert <= _MAX_CRASHED:
        # Exact: drop only the inert crashed calls; the bounded kernel
        # carries the rest.
        if n_inert:
            red_drop = np.zeros(len(ops), bool)
            for (ip, cp, _), isin in zip(crashed, inert):
                if isin:
                    red_drop[ip] = True
                    if cp >= 0:
                        red_drop[cp] = True
            reduced = [o for pos, o in enumerate(ops)
                       if not red_drop[pos]]
        else:
            reduced = ops
        res = _check_fast(
            model, spec, History(reduced), max_states=max_states,
            max_open_bits=max_open_bits,
            target_returns_per_segment=target_returns_per_segment,
            localize=localize, mesh=mesh, mesh_axis=mesh_axis,
            backend_name=backend_name, st=st,
            max_crashed=_MAX_CRASHED, escalate=False)
        if res is not None:
            if n_inert:
                res["crashed_dropped"] = n_inert
            return res
        # tier 2 ineligible (e.g. Sn << nc too wide): fall through to
        # the stripped validity proof rather than straight to serial.

    # Beyond the bounded kernel's reach: a valid verdict on the fully-
    # stripped history is a valid verdict on the original.
    res = _check_fast(
        model, spec, History(stripped), max_states=max_states,
        max_open_bits=max_open_bits,
        target_returns_per_segment=target_returns_per_segment,
        localize=False, mesh=mesh, mesh_axis=mesh_axis,
        backend_name=backend_name, st=st, escalate=False)
    if res is None:
        # outside the register-delta gate (e.g. concurrency > 8): the
        # stripped twin has no crashes, so the full check() chain (the
        # candidate-table kernel) can still prove it — no recursion
        # hazard, _check_crashed_fast bails on crash-free input.
        try:
            res = check(model, History(stripped), max_states=max_states,
                        max_open_bits=max_open_bits,
                        target_returns_per_segment=
                        target_returns_per_segment,
                        localize=False, mesh=mesh, mesh_axis=mesh_axis)
        except Unsupported:
            telemetry.count_fallback("wgl_seg_crash_fast",
                                     "stripped-chain")
            res = None
    if res is not None and res.get("valid?") is True:
        res["crashed_ignored"] = len(crashed)
        return res

    # Tier 4: the stripped history could NOT be proven valid — attempt
    # a sound refutation under relaxed crash semantics (any number of
    # crashes; see _relaxed_refute).  Inconclusive -> None (serial
    # engines take over, exactly as before).
    return _relaxed_refute(
        model, spec, history, ops, drop, crashed, crash_uop, inert,
        seen, rows, states, legal, next_state,
        max_open_bits=max_open_bits,
        target_returns_per_segment=target_returns_per_segment,
        backend_name=backend_name, localize=localize, st=st)


def _check_deep(model, ops, fk, legal, next_state,
                diag_w, const_w, const_t0, *, R, Sn, nc, localize,
                backend_name, st):
    """Deep-overlap single history on the ops.wgl_deep Pallas
    megakernel (R > the register-delta gate, up to the word-split
    boundary planner.deep_r_max(backend, 1);
    crashed calls ride as permanent slots — no J-axis width limit).
    Returns a knossos-shaped result, or None when out of scope
    (callers fall through to the serial engines)."""
    from jepsen_tpu.ops import wgl_deep

    if diag_w is None or not wgl_deep.supported(
            R, Sn, legal.shape[0], True, backend_name):
        return None
    I = min(2, R) if R else 1
    with st.span("pack"):
        if fk.deltas is not None:
            # columnar scan: the delta stream feeds the layout directly
            ret_t, islot_t, iuop_t, Lp = _pack_regs_single(
                fk, [fk.n_rets], R, int(legal.shape[0]), I)
        else:
            # crash-tolerant Python scan: snapshot-diff packer
            ret_t, islot_t, iuop_t, Lp = _pack_regs(
                [(0, fk)], 1, R, int(legal.shape[0]), I)
    with st.span("tables"):
        a1t, a2t, t0t = _pack_uop_tables(
            legal, next_state, diag_w, const_w, const_t0)
    with st.span("kernel"):
        # the deep program's own compact wire packing rides inside
        res = wgl_deep.check_tables(ret_t, islot_t, iuop_t, a1t, a2t,
                                    t0t, R, Sn)
    st.tally(telemetry.DEVICE_CALLS, engine="wgl_deep")
    result: dict[str, Any] = {
        "valid?": res["valid?"],
        "op_count": fk.n_calls,
        "backend": backend_name,
        "engine": "wgl_deep",
        "max_open": R,
        "states": Sn,
    }
    for key in ("deep_variant", "shards"):   # word-split provenance
        if key in res:
            result[key] = res[key]
    if nc:
        result["crashed"] = nc
    if res["valid?"]:
        return result
    result["anomaly"] = "nonlinearizable"
    # Exact witness: the kernel reports the failing event row;
    # wgl_deep.map_witness turns it into the failing call's invoke op
    # (the same witness the oracle names, differentially pinned)
    w = wgl_deep.map_witness(ret_t, fk, ops, res["failed_row"])
    pos = None
    if w is not None:
        result["op"] = w[0].to_dict()
        result["op_index"] = w[1]
        pos = w[2]
    if localize:
        # artifacts (final-paths/configs) via a CAPPED oracle on the
        # prefix through the witness: the deep regime is exactly where
        # an uncapped oracle can spin, and the verdict + witness above
        # are already exact without it
        from jepsen_tpu.ops import wgl_cpu
        prefix = ops if pos is None else ops[:pos + 1]
        with telemetry.checker_stage("wgl_seg", "witness"):
            oracle = wgl_cpu.check(model, History(list(prefix)),
                                   **wgl_cpu.ARTIFACT_CAPS)
        if oracle.get("valid?") is False:
            for key in ("final-paths", "configs"):
                if key in oracle:
                    result[key] = oracle[key]
            if "op_index" not in result:
                for key in ("op", "op_index"):
                    if key in oracle:
                        result[key] = oracle[key]
        else:
            result["final-paths-error"] = wgl_cpu.artifacts_error(oracle)
    return result


def _check_fast(model, spec, history, *, max_states, max_open_bits,
                target_returns_per_segment, localize, mesh, mesh_axis,
                backend_name, st, max_crashed: int = 0,
                escalate: bool = True):
    """Single-history fast path: one fused host scan (the native C
    scanner when available) straight into per-segment register-delta
    lanes — no per-op Python objects.  Crash-bearing histories escalate
    to _check_crashed_fast (inert dropping / bounded kernel / stripped
    validity proof).  Returns None when out of scope so check() takes
    the plan() route, which raises the descriptive Unsupported.  Stage
    seconds go to `st`: scan, tables, pack, kernel."""
    seen: dict = {}
    rows: list = []
    with st.span("scan"):
        ops = history.ops if isinstance(history, History) else \
            History(history).ops
        fk = _scan_history(history, ops, spec, seen, rows, max_open_bits,
                           want_snaps=(mesh is not None), st=st)
        if fk is None and max_crashed:
            # crash-tolerant scan (Python twin; permanent high slots)
            fk = _fast_scan(history, spec, seen, rows, max_open_bits,
                            max_crashed=max_crashed)
            st.tally(telemetry.SCAN_KEYS, path="python")
    if fk is None:
        if escalate:
            return _check_crashed_fast(
                model, spec, history, max_states=max_states,
                max_open_bits=max_open_bits,
                target_returns_per_segment=target_returns_per_segment,
                localize=localize, mesh=mesh, mesh_axis=mesh_axis,
                backend_name=backend_name, st=st)
        return None
    if fk.n_calls == 0:
        return {"valid?": True, "op_count": 0, "backend": backend_name,
                "engine": "wgl_seg"}
    nc = int(fk.nc)
    rn = int(fk.rn) if fk.rn is not None else int(fk.max_open)
    try:
        with st.span("tables"):
            uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
            init = np.asarray(spec.encode(model), np.int32)
            states, legal, next_state = _enumerate_states(
                spec, init, uops, max_states)
            diag_w, const_w, const_t0 = _decompose(legal, next_state)
            Sn = states.shape[0]
            R = rn + nc if nc else int(fk.max_open)
            # THE routing decision (ops.planner), over the tables:
            # register-delta segment kernel vs deep-overlap Pallas
            # megakernel (crashed calls are permanent slots there; a
            # crash set too wide for the J = Sn * 2^nc entry axis
            # diverts the same way) vs the candidate-table plan() route
            # (None).
            route = planner.plan_engines(
                planner.Shape(kind="linear",
                              R=rn if nc else int(fk.max_open),
                              crashes=nc, Sn=int(Sn),
                              U=int(legal.shape[0]),
                              decomposed=diag_w is not None,
                              n_ops=int(fk.n_calls),
                              mesh=None if mesh is None else int(
                                  np.prod(mesh.devices.shape)),
                              max_states=max_states,
                              max_open_bits=max_open_bits),
                backend=backend_name)
    except Unsupported:
        telemetry.count_fallback("wgl_seg_regs", "state-space")
        return None
    if route.engine != "wgl_seg_regs":
        if route.engine in ("wgl_deep", "wgl_deep_split") \
                and mesh is None:
            r = _check_deep(
                model, ops, fk, legal, next_state,
                diag_w, const_w, const_t0, R=R, Sn=Sn, nc=nc,
                localize=localize, backend_name=backend_name, st=st)
            if isinstance(r, dict):
                r["_plan"] = route
            return r
        return None

    with st.span("pack"):
        # segment at quiescent cuts, >= target returns per segment
        cuts = np.asarray(fk.cuts, np.int32)
        if len(cuts) != fk.n_rets or not fk.n_rets or cuts[-1] != 1:
            return None              # defensive: malformed cut stream
        seg_ends = _segment_ends(cuts, target_returns_per_segment)
        K = len(seg_ends)
        tables = seg_fk = None
        if fk.deltas is not None and nc == 0 and mesh is None:
            # columnar scan supplied the invoke-delta stream: pack
            # tables directly, skipping the per-segment slicing round
            # trip
            tables = _pack_regs_single(fk, seg_ends, R,
                                       int(legal.shape[0]),
                                       min(2, R) if R else 1)
        else:
            seg_fk = _segments_from_fk(fk, R, seg_ends)

    T, _, sharded, verdict = _run_seg_regs(
        seg_fk, K, R, legal.shape[0], Sn, 1 << R, legal, next_state,
        diag_w, const_w, const_t0, mesh, mesh_axis, nc=nc, rn=rn,
        tables=tables, st=st)
    entry_mask = None
    if verdict is None:
        dead_segment = _compose_transfer(T, Sn << nc)
    else:
        dead_segment = int(verdict[1])
        entry_mask = verdict[2:6]

    result: dict[str, Any] = {
        "valid?": dead_segment < 0,
        "op_count": fk.n_calls,
        "backend": backend_name,
        "engine": "wgl_seg",
        "segments": K,
        "states": Sn,
        "sharded": sharded,
        "_plan": route.refine(
            bucket=("wgl_seg_regs", R, int(Sn), int(legal.shape[0]),
                    K)),
    }
    if nc:
        result["crashed"] = nc
    if dead_segment >= 0:
        result["anomaly"] = "nonlinearizable"
        result["dead_segment"] = dead_segment
        if localize:
            oracle = None
            with telemetry.checker_stage("wgl_seg", "witness"):
                if entry_mask is not None and nc == 0:
                    # segment-local replay from the device's entry
                    # mask — O(segment) instead of
                    # O(prefix-through-witness)
                    oracle = _localize_segment(model, spec, ops, fk,
                                               seg_ends, dead_segment,
                                               entry_mask, states)
                if oracle is None:
                    # fallback: whole-history oracle (terminates at the
                    # first non-linearizable op)
                    from jepsen_tpu.ops import wgl_cpu
                    oracle = wgl_cpu.check(model, history)
            for key in ("op", "op_index", "final-paths", "configs"):
                if key in oracle:
                    result[key] = oracle[key]
    return result


def check(model, history, *, max_states: int = 64, max_open_bits: int = 10,
          target_returns_per_segment: int = 256,
          localize: bool = True, mesh=None,
          mesh_axis: Optional[str] = None) -> dict[str, Any]:
    """_check_impl plus the inspectable dispatch record every verdict
    carries (jepsen_tpu.telemetry): which engine produced it, why, the
    fallback chain below it, and the env knobs in effect — so
    `results.json` explains its own dispatch instead of requiring the
    reader to re-derive eight modules' worth of gating.

    The call's stage seconds (scan, tables, pack, kernel on the fast
    path; plan, pack, kernel on the plan() route) ride the verdict's
    `stages` and `jepsen_stage_seconds_total`; its work counts reach
    the registry once, here."""
    st = telemetry.Stages()
    r = _check_impl(model, history, max_states=max_states,
                    max_open_bits=max_open_bits,
                    target_returns_per_segment=target_returns_per_segment,
                    localize=localize, mesh=mesh, mesh_axis=mesh_axis,
                    st=st)
    st.flush()
    if isinstance(r, dict):
        # the fast path stashed the planner-emitted Plan; the crash
        # tiers and the plan() route synthesize one so EVERY verdict
        # renders a plan (why + fallbacks + bucket) verbatim
        pl = r.pop("_plan", None)
        if pl is None and "dispatch" not in r:
            # crash tiers / the plan() route: re-derive the plan from
            # what the verdict discloses (same pure function, so the
            # env-knob prunes render here too), keeping the tier's own
            # why when it named one
            pl = planner.plan_engines(
                planner.Shape(
                    kind="linear",
                    R=int(r.get("max_open") or 0),
                    crashes=int(r.get("crashed")
                                or r.get("crashed_ignored") or 0),
                    Sn=r.get("states"),
                    max_states=max_states,
                    max_open_bits=max_open_bits),
                backend=r.get("backend"))
            tier_why = r.get("refutation") or r.get("crash_tier")
            if tier_why:
                pl = pl.refine(why=str(tier_why))
        if "dispatch" not in r:
            telemetry.attach_dispatch(
                [r],
                pl.record(
                    engine=r.get("engine", "wgl_seg"),
                    R=r.get("max_open"),
                    crashes=r.get("crashed_ignored"),
                    batch=1,
                    mesh=(getattr(mesh, "shape", None)
                          if mesh is not None else None)),
                stages=st.seconds)
    return r


def _check_impl(model, history, *, max_states: int = 64,
                max_open_bits: int = 10,
                target_returns_per_segment: int = 256,
                localize: bool = True, mesh=None,
                mesh_axis: Optional[str] = None,
                st: Optional[telemetry.Stages] = None) -> dict[str, Any]:
    """Segment-parallel linearizability check.  Returns a knossos-shaped
    analysis map (same keys as ops.wgl.check).  Crashed (:info) calls
    are handled exactly (inert dropping / bounded crash kernel /
    stripped validity proof — see _check_crashed_fast).  Raises
    Unsupported when the history/model falls outside this engine's
    scope (large state spaces, deep concurrency, residual many-crash
    histories) — callers fall back to ops.wgl.check / ops.wgl_cpu.check.

    With `mesh`/`mesh_axis`, ONE history's segment axis is sharded over
    the devices (SURVEY.md §5 long-context: "sharding the DFS/BFS
    frontier of a single long history across devices") — every device
    computes transfer matrices for its slice of the segments, and only
    the [K, Sn, Sn] matrices come back for the host composition."""
    import jax

    spec = model.device_spec()
    if spec is None:
        raise Unsupported(f"model {model!r} has no device spec")

    st = telemetry.Stages() if st is None else st
    backend_name = jax.default_backend()
    if (not isinstance(history, PreparedHistory)
            and getattr(spec, "encode_op", None) is None):
        fast = _check_fast(
            model, spec, history, max_states=max_states,
            max_open_bits=max_open_bits,
            target_returns_per_segment=target_returns_per_segment,
            localize=localize, mesh=mesh, mesh_axis=mesh_axis,
            backend_name=backend_name, st=st)
        if fast is not None:
            return fast
    with st.span("plan"):
        prep = history if isinstance(history, PreparedHistory) \
            else prepare(history)
        if prep.calls:
            pl = plan(prep, spec, model, max_states=max_states,
                      max_open_bits=max_open_bits,
                      target_returns_per_segment=target_returns_per_segment)
    if not prep.calls:
        return {"valid?": True, "op_count": 0, "backend": backend_name,
                "engine": "wgl_seg"}
    K, L = pl.ret_slot.shape
    C = pl.cand_slot.shape[2]
    Sn = pl.states.shape[0]
    M = 1 << pl.max_open

    # Register-delta kernel for segments (one lane per segment, J=Sn
    # entry states) under the same gate as the batch path; the
    # candidate-table kernel is the fallback.
    R = int(pl.max_open)
    decomposed = pl.diag_w is not None
    U = pl.legal.shape[0]
    dead_segment = None
    if pl.seg_fk is not None and _regs_eligible(R, U, Sn, decomposed):
        T, _, sharded, verdict = _run_seg_regs(
            pl.seg_fk, K, R, U, Sn, M, pl.legal, pl.next_state,
            pl.diag_w, pl.const_w, pl.const_t0, mesh, mesh_axis, st=st)
        if verdict is not None:
            dead_segment = int(verdict[1])
    else:
        sharded = False
        K_run = K
        if mesh is not None and mesh_axis is not None:
            # pad the segment axis up to a mesh-size multiple — the plan
            # does NOT guarantee divisibility, and all-padding segments
            # (ret -1, no candidates) are identity transfer matrices
            m = int(mesh.shape[mesh_axis])
            K_run = ((K + m - 1) // m) * m
            sharded = True
        with st.span("pack"):
            ret_slot, cand_slot, cand_uop = \
                pl.ret_slot, pl.cand_slot, pl.cand_uop
            if K_run != K:
                ret_slot = np.concatenate(
                    [ret_slot, np.full((K_run - K, L), -1, np.int32)])
                cand_slot = np.concatenate(
                    [cand_slot, np.zeros((K_run - K, L, C), np.int32)])
                cand_uop = np.concatenate(
                    [cand_uop, np.full((K_run - K, L, C), -1, np.int32)])
            ret_t = np.ascontiguousarray(ret_slot.T)         # [L, K]
            cslot_t = np.ascontiguousarray(cand_slot.transpose(1, 0, 2))
            cuop_t = np.ascontiguousarray(cand_uop.transpose(1, 0, 2))
            kern, args, n_sharded = _dispatch_kernel(
                K_run, int(L), int(C), int(M), int(Sn), R,
                int(Sn), ret_t, cslot_t, cuop_t, pl.legal, pl.next_state,
                pl.diag_w, pl.const_w, pl.const_t0)
        put = None
        if sharded:
            def put(a):
                return _shard_args(mesh, mesh_axis, a, n_sharded)
        with st.span("kernel"):
            T = _device_call(st, "wgl_seg", kern, args, lanes=K,
                             padded=K_run - K, put=put)[:K] > 0.5

    if dead_segment is None:
        dead_segment = _compose_transfer(T, Sn)

    result: dict[str, Any] = {
        "valid?": dead_segment < 0,
        "op_count": pl.n_calls,
        "backend": backend_name,
        "engine": "wgl_seg",
        "segments": K,
        "states": Sn,
        "sharded": sharded,
    }
    if dead_segment >= 0:
        result["anomaly"] = "nonlinearizable"
        result["dead_segment"] = dead_segment
        if localize and not isinstance(history, PreparedHistory):
            # Exact failing op: CPU oracle on the prefix through the
            # first dead segment (bounded: verdict is known invalid).
            from jepsen_tpu.history import History
            from jepsen_tpu.ops import wgl_cpu
            end_call = int(pl.seg_end_call[dead_segment])
            if 0 <= end_call < len(prep.calls):
                last = prep.calls[end_call]
                cutoff = (last.completion.index
                          if last.completion is not None else last.op.index)
                with telemetry.checker_stage("wgl_seg", "witness"):
                    prefix = History(
                        [o for o in history if o.index <= cutoff])
                    oracle = wgl_cpu.check(model, prefix)
                for key in ("op", "op_index", "final-paths", "configs"):
                    if key in oracle:
                        result[key] = oracle[key]
    return result


_fill_pool_lock = threading.Lock()
_fill_pool_inst = None


def _fill_pool():
    """Module-level lazy ThreadPoolExecutor for pipeline layout/fill —
    created once under a lock (two threads entering check_pipeline
    concurrently must not race a lazy attribute and leak a pool) and
    reused for the process lifetime."""
    global _fill_pool_inst
    if _fill_pool_inst is None:
        with _fill_pool_lock:
            if _fill_pool_inst is None:
                import concurrent.futures as _cf
                _fill_pool_inst = _cf.ThreadPoolExecutor(4)
    return _fill_pool_inst


def check_pipeline(model, histories, *, max_states: int = 64,
                   max_open_bits: int = 10,
                   target_returns_per_segment: int = 256,
                   localize: bool = True,
                   stats: Optional[dict] = None) -> list:
    """Steady-state checking of MANY long histories, fully STREAMED:
    histories are scanned, segmented, packed, and dispatched in groups
    of G, and every host-side stage of group g+1 runs while the device
    executes group g (dispatch is asynchronous); ALL verdicts are
    stacked on device and fetched in ONE round trip — amortizing the
    fixed D2H latency over the batch, which bounds any single-shot
    check from below.

    The group kernel runs SPECULATIVE closure rounds (default 2): the
    exact fixpoint needs rounds=R, but fewer rounds only
    under-approximate the per-segment transfer matrices (strictly
    fewer truly-reachable configs survive), so a surviving composed
    verdict is an exact VALID; a speculative death is re-checked at
    full rounds via check() — valid workloads never pay the rerun.
    Verdict-identical to check() per history either way.

    Compiled-shape control: the kernel is keyed on (R, Sn, U, Lp, K);
    a later group that grows any of them (new op values enlarging the
    state space, deeper concurrency, longer segments) rebuilds the
    kernel for SUBSEQUENT groups only — already-dispatched verdicts
    stay valid, since a group's tables are self-consistent with the
    kernel that ran them.  Same-shaped steady-state batches (the
    reference's `analyze` re-check loop, cli.clj:366-397) compile
    exactly once.

    `stats`, when given a dict, receives the per-stage host-time
    decomposition in seconds (cumulative over the whole call): scan,
    segment, layout, tables (state enumeration + uop packing + kernel
    build), fill, dispatch (the async kernel calls), fetch (the single
    stacked D2H — this also absorbs whatever transfer/execution hasn't
    finished in the background), assemble — so bench regressions are
    attributable to a stage instead of a wall-clock blur."""
    import jax

    spec = model.device_spec()
    if spec is None:
        raise Unsupported(f"model {model!r} has no device spec")
    # stage timings are ALWAYS collected (a handful of spans per
    # group): every pipelined verdict carries its stage decomposition +
    # dispatch record
    st = telemetry.Stages(stats)
    stats = st.seconds
    backend_name = jax.default_backend()
    n = len(histories)
    results: list = [None] * n
    seen: dict = {}
    rows: list = []
    strag: list = []
    G = max(1, min(PIPE_GROUP, len(histories) or 1))
    init = np.asarray(spec.encode(model), np.int32)

    # streaming state: rebuilt only when the alphabet/shape grows
    U_at = -1           # len(rows) the tables were built for
    Sn = 0
    states = legal = next_state = None
    diag_w = const_w = const_t0 = None
    buf32 = None
    R_cur = 0
    Lp_c = K_c = Rp_c = 0
    fn = None
    spec_rounds = 1
    dispatched: list = []    # (device_out, [history indices])
    metas: dict = {}         # i -> (fk, seg_ends, k_segments)

    def refresh_tables():
        nonlocal U_at, Sn, states, legal, next_state, diag_w, \
            const_w, const_t0, buf32, fn
        uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
        states, legal, next_state = _enumerate_states(
            spec, init, uops, max_states)
        Sn_new = states.shape[0]
        diag_w, const_w, const_t0 = _decompose(legal, next_state)
        a1t, a2t, t0t = _pack_uop_tables(
            legal, next_state, diag_w, const_w, const_t0)
        buf32 = np.concatenate([a1t, a2t, t0t.view(np.uint32)])
        U_at = len(rows)
        # ANY table growth invalidates the compiled kernel: it slices
        # buf32 at static U offsets and fixes the iuop width, so a
        # stale kernel over a grown buf32 would read garbage tables
        fn = None
        return Sn_new

    pos = 0
    while pos < n:
        grp: list = []
        while pos < n and len(grp) < G:
            i = pos
            pos += 1
            h = histories[i]
            if isinstance(h, PreparedHistory):
                strag.append(i)
                continue
            with st.span("scan"):
                # fast path: ONE C pass from packed columns to the wire
                # layout (scan + segmentation + row streams fused)
                sk = _native_scan_streams(
                    h.packed_columns() if isinstance(h, History)
                    else None, spec, seen, rows, max_open_bits,
                    target_returns_per_segment)
                streamed = sk is not None and sk is not False
                if not streamed:
                    ops = h.ops if isinstance(h, History) \
                        else History(h).ops
                    fk = _scan_history(h, ops, spec, seen, rows,
                                       max_open_bits, want_snaps=False,
                                       st=st)
            if streamed:
                st.tally(telemetry.SCAN_KEYS, path="columnar-stream")
                if sk.n_calls == 0:
                    results[i] = {"valid?": True, "op_count": 0,
                                  "backend": backend_name,
                                  "engine": "wgl_seg"}
                    continue
                grp.append((i, sk, sk.seg_ends, sk))
                continue
            if fk is None:
                strag.append(i)
                continue
            if fk.n_calls == 0:
                results[i] = {"valid?": True, "op_count": 0,
                              "backend": backend_name,
                              "engine": "wgl_seg"}
                continue
            with st.span("segment"):
                cuts = np.asarray(fk.cuts, np.int32)
                cut_ok = (len(cuts) == fk.n_rets and fk.n_rets
                          and cuts[-1] == 1 and fk.deltas is not None)
                if cut_ok:
                    seg_ends = _segment_ends(cuts,
                                             target_returns_per_segment)
            if not cut_ok:
                strag.append(i)
                continue
            with st.span("layout"):
                lay = _RegsLayout(fk, seg_ends, 1)
            grp.append((i, fk, seg_ends, lay))
        if not grp:
            continue

        # (re)build tables/kernel if this group grew anything
        with st.span("tables"):
            off = False
            if len(rows) != U_at:
                try:
                    Sn = refresh_tables()
                except Unsupported:
                    # state space outgrew max_states: this group (and
                    # any later one — the alphabet only grows) goes
                    # through check()'s own fallback chain
                    telemetry.count_fallback("wgl_seg_pipeline",
                                             "state-space")
                    off = True
            if not off:
                R_g = max(fk.max_open for _, fk, _, _ in grp)
                U = int(legal.shape[0])
                # a group off the batched engine (deep overlap /
                # undecomposable growth) goes through check(), which
                # owns the full fallback chain, and streaming goes on
                off = not _regs_eligible(max(R_g, R_cur), U, Sn,
                                         diag_w is not None)
            if not off:
                grow = False
                for _, fk, seg_ends, filler in grp:
                    if isinstance(filler, _StreamKey):
                        lp, k, rp = filler.lp_min, filler.k, filler.rtot
                    else:
                        lp, k = filler.lp_min, filler.k
                        rp = int(filler.rows_per_key.sum()) if k else 0
                    if lp > Lp_c or k > K_c or rp > Rp_c:
                        grow = True
                        Lp_c = max(Lp_c, lp)
                        K_c = max(K_c, k)
                        Rp_c = max(Rp_c, rp)
                if R_g > R_cur:
                    R_cur = R_g
                    fn = None
                if grow:
                    Lp_c = _pad_len(Lp_c)
                    K_c = ((K_c + 63) // 64) * 64
                    Rp_c = ((Rp_c + 8191) // 8192) * 8192
                    fn = None
                if fn is None:
                    spec_rounds = min(R_cur, SPEC_ROUNDS)
                    fn = planner.compiled(
                        "wgl_seg_pipeline",
                        (G, K_c, Lp_c, R_cur, int(Sn), U, Rp_c,
                         spec_rounds, SCAN_UNROLL, diag_w is not None),
                        _build_kernel_regs_group_c,
                        G, K_c, Lp_c, max(1, (1 << R_cur) // 32),
                        int(Sn), R_cur, diag_w is not None, spec_rounds,
                        SCAN_UNROLL, U, Rp_c)
        if off:
            strag.extend(i for i, *_ in grp)
            continue

        def _layout_fill(args):
            i, fk, seg_ends, filler = args
            if isinstance(filler, _StreamKey):
                return i, filler.k, _fill_block_stream(
                    filler, Rp_c, K_c, U)
            buf, _ = _regs_fill_compact(filler, Rp_c, K_c, U)
            return i, filler.k, buf

        # layout+fill are numpy-bound (GIL-releasing): a small pool
        # packs the group's histories in parallel while the device
        # executes the previous group
        with st.span("fill"):
            if len(grp) > 1:
                filled = list(_fill_pool().map(_layout_fill, grp))
            else:
                filled = [_layout_fill(grp[0])]
            blocks = []
            for (i, fk, seg_ends, lay), (i2, k_segs, buf) \
                    in zip(grp, filled):
                assert i == i2
                metas[i] = (fk, seg_ends, k_segs)
                blocks.append(buf)
            while len(blocks) < G:        # short tail group: padding
                blocks.append(blocks[0])  # lane (verdicts discarded)
        with st.span("dispatch"):
            payload = np.concatenate(blocks)
            # measured wire traffic: the compact event blocks + the uop
            # tables shipped with every group
            stats["wire_bytes"] = (stats.get("wire_bytes", 0)
                                   + payload.nbytes + buf32.nbytes)
            dispatched.append(
                (fn(payload, buf32),
                 [i for i, *_ in grp], spec_rounds, R_cur, Sn, states))

    if dispatched:
        with st.span("fetch"):
            stacked = _build_stack(len(dispatched))(
                *[d for d, *_ in dispatched])
            vds = np.asarray(stacked)                 # ONE fetch
        with st.span("assemble"):
            for g, (_, idxs, sr, R_g_disp, Sn_g, states_g) \
                    in enumerate(dispatched):
                vd = vds[g].reshape(-1, 6)
                for j, i in enumerate(idxs):
                    valid = bool(vd[j, 0])
                    fk, seg_ends_i, k_segs = metas[i]
                    if not valid and sr < R_g_disp:
                        # speculative death is inconclusive: exact re-run
                        # (rare on valid workloads; carries the witness)
                        res = check(model, histories[i],
                                    max_states=max_states,
                                    max_open_bits=max_open_bits,
                                    target_returns_per_segment=
                                    target_returns_per_segment,
                                    localize=localize)
                        res["pipelined"] = True
                        res["speculation"] = "exact-rerun"
                        results[i] = res
                        continue
                    res = {"valid?": valid, "op_count": fk.n_calls,
                           "backend": backend_name, "engine": "wgl_seg",
                           "segments": k_segs, "states": int(Sn_g),
                           "pipelined": True}
                    if not valid:
                        res["anomaly"] = "nonlinearizable"
                        res["dead_segment"] = int(vd[j, 1])
                        if localize:
                            hi = histories[i]
                            h_ops = hi.ops if isinstance(hi, History) \
                                else History(hi).ops
                            oracle = _localize_segment(
                                model, spec, h_ops, fk, seg_ends_i,
                                int(vd[j, 1]), vd[j, 2:6], states_g)
                            if oracle is None:
                                from jepsen_tpu.ops import wgl_cpu
                                oracle = wgl_cpu.check(model, histories[i])
                            for key in ("op", "op_index", "final-paths",
                                        "configs"):
                                if key in oracle:
                                    res[key] = oracle[key]
                    results[i] = res
    # pipelined verdicts carry the pipeline's plan + stage
    # decomposition; stragglers (checked below through check()'s own
    # chain) carry the plan check() attaches for the engine that
    # actually produced them
    pipe_plan = planner.plan_engines(
        planner.Shape(kind="linear-pipeline", R=R_cur, Sn=Sn or None,
                      U=len(rows) or None, decomposed=True, batch=n,
                      max_states=max_states,
                      max_open_bits=max_open_bits),
        backend=backend_name).refine(
        bucket=("wgl_seg_pipeline", R_cur, int(Sn), G, Lp_c, K_c,
                Rp_c))
    telemetry.attach_dispatch(
        results,
        pipe_plan.record(engine="wgl_seg",
                         R=R_cur or None, batch=n,
                         stragglers=len(strag) or None),
        stages=stats)
    st.flush()
    for i in strag:
        results[i] = check(model, histories[i], max_states=max_states,
                           max_open_bits=max_open_bits,
                           target_returns_per_segment=
                           target_returns_per_segment,
                           localize=localize)
    return results


def _emit_batch_result(results, i, fk, ok: bool, backend_name: str,
                       engine: str, witness: list, localize: bool) -> None:
    """Per-key result dict — shared by the batch paths.  An invalid key
    to localize joins `witness`, for _localize_keys."""
    results[i] = {
        "valid?": ok,
        "op_count": fk.n_calls,
        "backend": backend_name,
        "engine": engine,
    }
    if not ok:
        results[i]["anomaly"] = "nonlinearizable"
        if localize:
            witness.append(i)


def _scan_stripped(h, packed, spec, seen: dict, rows: list,
                   max_open_bits: int, native_ok: bool, st):
    """A crashed key's crash-stripped twin, scanned: (_FastKey, or None
    while still out of scope; crash count), or None where the key holds
    no crashed call or a malformed one.  Complete columns
    (planner._cols_complete) split and scan as columns; the key's ops
    are read only where its columns cannot stand for them."""
    if native_ok and planner._cols_complete(packed):
        split = planner._split_crashed_cols(packed)
        if split is None or not split[1]:
            return None
        drop, n_crashed = split
        sfk = _native_scan_cols(packed, spec, seen, rows, max_open_bits,
                                keep=np.flatnonzero(~drop))
        if sfk is not False:
            st.tally(telemetry.SCAN_KEYS, path="columnar")
            return sfk, n_crashed
    ops = h.ops if isinstance(h, History) else History(h).ops
    split = _split_crashed(ops)
    if split is None or not split[1]:
        return None
    drop, crashed = split
    stripped = [o for pos, o in enumerate(ops) if not drop[pos]]
    sfk = _native_scan(stripped, spec, seen, rows, max_open_bits) \
        if native_ok else False
    path = "objects"
    if sfk is False:
        path = "python"
        sfk = _fast_scan(stripped, spec, seen, rows, max_open_bits)
    st.tally(telemetry.SCAN_KEYS, path=path)
    return sfk, len(crashed)


def _localize_keys(results, keys: list, model, histories) -> None:
    """The witness of each invalid batched key, from the CPU oracle on
    its history: one `witness` checker span for the whole batch."""
    if not keys:
        return
    from jepsen_tpu.ops import wgl_cpu

    with telemetry.checker_stage("wgl_seg", "witness"):
        for i in keys:
            if isinstance(histories[i], PreparedHistory):
                continue
            oracle = wgl_cpu.check(model, histories[i])
            for key in ("op", "op_index", "final-paths", "configs"):
                if key in oracle:
                    results[i][key] = oracle[key]


# ---------------------------------------------------------------------------
# Multi-key batch mode (jepsen.independent on device)
# ---------------------------------------------------------------------------

def _run_many_overlapped(batch, R: int, U: int, Sn: int, M: int,
                         decomposed: bool, unroll: int,
                         buf32: np.ndarray, st: telemetry.Stages,
                         backend_name: str):
    """check_many's compact register-delta path through the async
    double-buffered executor (ops.runner.overlap): the key batch is cut
    into chunks; chunk k+1's host packing (_pack_regs +
    _compact_many_block — the dominant host cost on the 3400-key bench
    row) runs while the device executes chunk k's kernel (JAX dispatch
    is asynchronous), and ALL chunk verdicts are stacked on device and
    fetched in ONE round trip.  Per-chunk event buffers are donated to
    the executable off-CPU (fresh host buffer per dispatch, so an OOM
    retry never touches a consumed donation).  Chunks share one padded
    lane count, and Lp/Rp bucket at 64/8192 granularity, so a uniform
    batch reuses ONE compiled executable (planner.compiled counts the
    hits).  Verdict-identical to the monolithic dispatch: keys are
    independent and chunking only partitions the lane axis
    (differentially pinned in tests/test_planner.py).

    Returns ok bool[len(batch)].  Stage seconds go to `st`: `pack` for
    each chunk's packing, `kernel` for the rest of the overlapped
    window (puts, calls, the fetch) — the packing runs inside that
    window, so it is a child span, never kernel time."""
    import jax

    from jepsen_tpu.ops import runner as runner_mod

    stats = st.seconds
    chunk = OVERLAP_CHUNK
    chunks = [batch[k:k + chunk] for k in range(0, len(batch), chunk)]
    Kp = max(128, ((min(len(batch), chunk) + 127) // 128) * 128)
    donate = backend_name not in ("cpu", "unknown")
    n_native = [0]
    h2d = [buf32.nbytes * len(chunks)]

    def pack(ch):
        with st.span("pack"):
            # Native parallel ingest: GIL-released
            # work-stealing snapshot-delta pack straight into one arena,
            # bit-identical to the numpy packers below (the permanent
            # differential twin and total fallback — any native error
            # degrades here, never a silent wrong pack; planner counts
            # both outcomes).
            nat = planner._native_pack_compact(ch, Kp, int(R), int(U))
            if nat is not None:
                buf8, Rp, Lp = nat
                n_native[0] += 1
            else:
                ret_t, islot_t, iuop_t, Lp = _pack_regs(ch, Kp, R, U, 1)
                buf8, Rp = _compact_many_block(ret_t, islot_t, iuop_t,
                                               Kp, U)
        h2d[0] += buf8.nbytes
        if donate:
            # start the H2D transfer of the arena now, while the NEXT
            # chunk packs — the executable then consumes (and donates)
            # an already-on-device buffer instead of paying transfer
            # inside its own dispatch
            with telemetry.trace("kernel.put"):
                buf8 = jax.device_put(buf8)
        return buf8, int(Lp), Rp

    def dispatch(payload):
        buf8, Lp, Rp = payload
        # AOT: jit(...).lower(...).compile() inside planner.compiled,
        # so the XLA compile is timed and charged to
        # cache_stats()['compile_s'] (and lands in the persistent
        # plan cache) instead of hiding in the first device call
        kern = planner.compiled(
            "wgl_seg_batch_regs",
            (Kp, Lp, R, Sn, U, Rp, unroll, decomposed, donate),
            _build_kernel_regs_many_c,
            Kp, Lp, max(1, M // 32), Sn, R, decomposed, R, unroll,
            U, Rp, donate,
            lower_args=(jax.ShapeDtypeStruct(buf8.shape, buf8.dtype),
                        jax.ShapeDtypeStruct(buf32.shape,
                                             buf32.dtype)))
        with telemetry.trace("kernel.call"):
            return kern(buf8, buf32)        # async device call

    with st.span("kernel"):
        outs = runner_mod.overlap(chunks, pack, dispatch, depth=2)
        with telemetry.trace("kernel.fetch"):
            if len(outs) == 1:
                T = np.asarray(outs[0])                  # [Kp, 1, Sn]
            else:
                # ONE fetch
                T = np.asarray(_build_stack(len(outs))(*outs))
    if len(outs) == 1:
        ok = (T[:, 0, :] > 0.5).any(axis=1)[:len(batch)]
    else:
        ok_all = (T[:, :, 0, :] > 0.5).any(axis=2)       # [G, Kp]
        ok = np.concatenate(
            [ok_all[g][:len(ch)] for g, ch in enumerate(chunks)])
    engine = "wgl_seg_batch_regs"
    st.tally(telemetry.DEVICE_CALLS, len(chunks), engine=engine)
    st.tally(telemetry.LANES, len(batch), engine=engine, kind="used")
    st.tally(telemetry.LANES, Kp * len(chunks) - len(batch),
             engine=engine, kind="padded")
    st.tally(telemetry.WIRE_BYTES, h2d[0], engine=engine,
             direction="h2d")
    st.tally(telemetry.WIRE_BYTES, T.nbytes, engine=engine,
             direction="d2h")
    stats["wire_bytes"] = stats.get("wire_bytes", 0) + h2d[0]
    stats["overlap_chunks"] = len(chunks)
    # which ingest backend actually packed (vs the plan's intent):
    # popped into the dispatch RECORD by check_many — "mixed" means a
    # native error degraded some chunks to the Python twin
    stats["pack_backend"] = (
        "native" if n_native[0] == len(chunks)
        else "mixed" if n_native[0] else "python")
    stats["pack_threads"] = planner.pack_threads_effective()
    return ok


def check_many(model, histories, *, max_states: int = 64,
               max_open_bits: int = 10, localize: bool = True,
               mesh=None, mesh_axis: Optional[str] = None,
               fallback=None) -> list:
    """Check many INDEPENDENT histories in one device program — the
    `jepsen.independent` key-sharded workload (`independent.clj:247-298`
    runs a bounded-pmap over per-key subhistories; here every key is one
    row of the batched bitmap kernel, J=1 start state).  Short per-key
    histories are the reference's own scaling recipe ("linearizability
    ... requires we verify only short histories", independent.clj:2-7).

    Keys outside this engine's scope (crashed ops, big state spaces) are
    checked by `fallback(model, prep) -> dict` (default: the serial
    device kernel via ops.wgl, then ops.wgl_cpu on no-device models).

    With `mesh`/`mesh_axis`, the key axis is sharded over the mesh
    (pure data parallelism over ICI; SURVEY.md §2.5).
    """
    import jax

    spec = model.device_spec()
    if spec is None:
        raise Unsupported(f"model {model!r} has no device spec")

    backend_name = jax.default_backend()
    results: list = [None] * len(histories)
    # per-stage host seconds (telemetry): scan, tables, pack, kernel on
    # the register-delta path; counted once per call, whatever mix of
    # engines produced the verdicts
    st = telemetry.Stages()
    stats = st.seconds
    witness: list = []          # invalid batched keys to localize

    # Partition keys: batchable vs fallback — one fused host pass per
    # key (no per-op objects).  With the native ingest layer and >= 2
    # threads, the whole batch's columnar scans run on the
    # work-stealing pool first (GIL released); keys it couldn't take
    # (no packed columns) and out-of-scope keys ride the serial
    # ladder below, with identical interning order either way.
    seen: dict = {}
    rows: list = []
    batch: list = []        # (key index, _FastKey)
    fall: list = []
    stripped_note: dict = {}  # key idx -> crash count (stripped twin batched)
    native_ok = getattr(spec, "encode_op", None) is None
    with st.span("scan"):
        pre = planner._scan_cols_many(histories, spec, seen, rows,
                                      max_open_bits)
        if pre:
            st.tally(telemetry.SCAN_KEYS, len(pre), path="columnar-batch")
        for i, h in enumerate(histories):
            if isinstance(h, PreparedHistory):
                fall.append(i)  # pre-prepped callers take the slow path
                continue
            packed = h.packed_columns() if isinstance(h, History) \
                else None
            if pre is not None and i in pre and (
                    pre[i] is not None or planner._cols_complete(packed)):
                fk = pre[i]
            else:
                # keys the batch scan did not take, and out-of-scope
                # keys whose columns are not complete: the serial
                # ladder's object-scan retry recovers what the columns
                # cannot represent (an out-of-int32 client id)
                fk = _scan_history(h, None, spec, seen, rows,
                                   max_open_bits, st=st)
            if fk is None:
                # Crashed keys ride the batch as their crash-stripped
                # twin: stripped-valid => valid (a crashed call carries
                # no obligation, so a linearization that never
                # linearizes one is a linearization of the full key).
                # Keys the stripped pass cannot prove valid are
                # re-checked exactly afterwards (bounded crash kernel
                # via check(), then serial fallback).
                stripped = _scan_stripped(h, packed, spec, seen, rows,
                                          max_open_bits, native_ok, st)
                if stripped is not None:
                    sfk, n_crashed = stripped
                    if sfk is not None and sfk.n_calls:
                        stripped_note[i] = n_crashed
                        batch.append((i, sfk))
                        continue
                    if sfk is not None:
                        # every client call crashed: trivially
                        # linearizable (linearize none of them)
                        results[i] = {"valid?": True, "op_count": 0,
                                      "backend": backend_name,
                                      "engine": "wgl_seg_batch",
                                      "crashed_ignored": n_crashed}
                        continue
                fall.append(i)
            elif fk.n_calls == 0:
                results[i] = {"valid?": True, "op_count": 0,
                              "backend": backend_name,
                              "engine": "wgl_seg_batch"}
            else:
                batch.append((i, fk))

    R_batch = None
    route = None
    if batch:
        try:
            with st.span("tables"):
                uops = np.asarray(rows, np.int32).reshape(len(rows), 4)
                init = np.asarray(spec.encode(model), np.int32)
                states, legal, next_state = _enumerate_states(
                    spec, init, uops, max_states)
                diag_w, const_w, const_t0 = _decompose(legal, next_state)
                Sn = states.shape[0]
                R = max(fk.max_open for _, fk in batch)
                # THE routing decision (ops.planner), over the tables:
                # register-delta compact lanes vs candidate-table lanes.
                route = planner.plan_engines(
                    planner.Shape(kind="linear-many", R=int(R),
                                  Sn=int(Sn), U=len(rows),
                                  decomposed=diag_w is not None,
                                  batch=len(batch),
                                  mesh=None if mesh is None else int(
                                      np.prod(mesh.devices.shape)),
                                  max_states=max_states,
                                  max_open_bits=max_open_bits),
                    backend=backend_name)
        except Unsupported:
            telemetry.count_fallback("wgl_seg_batch", "state-space")
            fall.extend(i for i, _ in batch)
            batch = []

    if batch:
        R_batch = int(R)
        M = 1 << R
        # C needs no pow2 pad — a return's candidate set is the open
        # calls, <= R.
        L = _pad_len(max(fk.n_rets for _, fk in batch))
        C = int(R)

        # Pad the key axis for lane alignment (and even mesh sharding).
        Kk = len(batch)
        mult = 128
        if mesh is not None and mesh_axis is not None:
            mult = int(np.lcm(mult, mesh.shape[mesh_axis]))
        Kp = max(mult, ((Kk + mult - 1) // mult) * mult)

        decomposed = diag_w is not None
        U = legal.shape[0]

        # Register-delta path (default): ship only per-return invoke
        # deltas and let the device maintain the open set — see
        # _build_kernel_regs and the shared _regs_eligible gate
        # (planner._linear_candidates routes on exactly that gate).
        if route.engine == "wgl_seg_batch_regs":
            engine_name = "wgl_seg_batch_regs"
            with st.span("tables"):
                a1t, a2t, t0t = _pack_uop_tables(
                    legal, next_state, diag_w, const_w, const_t0)
            if mesh is None:
                # compact wire (I = 1): key-major row streams, tables
                # rebuilt on device — ~3x fewer bytes than the padded
                # tables.  Large batches run through the async
                # double-buffered executor (ops.runner.overlap): host
                # packing of chunk k+1 overlaps device compute of
                # chunk k, all verdicts fetched ONCE at the end.
                buf32 = np.concatenate(
                    [a1t, a2t, t0t.view(np.uint32)])
                ok_k = _run_many_overlapped(
                    batch, int(R), int(U), int(Sn), int(M),
                    decomposed, SCAN_UNROLL, buf32, st, backend_name)
            else:
                I = min(2, int(R))
                with st.span("pack"):
                    ret_t, islot_t, iuop_t, Lp = _pack_regs(
                        batch, Kp, int(R), int(U), I)
                args = [ret_t, islot_t, iuop_t, a1t, a2t, t0t]
                with st.span("kernel"):
                    kern = planner.compiled(
                        "wgl_seg_batch_regs",
                        (Kp, int(Lp), I, int(R), int(Sn), int(U),
                         SCAN_UNROLL, decomposed, "mesh"),
                        _build_kernel_regs,
                        Kp, int(Lp), I, max(1, M // 32),
                        int(Sn), int(R), decomposed,
                        rounds=int(R), unroll=SCAN_UNROLL)
                    T = _device_call(
                        st, engine_name, kern, args, lanes=Kk,
                        padded=Kp - Kk,
                        put=lambda a: _shard_args(mesh, mesh_axis, a,
                                                  3))  # [Kp, 1, Sn]
                stats["wire_bytes"] = (stats.get("wire_bytes", 0)
                                       + sum(a.nbytes for a in args))
                ok_k = (T[:, 0, :] > 0.5).any(axis=1)
            for kk, (i, fk) in enumerate(batch):
                _emit_batch_result(results, i, fk, bool(ok_k[kk]),
                                   backend_name, engine_name, witness,
                                   localize and i not in stripped_note)
            batch = []

    if batch:
        with st.span("pack"):
            ret_slot = np.full((Kp, L), -1, np.int32)
            cand_slot = np.zeros((Kp, L, C), np.int32)
            cand_uop = np.full((Kp, L, C), -1, np.int32)
            for kk, (_, fk) in enumerate(batch):
                if fk.arrays is not None:
                    # native form: vectorized scatter from the flat
                    # arrays
                    rs, counts, cs, cu = fk.arrays
                    nr = len(rs)
                    ret_slot[kk, :nr] = rs
                    if len(cs):
                        ends = np.cumsum(counts)
                        r_idx = np.repeat(np.arange(nr), counts)
                        j_idx = (np.arange(ends[-1])
                                 - np.repeat(ends - counts, counts))
                        cand_slot[kk, r_idx, j_idx] = cs
                        cand_uop[kk, r_idx, j_idx] = cu
                    continue
                for r, (slot, cands) in enumerate(fk.rets):
                    ret_slot[kk, r] = slot
                    for j, (s2, u2) in enumerate(cands):
                        cand_slot[kk, r, j] = s2
                        cand_uop[kk, r, j] = u2

            ret_t = np.ascontiguousarray(ret_slot.T)         # [L, K]
            cslot_t = np.ascontiguousarray(cand_slot.transpose(1, 0, 2))
            cuop_t = np.ascontiguousarray(cand_uop.transpose(1, 0, 2))

            # (No Pallas megakernel here: one never beat XLA's fusion of
            # the same bitmap algebra on any measured shape, ~25% slower
            # at its best — hand-scheduling what the compiler already
            # fuses well buys nothing but maintenance surface.)
            engine_name = "wgl_seg_batch"
            kern, args, kc_shaped = _dispatch_kernel(
                Kp, int(L), int(C), int(M), int(Sn), int(R), 1,
                ret_t, cslot_t, cuop_t, legal, next_state,
                diag_w, const_w, const_t0)
        put = None
        if mesh is not None and mesh_axis is not None:
            def put(a):
                return _shard_args(mesh, mesh_axis, a, kc_shaped)
        with st.span("kernel"):
            T = _device_call(st, engine_name, kern, args,
                             lanes=len(batch), padded=Kp - len(batch),
                             put=put)                    # [Kp, 1, Sn]
        ok_k = (T[:, 0, :] > 0.5).any(axis=1)
        for kk, (i, fk) in enumerate(batch):
            _emit_batch_result(results, i, fk, bool(ok_k[kk]),
                               backend_name, engine_name, witness,
                               localize and i not in stripped_note)
    _localize_keys(results, witness, model, histories)

    if stripped_note:
        # Crash-bearing keys: a valid verdict on the stripped twin IS
        # the verdict; anything else gets the exact single-key chain
        # (inert dropping + bounded crash kernel), then the serial
        # fallback below.  Keys already routed to `fall` (e.g. the
        # whole batch bailed on state enumeration) are left to it.
        in_fall = set(fall)
        for i, nc in stripped_note.items():
            if i in in_fall:
                continue
            r = results[i]
            if r is not None and r.get("valid?") is True:
                r["crashed_ignored"] = nc
                continue
            try:
                results[i] = check(model, histories[i],
                                   max_states=max_states,
                                   max_open_bits=max_open_bits,
                                   localize=localize)
            except Unsupported:
                telemetry.count_fallback("wgl_seg_batch", "per-key-chain")
                results[i] = None
                fall.append(i)

    if fall:
        if fallback is None:
            from jepsen_tpu.ops import wgl, wgl_cpu

            def fallback(m, h):
                try:
                    return wgl.check(m, h)
                except ValueError:
                    # Outside the serial device kernel's scope too
                    # (e.g. values that don't encode to int32) — the
                    # exact CPU oracle handles anything.
                    return wgl_cpu.check(m, h)
        for i in fall:
            h = histories[i]
            p = h if isinstance(h, PreparedHistory) else prepare(h)
            results[i] = fallback(model, p)
            results[i].setdefault("engine", "fallback")

    # Dispatch records, grouped by the engine that actually produced
    # each verdict (batched kernel lanes, exact single-key crash
    # chains, serial fallbacks): one shared plan-rendered record per
    # engine, so the attribution costs dict references, not
    # per-verdict env scans.  The call's stage seconds are counted
    # once, under the routed engine when it produced verdicts.
    by_engine: dict = {}
    for r in results:
        if isinstance(r, dict) and "dispatch" not in r:
            by_engine.setdefault(r.get("engine", "wgl_seg_batch"),
                                 []).append(r)
    n_crash = sum(stripped_note.values()) if stripped_note else None
    if route is None:
        route = planner.plan_engines(
            planner.Shape(kind="linear-many", R=0,
                          batch=len(histories),
                          max_states=max_states,
                          max_open_bits=max_open_bits),
            backend=backend_name)
    # the ingest backend that ACTUALLY packed (may differ from the
    # plan's pack_backend when a native error degraded mid-batch) —
    # strings ride the record, not the numeric stage decomposition
    pack_used = stats.pop("pack_backend", None)
    pack_nt = stats.pop("pack_threads", None)
    order = sorted(by_engine, key=lambda e: e != route.engine)
    for n, eng in enumerate(order):
        telemetry.attach_dispatch(
            by_engine[eng],
            route.record(
                engine=eng,
                R=R_batch, crashes=n_crash, batch=len(histories),
                mesh=(getattr(mesh, "shape", None)
                      if mesh is not None else None),
                pack_backend=pack_used, pack_threads=pack_nt),
            stages=stats, count_stages=(n == 0))
    st.flush()
    return results
