"""Mesh-sharded, bit-packed Elle closure engine — million-transaction
isolation certificates.

`ops/elle_graph.py` decides the Adya classes with dense bf16 plane
stacks vmapped on ONE device: the O(n^3 log n) closure and a single
device's HBM cap histories at ~1k-10k txns.  This module removes both
caps, with the same masked-closure semantics (differentially pinned):

**Bit-packed uint32 planes.**  A boolean plane row packs 32 columns
per word (`bit b of word w  <->  column w*32 + b`), so a resident
plane costs n^2/8 bytes — 8x below the dense bool stack and 32x below
the bf16 matmul operands the dense path materializes.  Plane unions
(ww|wr|order...) are single bitwise ORs on the packed words.  The
closure matmuls stay 0/1-exact MXU work: each blocked product unpacks
only the (block x block) tiles in flight to bf16, accumulates f32
counts (exact to 2^24 > any path count we admit), thresholds, and
repacks — HBM residency never sees a dense plane.

**Mesh sharding.**  Packed planes shard by ROWS over the device mesh
(`PartitionSpec("rows")` via the same shard_map helper
`wgl_deep.check_mesh` uses).  One log-squaring round all-gathers the
frontier operands (every device needs all rows of the RIGHT operand;
its own row shard of the LEFT stays local), then runs the blocked
products on the local shard: compute n^3/D per device, wire 3 packed
planes per round.

**Device-side early exit.**  The closure state is monotone, so the
fixpoint is detected exactly: a round that changes nothing anywhere
(psum over the mesh) ends the `while_loop`.  Clean histories with
short dependency diameters settle in ~log2(diameter) rounds instead
of the full log2(n) schedule; `rounds` is reported per history for
telemetry and the bench's early-exit accounting.

One pair-closure carries everything the four class masks need:

    cww       closure of ww|order                 (G0)
    p0        reflexive closure of ww|wr|order    (zero-rw paths;
              off-diagonal it IS c_wwr, and defining edges are never
              diagonal)                            (G1c, G-single)
    p1        >=1-rw paths over ww|wr|order|rw    (G2-item, priority-
              masked by ~p0.T exactly as the dense engine)

    round:  cww <- cww | cww.cww
            p0  <- p0  | p0.p0
            p1  <- p1  | q.p1 | p1.q      (q = p0|p1: 3 products
                                           instead of the naive 4)

Host-side companions (numpy over the same packed layout, no dense
materialization):

  * `find_witness_packed` — level-BFS cycle recovery for device-found
    anomalies (product-graph BFS for G2's >=1-rw constraint);
  * `classify_host_packed` — the sharded-scale differential oracle:
    SCC (iterative Tarjan) decides G0/G1c exactly in O(V+E); rw edges
    probe G-single/G2 per edge (SCC pre-filter, then BFS), with a
    DISCLOSED probe cap and deadline — on exceeding either it returns
    an honest `unknown` degradation row, never a silent pass.

`checker/elle.py` runs this as the `elle-mesh` tier of its
ResilientRunner chain (elle-mesh -> elle-device -> elle-host).
"""

from __future__ import annotations

import math
import time
from typing import Optional, Sequence

import numpy as np

from jepsen_tpu import telemetry
from jepsen_tpu.elle.infer import PLANES

ENGINE = "elle-mesh"
_TILE = 128
_MESH_STEP = 1024               # padding step from _MESH_STEP_FROM txns on
_MESH_STEP_FROM = 8 * _MESH_STEP
_BITS32 = np.arange(32, dtype=np.uint32)

ANOMALY_CLASSES = ("G0", "G1c", "G-single", "G2-item")


# ---------------------------------------------------------------------------
# Packed layout (host side, numpy)
# ---------------------------------------------------------------------------

def mesh_tile(n_dev: int) -> int:
    """Row-count granularity a D-device mesh needs: rows split evenly
    AND every shard offset lands on a word boundary (the transpose
    step slices whole words)."""
    return int(np.lcm(_TILE, 32 * max(1, int(n_dev))))

def pad_for_mesh(n: int, n_dev: int = 1) -> int:
    """n rounded up to a multiple of mesh_tile(D) or, from
    `_MESH_STEP_FROM` (8,192) txns on, of lcm(mesh_tile(D), _MESH_STEP):
    histories a few txns apart then share one n_pad, one block from
    `_block_for` (1,024 or 2,048) and one compile, at most 1,023 rows
    of padding.  With 128-steps each neighbouring size compiled a
    program of its own, some with a 128-wide block.  The step only
    grows with n, so n_pad does too (the live tier's `grow_packed`
    relies on it)."""
    t = mesh_tile(n_dev)
    if n >= _MESH_STEP_FROM:
        t = int(np.lcm(t, _MESH_STEP))
    return max(t, t * math.ceil(n / t))

def plane_nbytes(n: int, packed: bool = True) -> int:
    """Resident bytes for one n x n boolean plane (the memory math
    docs/elle.md quotes)."""
    return (n * n) // 8 if packed else n * n

def pack_bits(dense) -> np.ndarray:
    """bool [..., n] -> uint32 [..., ceil32(n)] (bit b of word w is
    column w*32+b)."""
    dense = np.asarray(dense, bool)
    n = dense.shape[-1]
    w = math.ceil(n / 32)
    if n % 32:
        pad = np.zeros(dense.shape[:-1] + (w * 32 - n,), bool)
        dense = np.concatenate([dense, pad], axis=-1)
    bits = dense.reshape(dense.shape[:-1] + (w, 32)).astype(np.uint32)
    return (bits << _BITS32).sum(axis=-1, dtype=np.uint32)

def unpack_bits(packed, n: int) -> np.ndarray:
    """uint32 [..., W] -> bool [..., n]."""
    packed = np.asarray(packed, np.uint32)
    bits = (packed[..., None] >> _BITS32) & np.uint32(1)
    return bits.reshape(packed.shape[:-1] + (-1,))[..., :n].astype(bool)

def pack_planes(stack, n_pad: Optional[int] = None,
                n_dev: int = 1) -> np.ndarray:
    """Dense [P, n, n] bool plane stack -> packed uint32
    [P, n_pad, n_pad/32] padded for an n_dev-row mesh."""
    stack = np.asarray(stack, bool)
    p, n, _ = stack.shape
    if n_pad is None:
        n_pad = pad_for_mesh(n, n_dev)
    out = np.zeros((p, n_pad, n_pad // 32), np.uint32)
    if n:
        out[:, :n, :math.ceil(n / 32)] = pack_bits(stack)
    return out

def set_bits(plane: np.ndarray, src, dst) -> None:
    """Sparse edge insertion into one packed plane [n_pad, W]:
    plane[src, dst//32] |= 1 << (dst%32) (the bench's 100k/1M
    generators and elle/infer's plane construction build packed planes
    without a dense detour).  Rides the native ingest layer's batch
    word-OR (packext.or_words, GIL released) when available; the numpy
    fallback is the raveled-index form of np.bitwise_or.at — one flat
    word index per edge instead of a 2-d fancy tuple, measurably
    faster and pinned bit-identical to the per-edge loop by
    tests/test_packext.py."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if not len(src):
        return
    W = plane.shape[-1]
    masks = (np.uint32(1) << (dst & 31).astype(np.uint32))
    if plane.flags.c_contiguous:
        words = src * np.int64(W) + (dst >> 5)
        mod = _packext()
        if mod is not None:
            mod.or_words(plane, np.ascontiguousarray(words),
                         np.ascontiguousarray(masks))
            return
        np.bitwise_or.at(plane.reshape(-1), words, masks)
        return
    np.bitwise_or.at(plane, (src, dst >> 5), masks)


def clear_bits(plane: np.ndarray, src, dst) -> None:
    """Sparse edge RETRACTION from one packed plane — the inverse of
    `set_bits`, for the incremental tier's covered-removal deltas
    (elle/infer.IncrementalInference): plane[src, dst//32] &=
    ~(1 << (dst%32)).  Pure numpy (bitwise_and.at over raveled word
    indices, the same flat-index trick set_bits' fallback uses);
    retractions are orders of magnitude rarer than insertions, so the
    native OR path has no AND twin."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if not len(src):
        return
    W = plane.shape[-1]
    masks = ~(np.uint32(1) << (dst & 31).astype(np.uint32))
    if plane.flags.c_contiguous:
        words = src * np.int64(W) + (dst >> 5)
        np.bitwise_and.at(plane.reshape(-1), words, masks)
        return
    np.bitwise_and.at(plane, (src, dst >> 5), masks)


def grow_packed(packed: np.ndarray, n_pad: int) -> np.ndarray:
    """Re-pad a packed plane stack [..., rows, W] to a larger n_pad
    (row AND word growth — the packed layout is word-aligned, so the
    old words copy verbatim into the top-left corner)."""
    old_rows, old_w = packed.shape[-2], packed.shape[-1]
    if n_pad < old_rows:
        raise ValueError(f"cannot shrink packed planes "
                         f"{old_rows} -> {n_pad}")
    out = np.zeros(packed.shape[:-2] + (n_pad, n_pad // 32),
                   np.uint32)
    out[..., :old_rows, :old_w] = packed
    return out


def _packext():
    """The native ingest extension, honoring the pack-threads knob
    (JEPSEN_TPU_PACK_THREADS=0 pins the pure-numpy twins)."""
    from jepsen_tpu import native
    from jepsen_tpu.ops import planner
    if planner.pack_threads_effective() <= 0:
        return None
    return native.packext()

def _get_bit(row: np.ndarray, j: int) -> bool:
    return bool((row[j // 32] >> np.uint32(j % 32)) & np.uint32(1))

def _row_indices(row: np.ndarray, n: int) -> np.ndarray:
    """Set bit positions (< n) of one packed row [W]."""
    nz = np.nonzero(row)[0]
    if not len(nz):
        return np.empty(0, np.int64)
    bits = (row[nz, None] >> _BITS32) & np.uint32(1)
    words, pos = np.nonzero(bits)
    idx = nz[words] * 32 + pos
    return idx[idx < n]


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------

_PLAN_CACHE: dict = {}
_PLAN_STATS = {"hits": 0, "misses": 0}

def plan_cache_stats() -> dict:
    return dict(_PLAN_STATS)

def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _PLAN_STATS.update(hits=0, misses=0)

def _block_for(n_pad: int) -> int:
    """Largest tile (bits) that divides n_pad — bounds the dense
    in-flight unpacked tiles."""
    for b in (2048, 1024, 512, 256, 128):
        if n_pad % b == 0:
            return b
    return _TILE

def _device_fns(n_pad: int, block: int):
    """(unpack, pack, pmm) closures for one (n_pad, block) shape."""
    import jax
    import jax.numpy as jnp

    wb = block // 32
    w = n_pad // 32
    nk = n_pad // block
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def unpack(words):
        # uint32 [r, v] -> bf16 [r, v*32]
        r, v = words.shape
        bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
        return bits.reshape(r, v * 32).astype(jnp.bfloat16)

    def pack(bits):
        # bool/0-1 [r, c] (c % 32 == 0) -> uint32 [r, c//32]
        r, c = bits.shape
        b = bits.reshape(r, c // 32, 32).astype(jnp.uint32)
        return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)

    def pmm(a, b):
        """Packed boolean product: a [r, W] (columns packed) @
        b [n_pad, W] (columns packed) -> [r, W].  Blocked so only
        (r x block) + (block x block) dense bf16 tiles exist at once;
        f32 accumulation keeps the 0/1 product exact."""
        r = a.shape[0]

        def jbody(j, out):
            def kbody(k, acc):
                at = unpack(jax.lax.dynamic_slice(
                    a, (0, k * wb), (r, wb)))
                bt = unpack(jax.lax.dynamic_slice(
                    b, (k * block, j * wb), (block, wb)))
                return acc + jnp.dot(
                    at, bt, preferred_element_type=jnp.float32)
            acc = jax.lax.fori_loop(
                0, nk, kbody, jnp.zeros((r, block), jnp.float32))
            return jax.lax.dynamic_update_slice(
                out, pack(acc > 0.5), (0, j * wb))

        return jax.lax.fori_loop(
            0, nk, jbody, jnp.zeros((r, w), jnp.uint32))

    return unpack, pack, pmm

def closure_steps(n_pad: int) -> int:
    """The most squaring rounds a closure over n_pad txns runs: paths
    double in length each round, and the early exit usually stops it
    far sooner."""
    return max(1, math.ceil(math.log2(max(n_pad - 1, 2))))

def _build_kernel(n_pad: int, devs: tuple, block: int,
                  warm: bool = False):
    """One compiled shard_map program: packed pair closure with early
    exit + class masks + per-device defining-edge picks.

    With `warm` (the incremental tier, ISSUE 18) the program takes the
    previous closure triple (cww, p0, p1) as three extra row-sharded
    operands seeding the while_loop, and returns the settled triple
    alongside the verdict.  The state is monotone, so the same
    early-exit psum that proves cold convergence proves warm
    convergence — a delta that extends the frontier by a short path
    settles in ~log2(delta diameter) rounds, not log2(n)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    from jepsen_tpu.ops.shard_map_compat import (all_gather_frontier,
                                                 frontier_settled,
                                                 shard_map_compat)

    n_dev = len(devs)
    m = n_pad // n_dev
    w = n_pad // 32
    wm = m // 32
    steps = closure_steps(n_pad)
    unpack, pack, pmm = _device_fns(n_pad, block)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    nk = n_pad // block
    wb = block // 32

    def tpose(full, a0):
        """Packed transpose restricted to this shard's rows:
        out[a, bit b] = full[b, a0 + a]."""
        def bbody(k, out):
            blk = jax.lax.dynamic_slice(
                full, (k * block, a0 // 32), (block, wm))
            bits = ((blk[:, :, None] >> shifts) & jnp.uint32(1)
                    ).reshape(block, m)
            return jax.lax.dynamic_update_slice(
                out, pack(bits.T), (0, k * wb))
        return jax.lax.fori_loop(
            0, nk, bbody, jnp.zeros((m, w), jnp.uint32))

    def pick(mask, a0):
        """(found, a, b) — lowest (a, b) row-major, matching the dense
        engine's argmax pick so cross-engine edges compare equal."""
        row_any = (mask != 0).any(axis=1)
        found = row_any.any()
        al = jnp.argmax(row_any)
        rowm = mask[al]
        wi = jnp.argmax(rowm != 0)
        word = rowm[wi]
        bit = jnp.argmax(((word >> shifts) & jnp.uint32(1)) > 0)
        return (found, (a0 + al).astype(jnp.int32),
                (wi * 32 + bit).astype(jnp.int32))

    def body(ww, wr, rw, od, *seed):
        idx = jax.lax.axis_index("rows")
        a0 = idx * m
        rows_idx = a0 + jnp.arange(m)
        eye = jnp.zeros((m, w), jnp.uint32).at[
            jnp.arange(m), rows_idx // 32].set(
            jnp.uint32(1) << (rows_idx % 32).astype(jnp.uint32))
        base = ww | wr | od

        def gather(x):
            return all_gather_frontier(x, "rows")

        def cond(st):
            _, _, _, rounds, done = st
            return (~done) & (rounds < steps)

        def round_(st):
            cww, p0, p1, rounds, _ = st
            cww_f, p0_f, p1_f = gather(cww), gather(p0), gather(p1)
            q, q_f = p0 | p1, p0_f | p1_f
            cww2 = cww | pmm(cww, cww_f)
            p0n = p0 | pmm(p0, p0_f)
            p1n = p1 | pmm(q, p1_f) | pmm(p1, q_f)
            ch = (jnp.any(cww2 != cww) | jnp.any(p0n != p0)
                  | jnp.any(p1n != p1))
            done = frontier_settled(ch, "rows")
            return cww2, p0n, p1n, rounds + 1, done

        init = (ww | od, base | eye, rw)
        if warm:
            # OR the previous closure under the fresh direct planes:
            # the union's closure equals the exact closure as long as
            # every retraction since the last cold rebuild was covered
            # (elle/infer.IncrementalInference's rebuild contract)
            init = (init[0] | seed[0], init[1] | seed[1],
                    init[2] | seed[2])
        cww, p0, p1, rounds, _ = jax.lax.while_loop(
            cond, round_, init + (jnp.int32(0), jnp.bool_(False)))

        t_cww = tpose(gather(cww), a0)
        t_p0 = tpose(gather(p0), a0)
        t_p1 = tpose(gather(p1), a0)
        masks = (ww & t_cww,               # G0
                 wr & t_p0,               # G1c   (planes have no
                 rw & t_p0,               # G-single  diagonal, so
                 rw & t_p1 & ~t_p0)       # G2-item   p0's eye is inert)
        flags, edges = [], []
        for mk in masks:
            f, a, b = pick(mk, a0)
            flags.append(f)
            edges.append(jnp.stack([a, b]))
        out = (jnp.stack(flags)[None], jnp.stack(edges)[None],
               rounds.reshape(1))
        if warm:
            out += (cww, p0, p1)
        return out

    mesh = Mesh(np.array(list(devs)), ("rows",))
    spec = PartitionSpec("rows")
    fn = shard_map_compat(
        body, mesh=mesh, in_specs=(spec,) * (7 if warm else 4),
        out_specs=(spec, spec, spec) + ((spec,) * 3 if warm else ()))
    return jax.jit(fn), mesh

def _kernel(n_pad: int, devs: tuple, warm: bool = False):
    """Compiled-plan cache over (n_pad, devices, block, warm) shape
    buckets, hit/miss counted (the mesh-path analogue of the dense
    engine's kernel-bucket counters)."""
    block = _block_for(n_pad)
    key = (n_pad, devs, block, "warm") if warm \
        else (n_pad, devs, block)
    hit = key in _PLAN_CACHE
    if hit:
        _PLAN_STATS["hits"] += 1
    else:
        _PLAN_CACHE[key] = _build_kernel(n_pad, devs, block,
                                         warm=warm)
        _PLAN_STATS["misses"] += 1
    try:
        from jepsen_tpu import telemetry
        telemetry.REGISTRY.counter(
            "jepsen_elle_mesh_plan_total",
            result="hit" if hit else "miss").inc()
    except Exception:           # noqa: BLE001 - telemetry is advisory
        pass
    return _PLAN_CACHE[key]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def mesh_devices(devices=None, max_devices: Optional[int] = None) -> list:
    """The devices the mesh runs on: `devices`, or all of JAX's, cut
    to `max_devices`; none is a BackendUnavailable."""
    import jax
    devs = list(devices) if devices is not None else list(jax.devices())
    if max_devices:
        devs = devs[:max_devices]
    if not devs:
        from jepsen_tpu.errors import BackendUnavailable
        raise BackendUnavailable("no jax devices for the elle mesh",
                                 backend="none")
    return devs

def classify_packed(packed_stacks: Sequence[np.ndarray],
                    ns: Sequence[int],
                    include_order: bool = True,
                    devices=None,
                    max_devices: Optional[int] = None,
                    st: Optional[telemetry.Stages] = None) -> list:
    """Classify histories whose planes are ALREADY bit-packed
    ([len(PLANES), n_pad, n_pad/32] uint32 each, `pack_planes` /
    `set_bits` layout, n_pad a multiple of `mesh_tile(D)`).

    Each history runs as one sharded device program over the row axis
    of the mesh (histories at mesh scale are individually huge; the
    batch axis is a host loop).  Returns one row per history:
    {"anomalies": {cls: (a, b)}, "n", "n_pad", "rounds", "shards",
    "backend"}, backend being the devices' platform.

    Each call is the caller's `kernel` span on `st` (put, closure
    call and block, fetch: the `jepsen.kernel.put/.call/.fetch`
    annotations), and tallies the call, its wire bytes and its
    closure rounds; without `st` the tallies reach the registry
    here."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    devs = mesh_devices(devices, max_devices)
    own = st is None
    st = telemetry.Stages() if own else st
    out = []
    for packed, n in zip(packed_stacks, ns):
        packed = np.asarray(packed, np.uint32)
        n_pad = packed.shape[-2]
        n_dev = len(devs)
        if n_pad % mesh_tile(n_dev):
            raise ValueError(
                f"n_pad={n_pad} not a multiple of mesh_tile({n_dev})="
                f"{mesh_tile(n_dev)}; pad with pad_for_mesh")
        fn, mesh = _kernel(n_pad, tuple(devs))
        sh = NamedSharding(mesh, PartitionSpec("rows"))
        with st.span("kernel"):
            with telemetry.trace("kernel.put"):
                od = (packed[3] | packed[4] if include_order
                      else np.zeros_like(packed[0]))
                args = [jax.device_put(x, sh)
                        for x in (packed[0], packed[1], packed[2], od)]
            with telemetry.trace("kernel.call"):
                res = jax.block_until_ready(fn(*args))
            with telemetry.trace("kernel.fetch"):
                flags, edges, rounds = (np.asarray(x) for x in res)
        st.tally(telemetry.DEVICE_CALLS, engine=ENGINE)
        st.tally(telemetry.WIRE_BYTES, 4 * packed[0].nbytes,
                 engine=ENGINE, direction="h2d")
        st.tally(telemetry.WIRE_BYTES,
                 flags.nbytes + edges.nbytes + rounds.nbytes,
                 engine=ENGINE, direction="d2h")
        st.tally(telemetry.ELLE_ROUNDS, int(rounds[0]), n_pad=str(n_pad))
        found: dict = {}
        for c, cls in enumerate(ANOMALY_CLASSES):
            hits = np.nonzero(flags[:, c])[0]
            if len(hits):
                d = int(hits[0])    # lowest device = lowest row block
                found[cls] = (int(edges[d, c, 0]), int(edges[d, c, 1]))
        out.append({"anomalies": found, "n": int(n), "n_pad": n_pad,
                    "rounds": int(rounds[0]), "shards": n_dev,
                    "backend": devs[0].platform})
    if own:
        st.flush()
    return out

CLOSURE_PLANES = 3                     # (cww, p0, p1)


def empty_closure(n_pad: int) -> np.ndarray:
    """A cold closure seed: the warm entry points treat all-zeros as
    'start from the direct planes alone'."""
    return np.zeros((CLOSURE_PLANES, n_pad, n_pad // 32), np.uint32)


def classify_packed_warm(packed_stack: np.ndarray, n: int,
                         closure: Optional[np.ndarray] = None,
                         include_order: bool = True,
                         devices=None,
                         max_devices: Optional[int] = None) -> tuple:
    """Incremental classify on the device mesh: one history's packed
    planes plus the PREVIOUS settled closure triple ([3, n_pad, W]
    uint32, or None for a cold start).  The while_loop seeds from the
    old closure OR'd under the current direct planes, so the delta's
    frontier-product rounds are all that run (monotone state — the
    early-exit psum proves convergence exactly as in the cold path).
    Returns (row, closure) where `row` matches `classify_packed` rows
    and `closure` is the settled triple to seed the next window."""
    import jax

    devs = mesh_devices(devices, max_devices)
    packed = np.asarray(packed_stack, np.uint32)
    n_pad = packed.shape[-2]
    n_dev = len(devs)
    if n_pad % mesh_tile(n_dev):
        raise ValueError(
            f"n_pad={n_pad} not a multiple of mesh_tile({n_dev})="
            f"{mesh_tile(n_dev)}; pad with pad_for_mesh")
    if closure is None:
        closure = empty_closure(n_pad)
    closure = np.asarray(closure, np.uint32)
    if closure.shape[-2] != n_pad:
        closure = grow_packed(closure, n_pad)
    fn, mesh = _kernel(n_pad, tuple(devs), warm=True)
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec("rows"))
    ww, wr, rw = (jax.device_put(packed[i], sh) for i in range(3))
    if include_order:
        od = jax.device_put(packed[3] | packed[4], sh)
    else:
        od = jax.device_put(np.zeros_like(packed[0]), sh)
    c0, q0, r0 = (jax.device_put(closure[i], sh) for i in range(3))
    flags, edges, rounds, cww, p0, p1 = fn(ww, wr, rw, od, c0, q0, r0)
    flags, edges, rounds = (np.asarray(x)
                            for x in (flags, edges, rounds))
    found: dict = {}
    for c, cls in enumerate(ANOMALY_CLASSES):
        hits = np.nonzero(flags[:, c])[0]
        if len(hits):
            d = int(hits[0])
            found[cls] = (int(edges[d, c, 0]), int(edges[d, c, 1]))
    row = {"anomalies": found, "n": int(n), "n_pad": n_pad,
           "rounds": int(rounds[0]), "shards": n_dev}
    out_closure = np.stack([np.asarray(cww), np.asarray(p0),
                            np.asarray(p1)]).astype(np.uint32)
    return row, out_closure


def classify_host_warm(packed_stack: np.ndarray, n: int,
                       closure: Optional[np.ndarray] = None,
                       include_order: bool = True) -> tuple:
    """Numpy twin of `classify_packed_warm` — same update rule, same
    early exit, same masks, same lowest-row-major defining-edge pick,
    so verdicts and closures interchange with the device path
    bit-for-bit (the live txn tenants' default engine; dense float32
    matmuls are exact 0/1 counts below 2^24)."""
    packed = np.asarray(packed_stack, np.uint32)
    n_pad = packed.shape[-2]
    if n_pad == 0:
        return ({"anomalies": {}, "n": 0, "n_pad": 0, "rounds": 0,
                 "shards": 0}, empty_closure(0))
    dense = [unpack_bits(packed[i], n_pad) for i in range(len(PLANES))]
    ww, wr, rw = dense[:3]
    od = (dense[3] | dense[4]) if include_order \
        else np.zeros_like(ww)
    base = ww | wr | od
    eye = np.eye(n_pad, dtype=bool)
    cww = ww | od
    p0 = base | eye
    p1 = rw.copy()
    if closure is not None and closure.shape[-2]:
        closure = np.asarray(closure, np.uint32)
        if closure.shape[-2] != n_pad:
            closure = grow_packed(closure, n_pad)
        cww |= unpack_bits(closure[0], n_pad)
        p0 |= unpack_bits(closure[1], n_pad)
        p1 |= unpack_bits(closure[2], n_pad)

    def bmm(a, b):
        return (a.astype(np.float32) @ b.astype(np.float32)) > 0.5

    steps = closure_steps(n_pad)
    rounds = 0
    done = False
    while not done and rounds < steps:
        q = p0 | p1
        cww2 = cww | bmm(cww, cww)
        p0n = p0 | bmm(p0, p0)
        p1n = p1 | bmm(q, p1) | bmm(p1, q)
        done = (np.array_equal(cww2, cww) and np.array_equal(p0n, p0)
                and np.array_equal(p1n, p1))
        cww, p0, p1 = cww2, p0n, p1n
        rounds += 1
    masks = (ww & cww.T, wr & p0.T, rw & p0.T, rw & p1.T & ~p0.T)
    found: dict = {}
    for cls, mk in zip(ANOMALY_CLASSES, masks):
        if mk.any():
            a, b = np.unravel_index(int(np.argmax(mk)), mk.shape)
            found[cls] = (int(a), int(b))
    row = {"anomalies": found, "n": int(n), "n_pad": n_pad,
           "rounds": rounds, "shards": 0}
    out_closure = np.stack([pack_bits(cww), pack_bits(p0),
                            pack_bits(p1)]).astype(np.uint32)
    return row, out_closure


def packed_product(a_dense, b_dense) -> np.ndarray:
    """Test pin: the device packed boolean product of two dense bool
    matrices, returned dense (must equal `(a @ b) > 0`)."""
    import jax

    a = np.asarray(a_dense, bool)
    n = a.shape[0]
    n_pad = pad_for_mesh(n, 1)
    ap = pack_planes(a[None])[0]
    bp = pack_planes(np.asarray(b_dense, bool)[None])[0]
    _, _, pmm = _device_fns(n_pad, _block_for(n_pad))
    out = np.asarray(jax.jit(pmm)(ap, bp))
    return unpack_bits(out, n)[:n]


# ---------------------------------------------------------------------------
# Witness recovery over packed planes — level-BFS, no dense planes
# ---------------------------------------------------------------------------

def _frontier_nodes(frontier: np.ndarray, n: int) -> np.ndarray:
    return _row_indices(frontier, n)

def _succ_or(adj: np.ndarray, nodes: np.ndarray) -> np.ndarray:
    if not len(nodes):
        return np.zeros(adj.shape[1], np.uint32)
    return np.bitwise_or.reduce(adj[nodes], axis=0)

def _bfs_path_packed(adj: np.ndarray, src: int, dst: int,
                     n: int) -> Optional[list]:
    """Shortest path src -> dst (length >= 1) over one packed
    adjacency [n_pad, W], or None.  Frontiers are packed bitsets; the
    expansion is one OR-reduction over the frontier's rows."""
    w = adj.shape[1]
    visited = np.zeros(w, np.uint32)
    frontier = np.zeros(w, np.uint32)
    frontier[src // 32] = np.uint32(1) << np.uint32(src % 32)
    visited |= frontier
    levels = []
    while frontier.any():
        nodes = _frontier_nodes(frontier, n)
        levels.append(nodes)
        nxt = _succ_or(adj, nodes)
        if _get_bit(nxt, dst):
            path = [dst]
            cur = dst
            for lv in reversed(levels):
                pred = lv[((adj[lv, cur // 32]
                            >> np.uint32(cur % 32)) & 1).astype(bool)]
                cur = int(pred[0])
                path.append(cur)
            path.reverse()
            return path
        nxt &= ~visited
        visited |= nxt
        frontier = nxt
    return None

def _bfs_path_with_rw_packed(base: np.ndarray, rw: np.ndarray,
                             src: int, dst: int,
                             n: int) -> Optional[list]:
    """Path src -> dst over base|rw using >= 1 rw edge: level-BFS over
    the (node, seen-rw) product graph with packed frontiers."""
    full = base | rw
    w = base.shape[1]
    f0 = np.zeros(w, np.uint32)
    f0[src // 32] = np.uint32(1) << np.uint32(src % 32)
    f1 = np.zeros(w, np.uint32)
    v0, v1 = f0.copy(), np.zeros(w, np.uint32)
    levels = []                      # (nodes0, nodes1) per level
    while f0.any() or f1.any():
        n0 = _frontier_nodes(f0, n)
        n1 = _frontier_nodes(f1, n)
        levels.append((n0, n1))
        nxt1 = _succ_or(full, n1) | _succ_or(rw, n0)
        if _get_bit(nxt1, dst):
            # walk back through the product graph
            path, cur, seen = [dst], dst, True
            for lv0, lv1 in reversed(levels):
                if seen:
                    p1 = lv1[((full[lv1, cur // 32]
                               >> np.uint32(cur % 32)) & 1
                              ).astype(bool)] if len(lv1) else lv1
                    if len(p1):
                        cur = int(p1[0])            # stay in seen-rw
                    else:
                        p0 = lv0[((rw[lv0, cur // 32]
                                   >> np.uint32(cur % 32)) & 1
                                  ).astype(bool)]
                        cur, seen = int(p0[0]), False
                else:
                    p0 = lv0[((base[lv0, cur // 32]
                               >> np.uint32(cur % 32)) & 1
                              ).astype(bool)]
                    cur = int(p0[0])
                path.append(cur)
            path.reverse()
            return path
        nxt0 = _succ_or(base, n0)
        nxt0 &= ~v0
        nxt1 &= ~v1
        v0 |= nxt0
        v1 |= nxt1
        f0, f1 = nxt0, nxt1
    return None

def find_witness_packed(packed_stack: np.ndarray, cls: str, edge,
                        n: int, include_order: bool = True
                        ) -> Optional[list]:
    """One explicit cycle [a, b, ..., a] for a mesh-found anomaly —
    the packed-layout twin of `elle_graph.find_witness`."""
    ww, wr, rw, po, rt = (np.asarray(packed_stack[i], np.uint32)
                          for i in range(len(PLANES)))
    order = (po | rt) if include_order else np.zeros_like(ww)
    a, b = int(edge[0]), int(edge[1])
    if cls == "G0":
        back = _bfs_path_packed(ww | order, b, a, n)
    elif cls in ("G1c", "G-single"):
        back = _bfs_path_packed(ww | wr | order, b, a, n)
    elif cls == "G2-item":
        back = _bfs_path_with_rw_packed(ww | wr | order, rw, b, a, n)
    else:
        raise ValueError(f"unknown anomaly class {cls!r}")
    if back is None:
        return None
    return [a] + back


# ---------------------------------------------------------------------------
# Sparse host oracle — SCC + bounded per-edge probes, honest caps
# ---------------------------------------------------------------------------

def _sccs(adj: np.ndarray, n: int) -> np.ndarray:
    """Strongly-connected components of one packed adjacency (Tarjan,
    iterative).  Returns comp id per node; comp ids are arbitrary."""
    UNSET = -1
    index = np.full(n, UNSET, np.int64)
    low = np.zeros(n, np.int64)
    comp = np.full(n, UNSET, np.int64)
    on_stack = np.zeros(n, bool)
    succ_cache: dict = {}

    def succ(u):
        s = succ_cache.get(u)
        if s is None:
            s = _row_indices(adj[u], n)
            succ_cache[u] = s
        return s

    counter = 0
    n_comp = 0
    tstack: list = []
    for root in range(n):
        if index[root] != UNSET:
            continue
        work = [(root, 0)]
        while work:
            u, pi = work[-1]
            if pi == 0:
                index[u] = low[u] = counter
                counter += 1
                tstack.append(u)
                on_stack[u] = True
            advanced = False
            su = succ(u)
            while pi < len(su):
                v = int(su[pi])
                pi += 1
                if index[v] == UNSET:
                    work[-1] = (u, pi)
                    work.append((v, 0))
                    advanced = True
                    break
                if on_stack[v]:
                    low[u] = min(low[u], index[v])
            if advanced:
                continue
            work.pop()
            if low[u] == index[u]:
                while True:
                    v = tstack.pop()
                    on_stack[v] = False
                    comp[v] = n_comp
                    if v == u:
                        break
                n_comp += 1
            if work:
                pu = work[-1][0]
                low[pu] = min(low[pu], low[u])
    return comp

def _edges_of(plane: np.ndarray, n: int):
    for u in range(n):
        for v in _row_indices(plane[u], n):
            yield u, int(v)

def classify_host_packed(packed_stack: np.ndarray, n: int,
                         include_order: bool = True,
                         deadline_s: Optional[float] = None,
                         max_rw_probe: int = 4096) -> dict:
    """Sparse host oracle over packed planes: exact G0/G1c via SCC in
    O(V+E); G-single/G2 via bounded per-rw-edge probes (SCC
    pre-filter, then packed BFS).  Never lies about its bounds: a
    blown `deadline_s` or rw probe cap yields an `unknown` degradation
    row with the cap disclosed (no-silent-caps)."""
    t0 = time.monotonic()

    def over_deadline() -> bool:
        return (deadline_s is not None
                and time.monotonic() - t0 > deadline_s)

    def degrade(reason: str, **extra) -> dict:
        row = {"anomalies": {}, "n": n,
               "n_pad": int(packed_stack.shape[-2]),
               "unknown": True, "degraded": reason,
               "elapsed_s": round(time.monotonic() - t0, 3)}
        if deadline_s is not None:
            row["deadline_s"] = deadline_s
        row.update(extra)
        return row

    ww, wr, rw, po, rt = (np.asarray(packed_stack[i], np.uint32)
                          for i in range(len(PLANES)))
    order = (po | rt) if include_order else np.zeros_like(ww)
    base = ww | wr | order
    found: dict = {}
    if n == 0:
        return {"anomalies": {}, "n": 0, "n_pad": 0}

    comp_ww = _sccs(ww | order, n)
    if over_deadline():
        return degrade("host-deadline", stage="scc-ww")
    for u, v in _edges_of(ww, n):
        if comp_ww[u] == comp_ww[v]:
            found["G0"] = (u, v)
            break
    comp = _sccs(base, n)
    if over_deadline():
        return degrade("host-deadline", stage="scc-base")
    for u, v in _edges_of(wr, n):
        if comp[u] == comp[v]:
            found["G1c"] = (u, v)
            break

    # rw probes: a zero-rw return (base path b=>a) is G-single; only a
    # >=1-rw return WITHOUT a zero-rw one defines G2 (the dense
    # engine's priority mask).  Same-SCC is a free G-single certificate
    # (edge a->b is in neither graph, so reachability may hold across
    # comps too — those pay a BFS each, hence the disclosed cap).
    probed = 0
    capped = False
    want = {"G-single", "G2-item"} - set(found)
    for a, b in _edges_of(rw, n):
        if not want:
            break
        if over_deadline():
            return degrade("host-deadline", stage="rw-probe",
                           rw_probed=probed, partial=dict(
                               (k, list(v)) for k, v in found.items()))
        if probed >= max_rw_probe:
            capped = True
            break
        probed += 1
        if "G-single" in want and comp[a] == comp[b]:
            found["G-single"] = (a, b)
            want.discard("G-single")
            continue
        zero_rw = (comp[a] == comp[b]
                   or _bfs_path_packed(base, b, a, n) is not None)
        if zero_rw:
            if "G-single" in want:
                found["G-single"] = (a, b)
                want.discard("G-single")
            continue
        if ("G2-item" in want and _bfs_path_with_rw_packed(
                base, rw, b, a, n) is not None):
            found["G2-item"] = (a, b)
            want.discard("G2-item")

    if capped and want:
        # classes still open when the cap hit: the verdict would be a
        # silent pass — degrade honestly instead
        return degrade("rw-probe-cap", rw_probed=probed,
                       max_rw_probe=max_rw_probe,
                       partial={k: list(v) for k, v in found.items()})
    return {"anomalies": found, "n": n,
            "n_pad": int(packed_stack.shape[-2]), "rw_probed": probed}
