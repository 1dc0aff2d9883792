/* Native CPU linearizability oracle (CPython extension).
 *
 * The C twin of jepsen_tpu/ops/wgl_cpu.check's hot loop — Lowe-style
 * just-in-time linearization with memoization, the same algorithm
 * knossos :linear implements on the JVM (checker.clj:141-145).  It
 * exists to BOUND THE BASELINE CONSTANT: device speedups are stated
 * against both the Python oracle (the knossos-equivalent
 * reference implementation) and this native one, so no ratio hides an
 * interpreter constant (VERDICT r2 #5).
 *
 * Works on the integer encoding (uop transition tables) the device
 * kernels use; rich host-side models stay on the Python oracle.
 *
 * run(ev_kind u8[nev] bytes, ev_cid i32[nev] bytes,
 *     call_uop i32[ncalls] bytes, legal u8[U*Sn] bytes,
 *     next u32[U*Sn] bytes, Sn, init_state, max_configs,
 *     time_limit_ms)
 * -> (code, events_done, fail_event, fail_cid, seen_total,
 *     survivors bytes [(u64 mask, u64 state) pairs, <= 16],
 *     pend_cid bytes i32[64])
 * code: 1 valid, 0 invalid, 2 config-explosion, 3 timeout,
 *       4 out-of-scope (> 64 simultaneously pending calls).
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>
#include <string.h>
#include <time.h>

#include "scancommon.h"

typedef struct {
    uint64_t mask;
    uint32_t state;
    uint32_t used;               /* epoch stamp; 0 = never used */
} centry;

typedef struct {
    centry *e;
    long cap, n;
} cset;

static int cset_init(cset *s, long cap) {
    long c = 64;
    while (c < cap * 2) c <<= 1;
    s->e = PyMem_Malloc(c * sizeof(centry));
    if (!s->e) return -1;
    memset(s->e, 0, c * sizeof(centry));
    s->cap = c;
    s->n = 0;
    return 0;
}

static uint64_t chash(uint64_t mask, uint32_t state) {
    uint64_t h = mask * 0x9E3779B97F4A7C15ULL;
    h ^= (uint64_t)state * 0xC2B2AE3D27D4EB4FULL;
    h ^= h >> 29;
    return h;
}

static int cset_grow(cset *s, uint32_t epoch);

/* insert; returns 1 if new, 0 if present, -1 OOM.  Entries from older
 * epochs read as empty, so clearing the set between returns is one
 * epoch increment instead of a memset. */
static int cset_add(cset *s, uint64_t mask, uint32_t state,
                    uint32_t epoch) {
    if (s->n * 2 >= s->cap && cset_grow(s, epoch) < 0) return -1;
    uint64_t m = (uint64_t)s->cap - 1;
    uint64_t i = chash(mask, state) & m;
    for (;;) {
        centry *e = &s->e[i];
        if (e->used != epoch) {
            e->mask = mask;
            e->state = state;
            e->used = epoch;
            s->n++;
            return 1;
        }
        if (e->mask == mask && e->state == state) return 0;
        i = (i + 1) & m;
    }
}

static int cset_grow(cset *s, uint32_t epoch) {
    centry *old = s->e;
    long ocap = s->cap;
    s->e = PyMem_Malloc(2 * ocap * sizeof(centry));
    if (!s->e) { s->e = old; return -1; }
    memset(s->e, 0, 2 * ocap * sizeof(centry));
    s->cap = 2 * ocap;
    s->n = 0;
    for (long i = 0; i < ocap; i++)
        if (old[i].used == epoch)
            cset_add(s, old[i].mask, old[i].state, epoch);
    PyMem_Free(old);
    return 0;
}

typedef struct {
    uint64_t *mask;
    uint32_t *state;
    long len, cap;
} clist;

static int clist_push(clist *l, uint64_t mask, uint32_t state) {
    if (l->len == l->cap) {
        long nc = l->cap ? l->cap * 2 : 64;
        uint64_t *nm = PyMem_Realloc(l->mask, nc * sizeof(uint64_t));
        if (!nm) return -1;
        l->mask = nm;
        uint32_t *ns = PyMem_Realloc(l->state, nc * sizeof(uint32_t));
        if (!ns) return -1;
        l->state = ns;
        l->cap = nc;
    }
    l->mask[l->len] = mask;
    l->state[l->len] = state;
    l->len++;
    return 0;
}

static double now_ms(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return ts.tv_sec * 1000.0 + ts.tv_nsec / 1e6;
}

static PyObject *run(PyObject *self, PyObject *args) {
    Py_buffer bkind = {0}, bcid = {0}, buop = {0}, blegal = {0},
              bnext = {0};
    long Sn, init_state, max_configs;
    double time_limit_ms;
    if (!PyArg_ParseTuple(args, "y*y*y*y*y*llld",
                          &bkind, &bcid, &buop, &blegal, &bnext,
                          &Sn, &init_state, &max_configs,
                          &time_limit_ms))
        return NULL;
    Py_ssize_t nev = bkind.len;
    const uint8_t *kind = bkind.buf;
    const int32_t *cid = bcid.buf;
    const int32_t *uop = buop.buf;
    const uint8_t *legal = blegal.buf;
    const uint32_t *next = bnext.buf;
    Py_ssize_t ncalls = buop.len / 4;

    PyObject *result = NULL;
    int code = 1;
    long events_done = 0, fail_event = -1, fail_cid = -1;
    long seen_total_max = 0;
    double t0 = now_ms();

    /* live pending calls: bit -> cid (-1 free) + summary bitmask */
    int32_t pend_cid[64];
    uint64_t pend_mask = 0;
    uint32_t epoch = 0;
    for (int i = 0; i < 64; i++) pend_cid[i] = -1;
    /* bit index per call id (only valid while pending) */
    int8_t *call_bit = PyMem_Malloc((ncalls ? ncalls : 1));
    clist configs = {0}, done = {0}, frontier = {0}, nxt = {0};
    cset seen = {0};
    if (!call_bit) { PyErr_NoMemory(); goto fail; }
    memset(call_bit, -1, ncalls ? ncalls : 1);
    if (cset_init(&seen, 64) < 0) goto nomem;

    if (clist_push(&configs, 0, (uint32_t)init_state) < 0)
        goto nomem;

    for (Py_ssize_t e = 0; e < nev; e++) {
        events_done++;
        int32_t c = cid[e];
        if (kind[e] == 0) {                    /* invoke */
            if (pend_mask == ~0ULL) { code = 4; goto out; }
            int b = __builtin_ctzll(~pend_mask);
            pend_mask |= 1ULL << b;
            pend_cid[b] = c;
            call_bit[c] = (int8_t)b;
            continue;
        }
        /* return of call c: BFS closure until every config has c */
        uint64_t cbit = 1ULL << call_bit[c];
        done.len = 0;
        frontier.len = 0;
        epoch++;
        seen.n = 0;
        if (epoch == 0) {            /* u32 wrap: hard reset */
            memset(seen.e, 0, seen.cap * sizeof(centry));
            epoch = 1;
        }
        for (long i = 0; i < configs.len; i++) {
            if (cset_add(&seen, configs.mask[i], configs.state[i],
                         epoch) < 0)
                goto nomem;
            if (clist_push(&frontier, configs.mask[i],
                           configs.state[i]) < 0)
                goto nomem;
        }
        while (frontier.len) {
            if (time_limit_ms > 0 && now_ms() - t0 > time_limit_ms) {
                code = 3;
                goto out;
            }
            nxt.len = 0;
            for (long i = 0; i < frontier.len; i++) {
                uint64_t mask = frontier.mask[i];
                uint32_t st = frontier.state[i];
                if (mask & cbit) {
                    if (clist_push(&done, mask, st) < 0) goto nomem;
                    continue;
                }
                uint64_t todo = pend_mask & ~mask;
                while (todo) {
                    int b = __builtin_ctzll(todo);
                    todo &= todo - 1;
                    int32_t j = pend_cid[b];
                    int32_t u = uop[j];
                    if (!legal[(int64_t)u * Sn + st]) continue;
                    uint32_t st2 = next[(int64_t)u * Sn + st];
                    int r = cset_add(&seen, mask | (1ULL << b), st2,
                                     epoch);
                    if (r < 0) goto nomem;
                    if (r == 1 && clist_push(&nxt, mask | (1ULL << b),
                                             st2) < 0)
                        goto nomem;
                }
            }
            if (seen.n > max_configs) { code = 2; goto out; }
            /* swap frontier <- nxt */
            {
                clist tmp = frontier;
                frontier = nxt;
                nxt = tmp;
            }
        }
        if (seen.n > seen_total_max) seen_total_max = seen.n;
        if (done.len == 0) {
            code = 0;
            fail_event = (long)e;
            fail_cid = c;
            goto out;
        }
        /* retire c's bit: dedupe (mask & ~cbit, state) */
        epoch++;
        seen.n = 0;
        if (epoch == 0) {
            memset(seen.e, 0, seen.cap * sizeof(centry));
            epoch = 1;
        }
        configs.len = 0;
        for (long i = 0; i < done.len; i++) {
            uint64_t m2 = done.mask[i] & ~cbit;
            int r = cset_add(&seen, m2, done.state[i], epoch);
            if (r < 0) goto nomem;
            if (r == 1 && clist_push(&configs, m2,
                                     done.state[i]) < 0)
                goto nomem;
        }
        pend_mask &= ~cbit;
        pend_cid[call_bit[c]] = -1;
        call_bit[c] = -1;
    }

out:
    {
        /* survivors: up to 16 configs (knossos truncates to 10 anyway,
         * checker.clj:155-158) */
        long ns = configs.len < 16 ? configs.len : 16;
        uint64_t surv[32];
        for (long i = 0; i < ns; i++) {
            surv[2 * i] = configs.mask[i];
            surv[2 * i + 1] = configs.state[i];
        }
        result = Py_BuildValue(
            "(llllly#y#)", (long)code, events_done, fail_event,
            fail_cid, seen_total_max,
            (char *)surv, ns * 2 * (Py_ssize_t)sizeof(uint64_t),
            (char *)pend_cid, (Py_ssize_t)sizeof(pend_cid));
    }
    goto cleanup;

nomem:
    PyErr_NoMemory();
fail:
cleanup:
    PyMem_Free(call_bit);
    PyMem_Free(configs.mask);
    PyMem_Free(configs.state);
    PyMem_Free(done.mask);
    PyMem_Free(done.state);
    PyMem_Free(frontier.mask);
    PyMem_Free(frontier.state);
    PyMem_Free(nxt.mask);
    PyMem_Free(nxt.state);
    PyMem_Free(seen.e);
    if (bkind.obj) PyBuffer_Release(&bkind);
    if (bcid.obj) PyBuffer_Release(&bcid);
    if (buop.obj) PyBuffer_Release(&buop);
    if (blegal.obj) PyBuffer_Release(&blegal);
    if (bnext.obj) PyBuffer_Release(&bnext);
    return result;
}

/* Columnar ingest: build (ev_kind, ev_cid, call_uop) event streams
 * straight from the history's struct-of-arrays columns — the native
 * twin of ops/prep.prepare() + the per-call encode loop, so the
 * native oracle is end-to-end native exactly like the device path.
 *
 * prep_cols(proc i32[n], typ u8[n], fmap i32[n], va i32[n],
 *           vb i32[n], vkind u8[n], seen dict, rows list)
 * -> None (out of scope: double invoke / missing f / vkind 4) or
 *    (n_calls, ev_kind bytes u8[nev], ev_cid bytes i32[nev],
 *     call_uop bytes i32[n_calls], crashed long)
 * Pairing semantics identical to prepare(): fail pairs dropped,
 * ok pairs invoke+return events, info/unpaired invokes crash (invoke
 * event only).  Invoke value None resolves from the completion. */
static PyObject *prep_cols(PyObject *self, PyObject *args) {
    Py_buffer bproc = {0}, btyp = {0}, bfmap = {0}, bva = {0},
              bvb = {0}, bvk = {0};
    PyObject *seen, *rows;
    if (!PyArg_ParseTuple(args, "y*y*y*y*y*y*O!O!",
                          &bproc, &btyp, &bfmap, &bva, &bvb, &bvk,
                          &PyDict_Type, &seen, &PyList_Type, &rows))
        return NULL;
    Py_ssize_t n = (Py_ssize_t)(bproc.len / 4);
    const int32_t *proc = bproc.buf;
    const uint8_t *typ = btyp.buf;
    const int32_t *fmap = bfmap.buf;
    const int32_t *va = bva.buf;
    const int32_t *vb = bvb.buf;
    const uint8_t *vk = bvk.buf;

    PyObject *result = NULL;
    PyObject *new_rows = NULL;
    Py_ssize_t *fate = PyMem_Malloc((n ? n : 1) * sizeof(Py_ssize_t));
    uint8_t *evk = PyMem_Malloc((n ? n : 1));
    int32_t *evc = PyMem_Malloc((n ? n : 1) * sizeof(int32_t));
    int32_t *cuop = PyMem_Malloc((n ? n : 1) * sizeof(int32_t));
    int32_t *cid_of_pos = PyMem_Malloc((n ? n : 1) * sizeof(int32_t));
    utab ut = {0};
    long nev = 0, ncalls = 0, crashed = 0;
    if (!fate || !evk || !evc || !cuop || !cid_of_pos) {
        PyErr_NoMemory();
        goto done;
    }

    /* pass 1: pairing (open (proc,pos) array) */
    {
        int32_t open_p[MAX_OPEN_HARD];
        Py_ssize_t open_i[MAX_OPEN_HARD];
        long n_open = 0;
        for (Py_ssize_t i = 0; i < n; i++) fate[i] = -1;
        for (Py_ssize_t i = 0; i < n; i++) {
            int32_t p = proc[i];
            if (p == -2) goto fallback;  /* out-of-int32 client id:
                * the object paths see the real id (history.py
                * P_OUT_OF_RANGE) — whole history out of columnar
                * scope so classifications cannot diverge */
            if (p < 0) continue;
            uint8_t t = typ[i];
            long j = -1;
            for (long k = 0; k < n_open; k++)
                if (open_p[k] == p) { j = k; break; }
            if (t == 0) {
                if (j >= 0 || n_open >= MAX_OPEN_HARD) goto fallback;
                open_p[n_open] = p;
                open_i[n_open] = i;
                n_open++;
            } else if (j >= 0) {
                fate[open_i[j]] = i;
                open_p[j] = open_p[n_open - 1];
                open_i[j] = open_i[n_open - 1];
                n_open--;
            }
        }
    }

    /* pass 2: events + call uops (interning shared with the scanners).
     * Invokes precede their completions, so one sweep suffices:
     * at an invoke, assign the call id + uop and tag the paired ok
     * completion's position; at a tagged ok completion, emit the
     * return event. */
    new_rows = PyList_New(0);
    if (!new_rows || utab_init(&ut, 256) < 0) {
        PyErr_NoMemory();
        goto done;
    }
    {
        Py_ssize_t base_rows = PyList_GET_SIZE(rows);
        int seen_nonempty = PyDict_GET_SIZE(seen) > 0;
        for (Py_ssize_t i = 0; i < n; i++) cid_of_pos[i] = -1;
        for (Py_ssize_t i = 0; i < n; i++) {
            int32_t p = proc[i];
            if (p < 0) continue;
            uint8_t t = typ[i];
            if (t == 0) {
                Py_ssize_t ci = fate[i];
                int is_crash = (ci < 0 || typ[ci] == 3);
                if (!is_crash && typ[ci] == 2) continue;  /* fail */
                long a, b, okv;
                uint8_t k = vk[i];
                Py_ssize_t vi = i;
                if (k == 0 && !is_crash) { k = vk[ci]; vi = ci; }
                if (k == 4) goto fallback;
                if (k == 0 || k == 3) { a = 0; b = 0; okv = 0; }
                else {
                    a = va[vi];
                    b = (k == 2) ? vb[vi] : 0;
                    okv = 1;
                }
                long fc = fmap[i];
                if (fc < 0) goto fallback;
                long u = intern_uop(&ut, seen, seen_nonempty, rows,
                                    new_rows, fc, a, b, okv);
                if (u < 0) goto done;
                cuop[ncalls] = (int32_t)u;
                evk[nev] = 0;
                evc[nev] = (int32_t)ncalls;
                nev++;
                if (is_crash) crashed++;
                else cid_of_pos[ci] = (int32_t)ncalls;
                ncalls++;
            } else if (t == 1 && cid_of_pos[i] >= 0) {
                evk[nev] = 1;
                evc[nev] = cid_of_pos[i];
                nev++;
            }
        }
        if (publish_interning(seen, rows, new_rows, base_rows) < 0)
            goto done;
        result = Py_BuildValue(
            "(ly#y#y#l)", ncalls,
            (char *)evk, (Py_ssize_t)nev,
            (char *)evc, nev * (Py_ssize_t)sizeof(int32_t),
            (char *)cuop, ncalls * (Py_ssize_t)sizeof(int32_t),
            crashed);
    }
    goto done;

fallback:
    result = Py_None;
    Py_INCREF(Py_None);
done:
    Py_XDECREF(new_rows);
    PyMem_Free(fate);
    PyMem_Free(evk);
    PyMem_Free(evc);
    PyMem_Free(cuop);
    PyMem_Free(cid_of_pos);
    PyMem_Free(ut.e);
    if (bproc.obj) PyBuffer_Release(&bproc);
    if (btyp.obj) PyBuffer_Release(&btyp);
    if (bfmap.obj) PyBuffer_Release(&bfmap);
    if (bva.obj) PyBuffer_Release(&bva);
    if (bvb.obj) PyBuffer_Release(&bvb);
    if (bvk.obj) PyBuffer_Release(&bvk);
    return result;
}

static PyMethodDef methods[] = {
    {"run", run, METH_VARARGS,
     "JIT-linearization oracle over integer uop tables."},
    {"prep_cols", prep_cols, METH_VARARGS,
     "Columnar event-stream ingest for the native oracle."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_wgloracle", NULL, -1, methods,
};

PyMODINIT_FUNC PyInit__wgloracle(void) {
    return PyModule_Create(&moduledef);
}
