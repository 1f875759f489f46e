/* _wirec: native fast path for the scheduler-extender wire protocol.
 *
 * The per-request hot cost at 10k nodes is NOT the scheduling math (that
 * is precomputed per state version, tas/fastpath.py) but the wire tails:
 * json-decoding an Args body into ~10k Python dicts/objects and re-encoding
 * ~10k HostPriority entries.  This module removes both:
 *
 *   parse_prioritize(body)        -> ParsedArgs (pod meta + node-name
 *                                    slices captured zero-copy; no per-node
 *                                    Python objects)
 *   build_table(node_names)       -> NameTable (FNV-1a open-addressing
 *                                    name->row map + pre-rendered per-row
 *                                    JSON fragments), built once per state
 *                                    version
 *   select_encode(parsed, table, ranked, planned_row)
 *                                 -> response bytes: global rank order
 *                                    restricted to the request's candidate
 *                                    set, ordinal 10-i scores, optional
 *                                    batch-plan promotion to rank 1 (asked
 *                                    with promotion=True, it returns
 *                                    (bytes, what the planned row did):
 *                                    see emit_ranked)
 *
 * The JSON scanner is strict: any structural surprise raises ValueError and
 * the caller falls back to the exact Python path (which reproduces every
 * reference quirk).  Semantics mirror tas/fastpath.py byte-for-byte; the
 * equivalence is pinned by tests/test_wirec.py.
 *
 * Reference for the wire shape: extender/types.go:26-64 (Args,
 * HostPriorityList); scoring semantics telemetryscheduler.go:128-149.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <stdint.h>
#include <string.h>

/* ------------------------------------------------------------------ */
/* growable byte buffer                                                */

typedef struct {
    char *data;
    size_t len;
    size_t cap;
} Buf;

static int buf_init(Buf *b, size_t cap) {
    b->data = malloc(cap ? cap : 64);
    if (!b->data) return -1;
    b->len = 0;
    b->cap = cap ? cap : 64;
    return 0;
}

static void buf_free(Buf *b) {
    free(b->data);
    b->data = NULL;
}

static int buf_reserve(Buf *b, size_t extra) {
    if (b->len + extra <= b->cap) return 0;
    size_t ncap = b->cap * 2;
    while (ncap < b->len + extra) ncap *= 2;
    char *nd = realloc(b->data, ncap);
    if (!nd) return -1;
    b->data = nd;
    b->cap = ncap;
    return 0;
}

static int buf_put(Buf *b, const char *src, size_t n) {
    if (buf_reserve(b, n) < 0) return -1;
    memcpy(b->data + b->len, src, n);
    b->len += n;
    return 0;
}

/* Process-wide pool of reusable scratch buffers for the per-request
 * encode paths.
 *
 * A 10k-node response is ~400 KB; glibc malloc serves that size from
 * mmap, so a fresh allocation per request means fresh pages — the
 * page-fault + munmap churn lands straight in p99 on the cache-miss
 * tier.  The pool keeps a handful of high-water buffers alive across
 * requests AND across connections (the server is thread-per-connection,
 * so thread-local scratch would leak per connection and never stay
 * warm).  pool_get always returns an owned Buf (possibly freshly
 * allocated; data==NULL only on OOM); pool_put returns it to a free
 * slot or frees it when the pool is full — bounded memory, no leak. */
#include <pthread.h>
#define POOL_SLOTS 8
static pthread_mutex_t pool_lock = PTHREAD_MUTEX_INITIALIZER;
static Buf buf_pool[POOL_SLOTS];

static Buf pool_get(size_t want) {
    Buf b = {NULL, 0, 0};
    pthread_mutex_lock(&pool_lock);
    for (int i = 0; i < POOL_SLOTS; i++) {
        if (buf_pool[i].data) {
            b = buf_pool[i];
            buf_pool[i].data = NULL;
            break;
        }
    }
    pthread_mutex_unlock(&pool_lock);
    if (b.data) {
        b.len = 0;
        if (want && buf_reserve(&b, want) < 0) {
            buf_free(&b);
            b.data = NULL;
        }
    } else if (buf_init(&b, want ? want : 4096) < 0) {
        b.data = NULL;
    }
    return b;
}

static void pool_put(Buf *b) {
    if (!b->data) return;
    pthread_mutex_lock(&pool_lock);
    for (int i = 0; i < POOL_SLOTS; i++) {
        if (!buf_pool[i].data) {
            buf_pool[i] = *b;
            b->data = NULL;
            break;
        }
    }
    pthread_mutex_unlock(&pool_lock);
    if (b->data) buf_free(b);
}

/* ------------------------------------------------------------------ */
/* JSON scanner over a byte body                                       */

typedef struct {
    const char *s;
    Py_ssize_t n;
    Py_ssize_t i;
    const char *err;  /* static message; raised as ValueError by the caller
                         (lets the scan run without the GIL) */
} Scan;

typedef struct {
    Py_ssize_t off;   /* offset of first char INSIDE the quotes */
    Py_ssize_t len;   /* raw length inside the quotes */
    int escaped;      /* contains backslash escapes (slow-path materialize) */
    int present;
} StrSlice;

static void skip_ws(Scan *sc) {
    while (sc->i < sc->n) {
        char c = sc->s[sc->i];
        if (c == ' ' || c == '\t' || c == '\n' || c == '\r') sc->i++;
        else break;
    }
}

/* record the first error on the scan state; raised as ValueError by the
 * entry point after the GIL is re-acquired */
static int fail_raw(Scan *sc, const char *msg) {
    if (!sc->err) sc->err = msg;
    return -1;
}

#define fail(msg) fail_raw(sc, msg)

/* any byte outside plain-ASCII string content: < 0x20 (control), '\\'
 * (escape), or >= 0x80 (multibyte UTF-8) — found via an 8-byte SWAR
 * sweep.  '"' cannot appear in the probed span (it is memchr's stop). */
static int span_has_special(const char *s, Py_ssize_t n) {
    const uint64_t ones = 0x0101010101010101ULL;
    const uint64_t highs = 0x8080808080808080ULL;
    Py_ssize_t i = 0;
    for (; i + 8 <= n; i += 8) {
        uint64_t w;
        memcpy(&w, s + i, 8);
        uint64_t lt20 = (w - ones * 0x20) & ~w & highs;
        uint64_t ge80 = w & highs;
        uint64_t xbs = w ^ (ones * (unsigned char)'\\');
        uint64_t isbs = (xbs - ones) & ~xbs & highs;
        if (lt20 | ge80 | isbs) return 1;
    }
    for (; i < n; i++) {
        unsigned char c = (unsigned char)s[i];
        if (c < 0x20 || c >= 0x80 || c == '\\') return 1;
    }
    return 0;
}

/* scan a JSON string starting at the opening quote; record the slice.
 *
 * Escape sequences and UTF-8 well-formedness are validated HERE, exactly
 * as strictly as json.loads over bytes (which UTF-8-decodes first): a body
 * that json.loads would reject must fail the native parse too, so the
 * exact Python path owns the response for it — never a silent divergence
 * or a deferred exception at slice-materialization time.
 *
 * Fast path: memchr to the next '"', one SWAR sweep over the span; when
 * the span is plain ASCII (the overwhelmingly common case for node
 * names/keys) the per-byte validating loop is skipped entirely.  Any
 * special byte — including an escaped quote, whose preceding backslash
 * trips the sweep — falls back to the exact loop from the start. */
static int scan_string(Scan *sc, StrSlice *out) {
    if (sc->i >= sc->n || sc->s[sc->i] != '"') return fail("expected string");
    sc->i++;
    Py_ssize_t start = sc->i;
    {
        const char *base = sc->s + start;
        const char *q = memchr(base, '"', (size_t)(sc->n - start));
        if (q) {
            Py_ssize_t len = (Py_ssize_t)(q - base);
            if (!span_has_special(base, len)) {
                if (out) {
                    out->off = start;
                    out->len = len;
                    out->escaped = 0;
                    out->present = 1;
                }
                sc->i = start + len + 1;
                return 0;
            }
        }
    }
    int escaped = 0;
    while (sc->i < sc->n) {
        unsigned char c = (unsigned char)sc->s[sc->i];
        if (c == '\\') {
            escaped = 1;
            if (sc->i + 1 >= sc->n) return fail("bad escape");
            char e = sc->s[sc->i + 1];
            if (e == 'u') {
                if (sc->i + 5 >= sc->n) return fail("bad \\u escape");
                for (int k = 2; k <= 5; k++) {
                    char h = sc->s[sc->i + k];
                    if (!((h >= '0' && h <= '9') || (h >= 'a' && h <= 'f') ||
                          (h >= 'A' && h <= 'F')))
                        return fail("bad \\u escape");
                }
                sc->i += 6;
            } else if (e == '"' || e == '\\' || e == '/' || e == 'b' ||
                       e == 'f' || e == 'n' || e == 'r' || e == 't') {
                sc->i += 2;
            } else {
                return fail("bad escape");
            }
            continue;
        }
        if (c == '"') {
            if (out) {
                out->off = start;
                out->len = sc->i - start;
                out->escaped = escaped;
                out->present = 1;
            }
            sc->i++;
            return 0;
        }
        if (c < 0x20) return fail("control char in string");
        if (c >= 0x80) {
            /* strict UTF-8: reject bad lead/continuation bytes, overlong
             * forms, surrogates, and code points past U+10FFFF — the same
             * set CPython's strict utf-8 decoder rejects */
            const unsigned char *p = (const unsigned char *)sc->s + sc->i;
            Py_ssize_t left = sc->n - sc->i;
            int len;
            if ((p[0] & 0xE0) == 0xC0) {
                if (p[0] < 0xC2) return fail("invalid UTF-8");
                len = 2;
            } else if ((p[0] & 0xF0) == 0xE0) {
                len = 3;
            } else if ((p[0] & 0xF8) == 0xF0) {
                if (p[0] > 0xF4) return fail("invalid UTF-8");
                len = 4;
            } else {
                return fail("invalid UTF-8");
            }
            if (left < len) return fail("invalid UTF-8");
            for (int k = 1; k < len; k++)
                if ((p[k] & 0xC0) != 0x80) return fail("invalid UTF-8");
            if (len == 3) {
                if (p[0] == 0xE0 && p[1] < 0xA0) return fail("invalid UTF-8");
                if (p[0] == 0xED && p[1] >= 0xA0) return fail("invalid UTF-8");
            } else if (len == 4) {
                if (p[0] == 0xF0 && p[1] < 0x90) return fail("invalid UTF-8");
                if (p[0] == 0xF4 && p[1] >= 0x90) return fail("invalid UTF-8");
            }
            sc->i += len;
            continue;
        }
        sc->i++;
    }
    return fail("unterminated string");
}

static int skip_value(Scan *sc);

/* ASCII-case-insensitive key match against a lowercase literal.  The
 * real kube-scheduler marshals the upstream extender types (lowercase
 * tags: "pod"/"nodes"/"nodenames"); the reference's untagged Go structs
 * accept them through encoding/json's case-insensitive field matching,
 * so the Args TOP-LEVEL keys must match case-insensitively here too
 * (inner object keys are Go-marshaled v1 structs — always canonical
 * lowercase on the wire — and stay exact, like the Python path). */
static int key_is_ci(const char *s, Py_ssize_t len, const char *lower_lit,
                     Py_ssize_t lit_len) {
    if (len != lit_len) return 0;
    for (Py_ssize_t i = 0; i < len; i++) {
        char a = s[i];
        if (a >= 'A' && a <= 'Z') a += 32;
        if (a != lower_lit[i]) return 0;
    }
    return 1;
}

static int skip_object(Scan *sc) {
    sc->i++; /* '{' */
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; return 0; }
    for (;;) {
        skip_ws(sc);
        if (scan_string(sc, NULL) < 0) return -1;
        skip_ws(sc);
        if (sc->i >= sc->n || sc->s[sc->i] != ':') return fail("expected ':'");
        sc->i++;
        if (skip_value(sc) < 0) return -1;
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated object");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == '}') { sc->i++; return 0; }
        return fail("bad object");
    }
}

static int skip_array(Scan *sc) {
    sc->i++; /* '[' */
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == ']') { sc->i++; return 0; }
    for (;;) {
        if (skip_value(sc) < 0) return -1;
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated array");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == ']') { sc->i++; return 0; }
        return fail("bad array");
    }
}

static int skip_number(Scan *sc) {
    if (sc->i < sc->n && sc->s[sc->i] == '-') sc->i++;
    /* strict like json.loads: no leading zeros */
    if (sc->i >= sc->n) return fail("bad number");
    if (sc->s[sc->i] == '0') {
        sc->i++;
        if (sc->i < sc->n && sc->s[sc->i] >= '0' && sc->s[sc->i] <= '9')
            return fail("leading zero");
    } else if (sc->s[sc->i] >= '1' && sc->s[sc->i] <= '9') {
        while (sc->i < sc->n && sc->s[sc->i] >= '0' && sc->s[sc->i] <= '9')
            sc->i++;
    } else {
        return fail("bad number");
    }
    int digits;
    if (sc->i < sc->n && sc->s[sc->i] == '.') {
        sc->i++;
        digits = 0;
        while (sc->i < sc->n && sc->s[sc->i] >= '0' && sc->s[sc->i] <= '9') {
            digits = 1; sc->i++;
        }
        if (!digits) return fail("bad number");
    }
    if (sc->i < sc->n && (sc->s[sc->i] == 'e' || sc->s[sc->i] == 'E')) {
        sc->i++;
        if (sc->i < sc->n && (sc->s[sc->i] == '+' || sc->s[sc->i] == '-')) sc->i++;
        digits = 0;
        while (sc->i < sc->n && sc->s[sc->i] >= '0' && sc->s[sc->i] <= '9') {
            digits = 1; sc->i++;
        }
        if (!digits) return fail("bad number");
    }
    return 0;
}

static int skip_literal(Scan *sc, const char *lit, Py_ssize_t len) {
    if (sc->i + len > sc->n || memcmp(sc->s + sc->i, lit, len) != 0)
        return fail("bad literal");
    sc->i += len;
    return 0;
}

static int skip_value(Scan *sc) {
    skip_ws(sc);
    if (sc->i >= sc->n) return fail("unexpected end");
    switch (sc->s[sc->i]) {
    case '{': return skip_object(sc);
    case '[': return skip_array(sc);
    case '"': return scan_string(sc, NULL);
    case 't': return skip_literal(sc, "true", 4);
    case 'f': return skip_literal(sc, "false", 5);
    case 'n': return skip_literal(sc, "null", 4);
    default:  return skip_number(sc);
    }
}

/* ------------------------------------------------------------------ */
/* ParsedArgs object                                                   */

typedef struct {
    PyObject_HEAD
    PyObject *body;        /* the bytes object; slices point into it */
    StrSlice pod_name;
    StrSlice pod_namespace;
    StrSlice policy_label; /* labels["telemetry-policy"] */
    int has_label;
    /* raw byte span [start, end) of the pod's labels object, the last
     * "labels" key of the last metadata (as the exact decode keeps it);
     * -1 when absent or null — what a gang member's Filter reads its
     * gang from (tas/telemetryscheduler.py) */
    Py_ssize_t labels_start, labels_end;
    int nodes_present;     /* "Nodes" was a non-null object with items */
    StrSlice *names;       /* node name slices (Nodes.items[].metadata.name) */
    Py_ssize_t num_names;
    /* two offsets per Nodes.items entry, grown with ``names``: the item's
     * opening '{' and one past its closing '}' in the body — what the
     * Nodes-wire Filter echoes (filter_encode_nodes) */
    Py_ssize_t *item_spans;
    int node_names_present; /* "NodeNames" was a non-null array */
    StrSlice *nn_names;     /* NodeNames[] string slices */
    Py_ssize_t num_nn_names;
    /* raw byte span [start, end) of the candidate-list JSON values —
     * identical spans mean identical candidate sets, the key of the
     * response-reuse cache (tas/fastpath.py); -1 when absent */
    Py_ssize_t nodes_span_start, nodes_span_end;
    Py_ssize_t nn_span_start, nn_span_end;
} ParsedArgs;

static void ParsedArgs_dealloc(ParsedArgs *self) {
    Py_XDECREF(self->body);
    free(self->names);  /* raw-allocated: grown while the GIL is released */
    free(self->item_spans);
    free(self->nn_names);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static PyObject *slice_to_unicode(PyObject *body, const StrSlice *sl) {
    if (!sl->present) Py_RETURN_NONE;
    const char *base = PyBytes_AS_STRING(body);
    if (!sl->escaped)
        return PyUnicode_DecodeUTF8(base + sl->off, sl->len, "strict");
    /* rare: route through the json module for exact escape handling */
    PyObject *json_mod = PyImport_ImportModule("json");
    if (!json_mod) return NULL;
    PyObject *raw = PyUnicode_DecodeUTF8(base + sl->off - 1, sl->len + 2, "strict");
    if (!raw) { Py_DECREF(json_mod); return NULL; }
    PyObject *res = PyObject_CallMethod(json_mod, "loads", "O", raw);
    Py_DECREF(raw);
    Py_DECREF(json_mod);
    return res;
}

static PyObject *ParsedArgs_get(ParsedArgs *self, void *closure) {
    const char *which = (const char *)closure;
    if (strcmp(which, "pod_name") == 0)
        return slice_to_unicode(self->body, &self->pod_name);
    if (strcmp(which, "pod_namespace") == 0)
        return slice_to_unicode(self->body, &self->pod_namespace);
    if (strcmp(which, "policy_label") == 0) {
        if (!self->has_label) Py_RETURN_NONE;
        return slice_to_unicode(self->body, &self->policy_label);
    }
    if (strcmp(which, "pod_labels_span") == 0) {
        if (self->labels_start < 0) Py_RETURN_NONE;
        return PyBytes_FromStringAndSize(
            PyBytes_AS_STRING(self->body) + self->labels_start,
            self->labels_end - self->labels_start);
    }
    if (strcmp(which, "nodes_present") == 0)
        return PyBool_FromLong(self->nodes_present);
    if (strcmp(which, "num_nodes") == 0)
        return PyLong_FromSsize_t(self->num_names);
    if (strcmp(which, "node_names_present") == 0)
        return PyBool_FromLong(self->node_names_present);
    if (strcmp(which, "num_node_names") == 0)
        return PyLong_FromSsize_t(self->num_nn_names);
    Py_RETURN_NONE;
}

static PyObject *materialize_names(PyObject *body, const StrSlice *slices,
                                   Py_ssize_t count) {
    PyObject *list = PyList_New(count);
    if (!list) return NULL;
    for (Py_ssize_t k = 0; k < count; k++) {
        PyObject *u = slice_to_unicode(body, &slices[k]);
        if (!u) { Py_DECREF(list); return NULL; }
        PyList_SET_ITEM(list, k, u);
    }
    return list;
}

static PyObject *ParsedArgs_node_names(ParsedArgs *self, PyObject *noargs) {
    (void)noargs;
    return materialize_names(self->body, self->names, self->num_names);
}

static PyObject *ParsedArgs_node_names_list(ParsedArgs *self, PyObject *noargs) {
    (void)noargs;
    return materialize_names(self->body, self->nn_names, self->num_nn_names);
}

static PyObject *span_copy(ParsedArgs *self, Py_ssize_t start, Py_ssize_t end) {
    if (start < 0) Py_RETURN_NONE;
    return PyBytes_FromStringAndSize(
        PyBytes_AS_STRING(self->body) + start, end - start);
}

static PyObject *ParsedArgs_nodes_span(ParsedArgs *self, PyObject *noargs) {
    (void)noargs;
    return span_copy(self, self->nodes_span_start, self->nodes_span_end);
}

static PyObject *ParsedArgs_nn_span(ParsedArgs *self, PyObject *noargs) {
    (void)noargs;
    return span_copy(self, self->nn_span_start, self->nn_span_end);
}

static PyObject *ParsedArgs_span_matches(ParsedArgs *self, PyObject *args) {
    /* span_matches(use_node_names, candidate: bytes) -> bool
     * memcmp of the raw candidate-list span against a cached span — the
     * zero-false-positive verify of the response-reuse cache, without
     * materializing the span (memoryview __eq__ is per-byte-slow and
     * bytes() would copy ~hundreds of KB per probe). */
    int use_node_names;
    PyObject *cand;
    if (!PyArg_ParseTuple(args, "pO", &use_node_names, &cand)) return NULL;
    if (!PyBytes_Check(cand)) {
        PyErr_SetString(PyExc_TypeError, "candidate span must be bytes");
        return NULL;
    }
    Py_ssize_t start = use_node_names ? self->nn_span_start
                                      : self->nodes_span_start;
    Py_ssize_t end = use_node_names ? self->nn_span_end : self->nodes_span_end;
    if (start < 0) Py_RETURN_FALSE;
    Py_ssize_t len = end - start;
    if (len != PyBytes_GET_SIZE(cand)) Py_RETURN_FALSE;
    int equal;
    const char *a = PyBytes_AS_STRING(self->body) + start;
    const char *b = PyBytes_AS_STRING(cand);
    Py_BEGIN_ALLOW_THREADS
    equal = memcmp(a, b, (size_t)len) == 0;
    Py_END_ALLOW_THREADS
    return PyBool_FromLong(equal);
}

static PyGetSetDef ParsedArgs_getset[] = {
    {"pod_name", (getter)ParsedArgs_get, NULL, NULL, "pod_name"},
    {"pod_namespace", (getter)ParsedArgs_get, NULL, NULL, "pod_namespace"},
    {"policy_label", (getter)ParsedArgs_get, NULL, NULL, "policy_label"},
    {"pod_labels_span", (getter)ParsedArgs_get, NULL, NULL,
     "pod_labels_span"},
    {"nodes_present", (getter)ParsedArgs_get, NULL, NULL, "nodes_present"},
    {"num_nodes", (getter)ParsedArgs_get, NULL, NULL, "num_nodes"},
    {"node_names_present", (getter)ParsedArgs_get, NULL, NULL,
     "node_names_present"},
    {"num_node_names", (getter)ParsedArgs_get, NULL, NULL, "num_node_names"},
    {NULL},
};

static PyMethodDef ParsedArgs_methods[] = {
    {"node_names", (PyCFunction)ParsedArgs_node_names, METH_NOARGS,
     "Materialize the Nodes.items name list (slow path / debugging)."},
    {"node_names_list", (PyCFunction)ParsedArgs_node_names_list, METH_NOARGS,
     "Materialize the NodeNames list (nodeCacheCapable mode)."},
    {"nodes_span", (PyCFunction)ParsedArgs_nodes_span, METH_NOARGS,
     "Copy of the raw Nodes JSON value bytes, or None."},
    {"node_names_span", (PyCFunction)ParsedArgs_nn_span, METH_NOARGS,
     "Copy of the raw NodeNames JSON value bytes, or None."},
    {"span_matches", (PyCFunction)ParsedArgs_span_matches, METH_VARARGS,
     "memcmp the request's candidate span against cached span bytes."},
    {NULL},
};

static PyTypeObject ParsedArgs_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_wirec.ParsedArgs",
    .tp_basicsize = sizeof(ParsedArgs),
    .tp_dealloc = (destructor)ParsedArgs_dealloc,
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_getset = ParsedArgs_getset,
    .tp_methods = ParsedArgs_methods,
};

/* -- Args-shaped scanning ------------------------------------------- */

#define NAME_CHUNK 1024

static int scan_pod_metadata(Scan *sc, ParsedArgs *pa) {
    skip_ws(sc);
    if (sc->i >= sc->n) return fail("eof in metadata");
    /* duplicate "metadata" keys: last wins like json.loads — the new value
     * (object or null) fully replaces fields from an earlier occurrence */
    memset(&pa->pod_name, 0, sizeof(StrSlice));
    memset(&pa->pod_namespace, 0, sizeof(StrSlice));
    memset(&pa->policy_label, 0, sizeof(StrSlice));
    pa->has_label = 0;
    pa->labels_start = pa->labels_end = -1;
    if (sc->s[sc->i] == 'n') return skip_literal(sc, "null", 4);
    if (sc->s[sc->i] != '{') return fail("metadata not object");
    sc->i++;
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; return 0; }
    for (;;) {
        skip_ws(sc);
        StrSlice key;
        if (scan_string(sc, &key) < 0) return -1;
        if (key.escaped) return fail("escaped key");
        skip_ws(sc);
        if (sc->i >= sc->n || sc->s[sc->i] != ':') return fail("expected ':'");
        sc->i++;
        skip_ws(sc);
        const char *kp = sc->s + key.off;
        if (key.len == 4 && memcmp(kp, "name", 4) == 0) {
            if (sc->i < sc->n && sc->s[sc->i] == '"') {
                if (scan_string(sc, &pa->pod_name) < 0) return -1;
            } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                /* null into a string is Go's zero value "": a repeated
                 * key's null clears an earlier captured string */
                memset(&pa->pod_name, 0, sizeof(StrSlice));
                if (skip_literal(sc, "null", 4) < 0) return -1;
            } else {
                return fail("pod name not string");  /* Go decode error */
            }
        } else if (key.len == 9 && memcmp(kp, "namespace", 9) == 0) {
            if (sc->i < sc->n && sc->s[sc->i] == '"') {
                if (scan_string(sc, &pa->pod_namespace) < 0) return -1;
            } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                memset(&pa->pod_namespace, 0, sizeof(StrSlice));
                if (skip_literal(sc, "null", 4) < 0) return -1;
            } else {
                return fail("pod namespace not string");
            }
        } else if (key.len == 6 && memcmp(kp, "labels", 6) == 0) {
            /* scan the labels object for "telemetry-policy", keeping its
             * span; a repeated "labels" key replaces any label and span
             * from an earlier occurrence */
            memset(&pa->policy_label, 0, sizeof(StrSlice));
            pa->has_label = 0;
            pa->labels_start = pa->labels_end = -1;
            skip_ws(sc);
            if (sc->i < sc->n && sc->s[sc->i] == '{') {
                Py_ssize_t labels_start = sc->i;
                sc->i++;
                skip_ws(sc);
                if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; }
                else for (;;) {
                    skip_ws(sc);
                    StrSlice lkey;
                    if (scan_string(sc, &lkey) < 0) return -1;
                    if (lkey.escaped) return fail("escaped key");
                    skip_ws(sc);
                    if (sc->i >= sc->n || sc->s[sc->i] != ':')
                        return fail("expected ':'");
                    sc->i++;
                    skip_ws(sc);
                    if (lkey.len == 16 &&
                        memcmp(sc->s + lkey.off, "telemetry-policy", 16) == 0) {
                        if (sc->i < sc->n && sc->s[sc->i] == '"') {
                            if (scan_string(sc, &pa->policy_label) < 0)
                                return -1;
                            pa->has_label = 1;
                        } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                            /* null label value: Go zero value "" (the
                             * exact path normalizes identically) */
                            if (skip_literal(sc, "null", 4) < 0) return -1;
                            memset(&pa->policy_label, 0, sizeof(StrSlice));
                            pa->policy_label.present = 1;  /* "" */
                            pa->has_label = 1;
                        } else {
                            return fail("label not string");
                        }
                    } else {
                        /* map[string]string: label values must be strings
                         * (or null -> zero value ""); anything else fails
                         * the Go decode — matched by from_json */
                        if (sc->i < sc->n && sc->s[sc->i] == '"') {
                            if (skip_value(sc) < 0) return -1;
                        } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                            if (skip_literal(sc, "null", 4) < 0) return -1;
                        } else {
                            return fail("label not string");
                        }
                    }
                    skip_ws(sc);
                    if (sc->i >= sc->n) return fail("unterminated labels");
                    if (sc->s[sc->i] == ',') { sc->i++; continue; }
                    if (sc->s[sc->i] == '}') { sc->i++; break; }
                    return fail("bad labels");
                }
                pa->labels_start = labels_start;
                pa->labels_end = sc->i;
            } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                /* null labels: Go zero-value map (clears, no error) */
                if (skip_literal(sc, "null", 4) < 0) return -1;
            } else {
                return fail("labels not object");
            }
        } else {
            if (skip_value(sc) < 0) return -1;
        }
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated metadata");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == '}') { sc->i++; return 0; }
        return fail("bad metadata");
    }
}

static int scan_pod(Scan *sc, ParsedArgs *pa) {
    skip_ws(sc);
    if (sc->i >= sc->n) return fail("eof in Pod");
    /* "Pod": null — Go decodes null into a VALUE struct as "no effect"
     * (the reference's Args.Pod is v1.Pod by value), so fields captured
     * from an earlier duplicate occurrence must survive; contrast the
     * pointer-typed Nodes/NodeNames where null assigns nil */
    if (sc->s[sc->i] == 'n') return skip_literal(sc, "null", 4);
    /* duplicate top-level "Pod" keys carrying objects: last wins */
    memset(&pa->pod_name, 0, sizeof(StrSlice));
    memset(&pa->pod_namespace, 0, sizeof(StrSlice));
    memset(&pa->policy_label, 0, sizeof(StrSlice));
    pa->has_label = 0;
    pa->labels_start = pa->labels_end = -1;
    if (sc->s[sc->i] != '{') return fail("Pod not object");
    sc->i++;
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; return 0; }
    for (;;) {
        skip_ws(sc);
        StrSlice key;
        if (scan_string(sc, &key) < 0) return -1;
        if (key.escaped) return fail("escaped key");
        skip_ws(sc);
        if (sc->i >= sc->n || sc->s[sc->i] != ':') return fail("expected ':'");
        sc->i++;
        if (key.len == 8 &&
            memcmp(sc->s + key.off, "metadata", 8) == 0) {
            if (scan_pod_metadata(sc, pa) < 0) return -1;
        } else {
            if (skip_value(sc) < 0) return -1;
        }
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated Pod");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == '}') { sc->i++; return 0; }
        return fail("bad Pod");
    }
}

/* process-wide high-water candidate count: the first growth of a name
 * array jumps straight to the size recent requests needed, collapsing
 * the realloc chain (each step past the mmap threshold is a fresh
 * mapping + copy — p99 churn at 10k nodes).  Atomic because the server
 * is thread-per-connection (a per-thread hint would reset every
 * connection); relaxed ordering — the hint is only an optimization.
 * Capped at NAME_HINT_MAX slots: the hint is driven by untrusted
 * request content, and without a ceiling one huge NodeNames body would
 * permanently raise the initial allocation for every later request
 * (64k slots = 2 MB of StrSlice, comfortably above any real cluster;
 * larger requests still parse — they just grow from the cap). */
#include <stdatomic.h>
#define NAME_HINT_MAX 65536
static _Atomic Py_ssize_t names_hint = NAME_CHUNK;

static Py_ssize_t grow_cap(Py_ssize_t cap) {
    return cap ? cap * 2
               : atomic_load_explicit(&names_hint, memory_order_relaxed);
}

static int push_name(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap,
                     const StrSlice *sl, Py_ssize_t begin, Py_ssize_t end) {
    if (pa->num_names == *cap) {
        Py_ssize_t ncap = grow_cap(*cap);
        StrSlice *nn = realloc(pa->names, ncap * sizeof(StrSlice));
        if (!nn) return fail("out of memory");
        pa->names = nn;
        Py_ssize_t *ns =
            realloc(pa->item_spans, ncap * 2 * sizeof(Py_ssize_t));
        if (!ns) return fail("out of memory");
        pa->item_spans = ns;
        *cap = ncap;
    }
    pa->item_spans[2 * pa->num_names] = begin;
    pa->item_spans[2 * pa->num_names + 1] = end;
    pa->names[pa->num_names++] = *sl;
    return 0;
}

static int scan_node_item(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap) {
    /* one Nodes.items entry: capture metadata.name and the item's own
     * byte span, skip the rest */
    skip_ws(sc);
    if (sc->i >= sc->n || sc->s[sc->i] != '{') return fail("node not object");
    Py_ssize_t begin = sc->i;
    sc->i++;
    skip_ws(sc);
    StrSlice name = {0, 0, 0, 0};
    if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; goto done; }
    for (;;) {
        skip_ws(sc);
        StrSlice key;
        if (scan_string(sc, &key) < 0) return -1;
        if (key.escaped) return fail("escaped key");
        skip_ws(sc);
        if (sc->i >= sc->n || sc->s[sc->i] != ':') return fail("expected ':'");
        sc->i++;
        if (key.len == 8 &&
            memcmp(sc->s + key.off, "metadata", 8) == 0) {
            skip_ws(sc);
            if (sc->i >= sc->n) return fail("eof in node metadata");
            /* repeated "metadata" key: last wins — the new value replaces
             * any name captured from an earlier occurrence.  null clears
             * to the zero value; any other non-object is a decode error
             * (as in Go), so the exact path owns the response */
            memset(&name, 0, sizeof(StrSlice));
            if (sc->s[sc->i] == 'n') {
                if (skip_literal(sc, "null", 4) < 0) return -1;
            } else if (sc->s[sc->i] != '{') {
                return fail("node metadata not object");
            } else {
                sc->i++;
                skip_ws(sc);
                if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; }
                else for (;;) {
                    skip_ws(sc);
                    StrSlice mkey;
                    if (scan_string(sc, &mkey) < 0) return -1;
                    if (mkey.escaped) return fail("escaped key");
                    skip_ws(sc);
                    if (sc->i >= sc->n || sc->s[sc->i] != ':')
                        return fail("expected ':'");
                    sc->i++;
                    skip_ws(sc);
                    if (mkey.len == 4 &&
                        memcmp(sc->s + mkey.off, "name", 4) == 0) {
                        if (sc->i < sc->n && sc->s[sc->i] == '"') {
                            if (scan_string(sc, &name) < 0) return -1;
                        } else if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                            /* null into a string: Go zero value "" */
                            memset(&name, 0, sizeof(StrSlice));
                            if (skip_literal(sc, "null", 4) < 0) return -1;
                        } else {
                            /* Go: UnmarshalTypeError — decode fails */
                            return fail("node name not string");
                        }
                    } else if (skip_value(sc) < 0) return -1;
                    skip_ws(sc);
                    if (sc->i >= sc->n) return fail("unterminated node metadata");
                    if (sc->s[sc->i] == ',') { sc->i++; continue; }
                    if (sc->s[sc->i] == '}') { sc->i++; break; }
                    return fail("bad node metadata");
                }
            }
        } else {
            if (skip_value(sc) < 0) return -1;
        }
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated node");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == '}') { sc->i++; break; }
        return fail("bad node");
    }
done:
    /* a node item whose metadata carries no name (absent key, null name,
     * or null metadata) is the Go zero value "" — a PRESENT empty name
     * that participates in candidate matching exactly as the Python
     * decode's Node({}).name == "" does (the round-5 differential fuzzer
     * caught the old drop-it behavior diverging when "" is an interned
     * node).  Non-string names fail the parse above, as in Go. */
    if (!name.present) {
        name.off = 0; name.len = 0; name.escaped = 0; name.present = 1;
    }
    return push_name(sc, pa, cap, &name, begin, sc->i);
}

static int push_nn_name(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap,
                        const StrSlice *sl) {
    if (pa->num_nn_names == *cap) {
        Py_ssize_t ncap = grow_cap(*cap);
        StrSlice *nn = realloc(pa->nn_names, ncap * sizeof(StrSlice));
        if (!nn) return fail("out of memory");
        pa->nn_names = nn;
        *cap = ncap;
    }
    pa->nn_names[pa->num_nn_names++] = *sl;
    return 0;
}

/* Batch-validated scan of a NodeNames array positioned at '['.
 *
 * Per-name scan_string pays ~2x the structural cost in validation
 * bookkeeping; at 10k names that is most of the request's parse floor
 * (BENCH_r05 filter_floor_breakdown: parse 173 us).  Here names are
 * recorded by bare memchr quote pairs and validated by ONE SWAR sweep
 * over the whole array span at the end: a clean sweep (no control
 * bytes, no backslash, no >= 0x80 — exactly scan_string's special set)
 * proves every recorded slice is an unescaped plain-ASCII string, i.e.
 * precisely what the strict loop would have produced.  Any special
 * byte anywhere (escapes, UTF-8, \t/\n between elements, an escaped
 * quote that desynced a memchr pair) returns 0 and the caller rescans
 * the same region with the strict loop from scratch — so acceptance
 * and slices can never diverge from the strict scanner's.
 * Returns 1 on success, 0 on fall-back (state rewound), -1 on error. */
static int scan_node_names_fast(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap) {
    Py_ssize_t arr_start = sc->i;  /* at '[' */
    Py_ssize_t i = arr_start + 1;
    const char *s = sc->s;
    Py_ssize_t n = sc->n;
    while (i < n && s[i] == ' ') i++;
    if (i < n && s[i] == ']') {
        sc->i = i + 1;
        return 1;
    }
    for (;;) {
        while (i < n && s[i] == ' ') i++;
        if (i >= n || s[i] != '"') goto fallback;
        const char *q = memchr(s + i + 1, '"', (size_t)(n - i - 1));
        if (!q) goto fallback;
        StrSlice name;
        name.off = i + 1;
        name.len = (Py_ssize_t)(q - (s + i + 1));
        name.escaped = 0;
        name.present = 1;
        if (push_nn_name(sc, pa, cap, &name) < 0) return -1;
        i = (Py_ssize_t)(q - s) + 1;
        while (i < n && s[i] == ' ') i++;
        if (i >= n) goto fallback;
        if (s[i] == ',') { i++; continue; }
        if (s[i] == ']') break;
        goto fallback;
    }
    /* the one validation sweep: [just past '[', the closing ']') */
    if (span_has_special(s + arr_start + 1, i - arr_start - 1)) goto fallback;
    sc->i = i + 1;
    return 1;

fallback:
    sc->i = arr_start;
    pa->num_nn_names = 0;
    return 0;
}

/* "NodeNames": null | array of strings (nodeCacheCapable mode,
 * extender/types.go:44-49); strict: non-string elements fail the parse */
static int scan_node_names(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap) {
    skip_ws(sc);
    if (sc->i >= sc->n) return fail("eof in NodeNames");
    /* duplicate "NodeNames" keys: last wins */
    pa->node_names_present = 0;
    pa->num_nn_names = 0;
    pa->nn_span_start = sc->i;
    if (sc->s[sc->i] == 'n') {
        if (skip_literal(sc, "null", 4) < 0) return -1;
        pa->nn_span_end = sc->i;
        return 0;
    }
    if (sc->s[sc->i] != '[') return fail("NodeNames not array");
    pa->node_names_present = 1;
    {
        int fast = scan_node_names_fast(sc, pa, cap);
        if (fast < 0) return -1;
        if (fast) {
            pa->nn_span_end = sc->i;
            return 0;
        }
    }
    sc->i++;
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == ']') {
        sc->i++;
        pa->nn_span_end = sc->i;
        return 0;
    }
    for (;;) {
        skip_ws(sc);
        StrSlice name;
        if (scan_string(sc, &name) < 0) return -1;
        if (push_nn_name(sc, pa, cap, &name) < 0) return -1;
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated NodeNames");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == ']') {
            sc->i++;
            pa->nn_span_end = sc->i;
            return 0;
        }
        return fail("bad NodeNames");
    }
}

static int scan_nodes(Scan *sc, ParsedArgs *pa, Py_ssize_t *cap) {
    skip_ws(sc);
    if (sc->i >= sc->n) return fail("eof in Nodes");
    pa->nodes_span_start = sc->i;
    if (sc->s[sc->i] == 'n') {
        int rc = skip_literal(sc, "null", 4);
        pa->nodes_span_end = sc->i;
        return rc;
    }
    if (sc->s[sc->i] != '{') return fail("Nodes not object");
    sc->i++;
    skip_ws(sc);
    if (sc->i < sc->n && sc->s[sc->i] == '}') {
        sc->i++;
        pa->nodes_span_end = sc->i;
        return 0;
    }
    for (;;) {
        skip_ws(sc);
        StrSlice key;
        if (scan_string(sc, &key) < 0) return -1;
        if (key.escaped) return fail("escaped key");
        skip_ws(sc);
        if (sc->i >= sc->n || sc->s[sc->i] != ':') return fail("expected ':'");
        sc->i++;
        if (key.len == 5 &&
            memcmp(sc->s + key.off, "items", 5) == 0) {
            skip_ws(sc);
            if (sc->i < sc->n && sc->s[sc->i] == 'n') {
                if (skip_literal(sc, "null", 4) < 0) return -1;
                pa->nodes_present = 1;  /* Nodes object exists, items null */
                pa->num_names = 0;      /* last-wins: null replaces any array */
            } else if (sc->i < sc->n && sc->s[sc->i] == '[') {
                pa->nodes_present = 1;
                /* duplicate "items" keys: last wins like json.loads */
                pa->num_names = 0;
                sc->i++;
                skip_ws(sc);
                if (sc->i < sc->n && sc->s[sc->i] == ']') { sc->i++; }
                else for (;;) {
                    if (scan_node_item(sc, pa, cap) < 0) return -1;
                    skip_ws(sc);
                    if (sc->i >= sc->n) return fail("unterminated items");
                    if (sc->s[sc->i] == ',') { sc->i++; continue; }
                    if (sc->s[sc->i] == ']') { sc->i++; break; }
                    return fail("bad items");
                }
            } else {
                return fail("items not array");
            }
        } else {
            if (skip_value(sc) < 0) return -1;
        }
        skip_ws(sc);
        if (sc->i >= sc->n) return fail("unterminated Nodes");
        if (sc->s[sc->i] == ',') { sc->i++; continue; }
        if (sc->s[sc->i] == '}') {
            sc->i++;
            pa->nodes_span_end = sc->i;
            return 0;
        }
        return fail("bad Nodes");
    }
}

static PyObject *wirec_parse_prioritize(PyObject *mod, PyObject *arg) {
    (void)mod;
    if (!PyBytes_Check(arg)) {
        PyErr_SetString(PyExc_TypeError, "body must be bytes");
        return NULL;
    }
    ParsedArgs *pa = PyObject_New(ParsedArgs, &ParsedArgs_Type);
    if (!pa) return NULL;
    Py_INCREF(arg);
    pa->body = arg;
    memset(&pa->pod_name, 0, sizeof(StrSlice));
    memset(&pa->pod_namespace, 0, sizeof(StrSlice));
    memset(&pa->policy_label, 0, sizeof(StrSlice));
    pa->has_label = 0;
    pa->labels_start = pa->labels_end = -1;
    pa->nodes_present = 0;
    pa->names = NULL;
    pa->num_names = 0;
    pa->item_spans = NULL;
    pa->node_names_present = 0;
    pa->nn_names = NULL;
    pa->num_nn_names = 0;
    pa->nodes_span_start = pa->nodes_span_end = -1;
    pa->nn_span_start = pa->nn_span_end = -1;
    Py_ssize_t cap = 0;
    Py_ssize_t nn_cap = 0;

    Scan scan_state = {PyBytes_AS_STRING(arg), PyBytes_GET_SIZE(arg), 0, NULL};
    Scan *sc = &scan_state;
    int ok = 1;
    /* the scan touches only raw body bytes + raw-allocated name slices, so
     * it runs without the GIL: concurrent requests parse in parallel */
    Py_BEGIN_ALLOW_THREADS
    skip_ws(sc);
    if (sc->i >= sc->n || sc->s[sc->i] != '{') {
        fail("body not a JSON object");
        ok = 0;
    } else {
        sc->i++;
        skip_ws(sc);
        if (sc->i < sc->n && sc->s[sc->i] == '}') { sc->i++; }
        else for (;;) {
            skip_ws(sc);
            StrSlice key;
            if (scan_string(sc, &key) < 0) { ok = 0; break; }
            if (key.escaped) { fail("escaped key"); ok = 0; break; }
            skip_ws(sc);
            if (sc->i >= sc->n || sc->s[sc->i] != ':') {
                fail("expected ':'");
                ok = 0;
                break;
            }
            sc->i++;
            const char *kp = sc->s + key.off;
            int handled = 0;
            if (key_is_ci(kp, key.len, "pod", 3)) {
                if (scan_pod(sc, pa) < 0) { ok = 0; break; }
                handled = 1;
            } else if (key_is_ci(kp, key.len, "nodes", 5)) {
                pa->nodes_present = 0;
                pa->num_names = 0;
                pa->nodes_span_start = pa->nodes_span_end = -1;
                if (scan_nodes(sc, pa, &cap) < 0) { ok = 0; break; }
                handled = 1;
            } else if (key_is_ci(kp, key.len, "nodenames", 9)) {
                if (scan_node_names(sc, pa, &nn_cap) < 0) { ok = 0; break; }
                handled = 1;
            }
            if (!handled && skip_value(sc) < 0) { ok = 0; break; }
            skip_ws(sc);
            if (sc->i >= sc->n) { fail("unterminated body"); ok = 0; break; }
            if (sc->s[sc->i] == ',') { sc->i++; continue; }
            if (sc->s[sc->i] == '}') { sc->i++; break; }
            fail("bad body");
            ok = 0;
            break;
        }
        if (ok) {
            skip_ws(sc);
            if (sc->i != sc->n) { fail("trailing data"); ok = 0; }
        }
    }
    Py_END_ALLOW_THREADS
    if (!ok) {
        Py_DECREF(pa);
        PyErr_SetString(PyExc_ValueError, sc->err ? sc->err : "parse error");
        return NULL;
    }
    /* remember this request's candidate count so the next request's
     * array starts at the right size (process-wide atomic, relaxed —
     * the hint is only an allocation-size optimization) */
    Py_ssize_t seen = pa->num_names > pa->num_nn_names ? pa->num_names
                                                       : pa->num_nn_names;
    if (seen > NAME_HINT_MAX) seen = NAME_HINT_MAX;
    if (seen > atomic_load_explicit(&names_hint, memory_order_relaxed)) {
        Py_ssize_t h = NAME_CHUNK;
        while (h < seen) h *= 2;
        atomic_store_explicit(&names_hint, h, memory_order_relaxed);
    }
    return (PyObject *)pa;
}

/* ------------------------------------------------------------------ */
/* NameTable: name -> row hash map + response fragments                */

typedef struct {
    PyObject_HEAD
    Py_ssize_t n_rows;
    /* open addressing table of 2^bits slots, each slot = row+1 (0=empty) */
    uint32_t *slots;
    uint32_t mask;
    /* interned copies of names (concatenated) for collision verification */
    char *name_bytes;
    Py_ssize_t *name_off;  /* n_rows + 1 offsets */
    /* pre-rendered fragments: {"Host": "<name>", "Score":  */
    char *frag_bytes;
    Py_ssize_t *frag_off;  /* n_rows + 1 offsets */
} NameTable;

static void NameTable_dealloc(NameTable *self) {
    PyMem_Free(self->slots);
    /* name_bytes/frag_bytes are Buf storage (malloc) — free with free();
     * mixing allocators is undefined behavior under PYTHONMALLOC=debug */
    free(self->name_bytes);
    PyMem_Free(self->name_off);
    free(self->frag_bytes);
    PyMem_Free(self->frag_off);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static uint64_t fnv1a(const char *s, Py_ssize_t n) {
    uint64_t h = 1469598103934665603ULL;
    for (Py_ssize_t i = 0; i < n; i++) {
        h ^= (unsigned char)s[i];
        h *= 1099511628211ULL;
    }
    return h;
}

/* row lookup by raw (unescaped) name bytes; -1 if absent */
static Py_ssize_t table_lookup(NameTable *t, const char *s, Py_ssize_t n) {
    uint64_t h = fnv1a(s, n);
    uint32_t idx = (uint32_t)h & t->mask;
    for (;;) {
        uint32_t slot = t->slots[idx];
        if (slot == 0) return -1;
        Py_ssize_t row = (Py_ssize_t)slot - 1;
        Py_ssize_t off = t->name_off[row];
        Py_ssize_t len = t->name_off[row + 1] - off;
        if (len == n && memcmp(t->name_bytes + off, s, n) == 0) return row;
        idx = (idx + 1) & t->mask;
    }
}

static PyTypeObject NameTable_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_wirec.NameTable",
    .tp_basicsize = sizeof(NameTable),
    .tp_dealloc = (destructor)NameTable_dealloc,
    .tp_flags = Py_TPFLAGS_DEFAULT,
};

static PyObject *wirec_build_table(PyObject *mod, PyObject *arg) {
    (void)mod;
    /* arg: sequence of str node names in row order; fragments use
     * json-exact escaping via json.dumps for non-ASCII-simple names */
    PyObject *seq = PySequence_Fast(arg, "expected a sequence of names");
    if (!seq) return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    NameTable *t = PyObject_New(NameTable, &NameTable_Type);
    if (!t) { Py_DECREF(seq); return NULL; }
    t->n_rows = n;
    t->slots = NULL;
    t->name_bytes = NULL;
    t->name_off = NULL;
    t->frag_bytes = NULL;
    t->frag_off = NULL;

    uint32_t bits = 3;
    while ((1u << bits) < (uint32_t)(n * 2 + 4)) bits++;
    uint32_t size = 1u << bits;
    t->mask = size - 1;
    t->slots = PyMem_Calloc(size, sizeof(uint32_t));
    t->name_off = PyMem_Malloc((n + 1) * sizeof(Py_ssize_t));
    t->frag_off = PyMem_Malloc((n + 1) * sizeof(Py_ssize_t));
    if (!t->slots || !t->name_off || !t->frag_off) {
        PyErr_NoMemory();
        goto error;
    }

    Buf names_buf, frag_buf;
    if (buf_init(&names_buf, 64 * (n + 1)) < 0) { PyErr_NoMemory(); goto error; }
    if (buf_init(&frag_buf, 96 * (n + 1)) < 0) {
        buf_free(&names_buf);
        PyErr_NoMemory();
        goto error;
    }

    PyObject *json_mod = NULL;
    for (Py_ssize_t row = 0; row < n; row++) {
        PyObject *name = PySequence_Fast_GET_ITEM(seq, row);
        Py_ssize_t nlen;
        const char *ns = PyUnicode_AsUTF8AndSize(name, &nlen);
        if (!ns) goto error_bufs;
        t->name_off[row] = (Py_ssize_t)names_buf.len;
        if (buf_put(&names_buf, ns, nlen) < 0) goto error_bufs;

        /* fragment */
        t->frag_off[row] = (Py_ssize_t)frag_buf.len;
        int needs_escape = 0;
        for (Py_ssize_t k = 0; k < nlen; k++) {
            unsigned char c = (unsigned char)ns[k];
            if (c == '"' || c == '\\' || c < 0x20 || c >= 0x7f) {
                needs_escape = 1;
                break;
            }
        }
        if (buf_put(&frag_buf, "{\"Host\": ", 9) < 0) goto error_bufs;
        if (!needs_escape) {
            if (buf_put(&frag_buf, "\"", 1) < 0) goto error_bufs;
            if (buf_put(&frag_buf, ns, nlen) < 0) goto error_bufs;
            if (buf_put(&frag_buf, "\"", 1) < 0) goto error_bufs;
        } else {
            if (!json_mod) {
                json_mod = PyImport_ImportModule("json");
                if (!json_mod) goto error_bufs;
            }
            PyObject *enc = PyObject_CallMethod(json_mod, "dumps", "O", name);
            if (!enc) goto error_bufs;
            Py_ssize_t elen;
            const char *es = PyUnicode_AsUTF8AndSize(enc, &elen);
            if (!es || buf_put(&frag_buf, es, elen) < 0) {
                Py_DECREF(enc);
                goto error_bufs;
            }
            Py_DECREF(enc);
        }
        if (buf_put(&frag_buf, ", \"Score\": ", 11) < 0) goto error_bufs;
    }
    t->name_off[n] = (Py_ssize_t)names_buf.len;
    t->frag_off[n] = (Py_ssize_t)frag_buf.len;
    Py_XDECREF(json_mod);
    json_mod = NULL;

    t->name_bytes = names_buf.data;  /* ownership moves */
    t->frag_bytes = frag_buf.data;

    /* populate hash slots (first writer wins; duplicate names share the
     * earlier row, which matches dict interning order semantics) */
    for (Py_ssize_t row = 0; row < n; row++) {
        Py_ssize_t off = t->name_off[row];
        Py_ssize_t len = t->name_off[row + 1] - off;
        uint64_t h = fnv1a(t->name_bytes + off, len);
        uint32_t idx = (uint32_t)h & t->mask;
        for (;;) {
            if (t->slots[idx] == 0) {
                t->slots[idx] = (uint32_t)(row + 1);
                break;
            }
            Py_ssize_t prow = (Py_ssize_t)t->slots[idx] - 1;
            Py_ssize_t poff = t->name_off[prow];
            Py_ssize_t plen = t->name_off[prow + 1] - poff;
            if (plen == len &&
                memcmp(t->name_bytes + poff, t->name_bytes + off, len) == 0)
                break;  /* duplicate name: keep first row */
            idx = (idx + 1) & t->mask;
        }
    }
    Py_DECREF(seq);
    return (PyObject *)t;

error_bufs:
    Py_XDECREF(json_mod);
    buf_free(&names_buf);
    buf_free(&frag_buf);
error:
    Py_DECREF(seq);
    Py_DECREF(t);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* select_encode                                                       */

/* decimal render of score + '}' — snprintf is ~10x slower and sits on the
 * per-row hot path of a 10k-entry response */
static int put_score(Buf *b, long score) {
    char tmp[24];
    char *end = tmp + sizeof(tmp);
    char *p = end;
    *--p = '}';
    unsigned long v = score < 0 ? (unsigned long)(-score) : (unsigned long)score;
    do {
        *--p = (char)('0' + (v % 10));
        v /= 10;
    } while (v);
    if (score < 0) *--p = '-';
    return buf_put(b, p, (size_t)(end - p));
}

/* THE Prioritize emit loop — the one copy both select_encode and
 * select_encode_universe compile from, so warm-universe bytes can never
 * drift from the cold path's: candidate mask + global rank order ->
 * "[{fragment}<score>, ...]\n" with optional planned-row promotion to
 * rank 1.  *promotion (when asked for) says what the planned row did:
 * 0 it is not among the ranked candidates, 1 it led the ranking already,
 * 2 it was moved to rank 1 past a better-ranked candidate.  0 on success,
 * -1 on OOM. */
static int emit_ranked(Buf *out, NameTable *t, const uint8_t *mask,
                       const int64_t *order, Py_ssize_t n_ranked,
                       Py_ssize_t planned_row, int *promotion) {
    int promote = 0;
    if (planned_row >= 0 && planned_row < t->n_rows && mask[planned_row]) {
        /* planned node goes first iff it appears in the ranked order */
        int ahead = 0;
        for (Py_ssize_t k = 0; k < n_ranked; k++) {
            int64_t row = order[k];
            if (row == planned_row) { promote = 1 + ahead; break; }
            if (row >= 0 && row < t->n_rows && mask[row]) ahead = 1;
        }
    }
    if (promotion) *promotion = promote;
    long rank = 0;
    int first = 1;
    if (buf_put(out, "[", 1) < 0) return -1;
    if (promote) {
        Py_ssize_t off = t->frag_off[planned_row];
        if (buf_put(out, t->frag_bytes + off,
                    (size_t)(t->frag_off[planned_row + 1] - off)) < 0 ||
            put_score(out, 10) < 0)
            return -1;
        rank = 1;
        first = 0;
    }
    for (Py_ssize_t k = 0; k < n_ranked; k++) {
        int64_t row = order[k];
        if (row < 0 || row >= t->n_rows || !mask[row]) continue;
        if (promote && row == planned_row) continue;
        if (!first && buf_put(out, ", ", 2) < 0) return -1;
        first = 0;
        Py_ssize_t off = t->frag_off[row];
        if (buf_put(out, t->frag_bytes + off,
                    (size_t)(t->frag_off[row + 1] - off)) < 0 ||
            put_score(out, 10 - rank) < 0)
            return -1;
        rank++;
    }
    return buf_put(out, "]\n", 2);
}

/* exact output sizing shared by both selects: masked fragments +
 * score/separator slack */
static size_t ranked_estimate(NameTable *t, const uint8_t *mask) {
    size_t est = 8;
    for (Py_ssize_t row = 0; row < t->n_rows; row++)
        if (mask[row])
            est += (size_t)(t->frag_off[row + 1] - t->frag_off[row]) + 16;
    return est;
}

static PyObject *wirec_select_encode(PyObject *mod, PyObject *args) {
    (void)mod;
    PyObject *parsed_obj, *table_obj, *ranked_obj;
    Py_ssize_t planned_row = -1;
    int use_node_names = 0, want_promotion = 0, promotion = 0;
    if (!PyArg_ParseTuple(args, "OOO|npp", &parsed_obj, &table_obj, &ranked_obj,
                          &planned_row, &use_node_names, &want_promotion))
        return NULL;
    if (!PyObject_TypeCheck(parsed_obj, &ParsedArgs_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected ParsedArgs");
        return NULL;
    }
    if (!PyObject_TypeCheck(table_obj, &NameTable_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected NameTable");
        return NULL;
    }
    ParsedArgs *pa = (ParsedArgs *)parsed_obj;
    NameTable *t = (NameTable *)table_obj;

    Py_buffer ranked;
    if (PyObject_GetBuffer(ranked_obj, &ranked, PyBUF_SIMPLE) < 0)
        return NULL;
    if (ranked.len % sizeof(int64_t) != 0) {
        PyBuffer_Release(&ranked);
        PyErr_SetString(PyExc_ValueError, "ranked must be int64 buffer");
        return NULL;
    }
    const int64_t *order = (const int64_t *)ranked.buf;
    Py_ssize_t n_ranked = ranked.len / sizeof(int64_t);

    /* candidate source: Nodes.items names, or the NodeNames array in
     * nodeCacheCapable mode */
    const StrSlice *cand = use_node_names ? pa->nn_names : pa->names;
    Py_ssize_t num_cand = use_node_names ? pa->num_nn_names : pa->num_names;

    /* candidate mask over rows; escaped names (rare) resolve under the
     * GIL first, everything else runs GIL-free below.  The mask comes
     * from the process-wide buffer pool (stale bytes cleared here) — a
     * fresh calloc per request at 10k rows churns pages into p99 */
    Buf mask_buf = pool_get((size_t)t->n_rows + 1);
    if (!mask_buf.data) {
        PyBuffer_Release(&ranked);
        return PyErr_NoMemory();
    }
    uint8_t *mask = (uint8_t *)mask_buf.data;
    memset(mask, 0, (size_t)t->n_rows + 1);
    for (Py_ssize_t k = 0; k < num_cand; k++) {
        const StrSlice *sl = &cand[k];
        if (sl->present && sl->escaped) {
            PyObject *u = slice_to_unicode(pa->body, sl);
            if (!u) goto error;
            Py_ssize_t ulen;
            const char *us = PyUnicode_AsUTF8AndSize(u, &ulen);
            if (!us) { Py_DECREF(u); goto error; }
            Py_ssize_t row = table_lookup(t, us, ulen);
            Py_DECREF(u);
            if (row >= 0) mask[row] = 1;
        }
    }

    const char *body = PyBytes_AS_STRING(pa->body);
    Buf out_buf = {NULL, 0, 0};
    Buf *out = &out_buf;
    int oom = 0;
    Py_BEGIN_ALLOW_THREADS
    for (Py_ssize_t k = 0; k < num_cand; k++) {
        const StrSlice *sl = &cand[k];
        if (!sl->present || sl->escaped) continue;
        Py_ssize_t row = table_lookup(t, body + sl->off, sl->len);
        if (row >= 0) mask[row] = 1;
    }

    out_buf = pool_get(ranked_estimate(t, mask));
    if (!out_buf.data) oom = 1;
    if (!oom && emit_ranked(out, t, mask, order, n_ranked, planned_row,
                            &promotion) < 0)
        oom = 1;
    Py_END_ALLOW_THREADS

    pool_put(&mask_buf);
    PyBuffer_Release(&ranked);
    if (oom) {
        pool_put(&out_buf);
        return PyErr_NoMemory();
    }
    PyObject *res = PyBytes_FromStringAndSize(out->data, (Py_ssize_t)out->len);
    pool_put(&out_buf);
    if (res && want_promotion) return Py_BuildValue("(Ni)", res, promotion);
    return res;

error:
    pool_put(&mask_buf);
    PyBuffer_Release(&ranked);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* filter_encode                                                       */

#define buf_lit(b, lit) buf_put((b), (lit), sizeof(lit) - 1)

/* THE Filter emit loop — the one copy filter_encode, filter_encode_nodes
 * and filter_respond compile from, so warm-universe bytes can never drift
 * from the cold path's:
 *
 *   {"Nodes": null, "NodeNames": [...passing...],
 *    "FailedNodes": {"<name>": "<reason>", ...}, "Error": ""}\n
 *
 * Candidates are described uniformly: slice bytes at ``base``+slices,
 * per-candidate resolved ``rows`` (-1 = absent from the table),
 * ``raw_ok`` (bytes emit verbatim) with ``enc_ptr``/``enc_len`` holding
 * the pre-JSON-encoded form for non-raw names (may be NULL when every
 * candidate is raw).  ``seen`` is a caller-zeroed per-row dedup
 * scratch; 0 on success with *n_failed_out set, -1 on OOM.
 *
 * With ``item_spans`` (two ``base`` offsets a candidate, the Nodes wire)
 * the answer is the exact path's Nodes-mode FilterResult instead:
 *
 *   {"Nodes": {"metadata": {}, "items": [<item>, ...]},
 *    "NodeNames": [...passing..., ""], "FailedNodes": {...}, "Error": ""}\n
 *
 * each <item> the passing candidate's own bytes, ``"items": null`` when
 * none passes, NodeNames closed by the "" the reference's split(" ")
 * leaves.
 *
 * A failed row with no reason of its own takes ``dflt`` (``dflt_len``
 * bytes, the ": " separator included): NODE_VIOLATES, the reference
 * literal, for every caller but a gang member's Filter, whose rows
 * outside its slice carry the member's own reason. */
static const char NODE_VIOLATES[] = ": \"Node violates\"";

static int emit_filter(Buf *out, const char *base, const StrSlice *cand,
                       Py_ssize_t num, const Py_ssize_t *rows,
                       const uint8_t *raw_ok, const char **enc_ptr,
                       const Py_ssize_t *enc_len, const uint8_t *vmask,
                       const char **reason_ptr, const Py_ssize_t *reason_len,
                       const char *dflt, size_t dflt_len,
                       uint8_t *seen, const Py_ssize_t *item_spans,
                       Py_ssize_t *n_failed_out) {
    Py_ssize_t n_failed = 0;
    int first = 1;
    if (item_spans) {
        if (buf_lit(out, "{\"Nodes\": {\"metadata\": {}, \"items\": ") < 0)
            return -1;
        for (Py_ssize_t k = 0; k < num; k++) {
            Py_ssize_t row = rows[k];
            if (row >= 0 && vmask[row]) continue;
            if (buf_put(out, first ? "[" : ", ", first ? 1 : 2) < 0 ||
                buf_put(out, base + item_spans[2 * k],
                        (size_t)(item_spans[2 * k + 1] - item_spans[2 * k])) < 0)
                return -1;
            first = 0;
        }
        if (buf_put(out, first ? "null" : "]", first ? 4 : 1) < 0 ||
            buf_lit(out, "}, \"NodeNames\": [") < 0)
            return -1;
        first = 1;
    } else if (buf_lit(out, "{\"Nodes\": null, \"NodeNames\": [") < 0) {
        return -1;
    }
    for (Py_ssize_t k = 0; k < num; k++) {
        Py_ssize_t row = rows[k];
        if (row >= 0 && vmask[row]) continue;  /* violating -> FailedNodes */
        if (!first && buf_put(out, ", ", 2) < 0) return -1;
        first = 0;
        if (raw_ok[k]) {
            const StrSlice *sl = &cand[k];
            if (buf_put(out, "\"", 1) < 0 ||
                buf_put(out, base + sl->off, (size_t)sl->len) < 0 ||
                buf_put(out, "\"", 1) < 0)
                return -1;
        } else if (buf_put(out, enc_ptr[k], (size_t)enc_len[k]) < 0) {
            return -1;
        }
    }
    if (item_spans && buf_put(out, first ? "\"\"" : ", \"\"", first ? 2 : 4) < 0)
        return -1;
    if (buf_put(out, "], \"FailedNodes\": {", 19) < 0) return -1;
    first = 1;
    for (Py_ssize_t k = 0; k < num; k++) {
        Py_ssize_t row = rows[k];
        if (row < 0 || !vmask[row] || seen[row]) continue;
        seen[row] = 1;
        n_failed++;
        if (!first && buf_put(out, ", ", 2) < 0) return -1;
        first = 0;
        if (raw_ok[k]) {
            const StrSlice *sl = &cand[k];
            if (buf_put(out, "\"", 1) < 0 ||
                buf_put(out, base + sl->off, (size_t)sl->len) < 0 ||
                buf_put(out, "\"", 1) < 0)
                return -1;
        } else if (buf_put(out, enc_ptr[k], (size_t)enc_len[k]) < 0) {
            return -1;
        }
        if (reason_ptr && reason_ptr[row]) {
            if (buf_put(out, ": ", 2) < 0 ||
                buf_put(out, reason_ptr[row], (size_t)reason_len[row]) < 0)
                return -1;
        } else if (buf_put(out, dflt, dflt_len) < 0) {
            return -1;
        }
    }
    if (buf_put(out, "}, \"Error\": \"\"}\n", 16) < 0) return -1;
    *n_failed_out = n_failed;
    return 0;
}

/* Build the NodeNames-mode FilterResult response straight from the
 * parsed body + name table + a per-row violation bitmask, optionally a
 * per-row reason table:
 *
 *   {"Nodes": null, "NodeNames": [...passing...],
 *    "FailedNodes": {"<name>": "<reason>", ...}, "Error": ""}\n
 *
 * Returns (bytes, n_failed): the failed-entry count rides along so the
 * decision log's per-request counters stay exact without re-parsing.
 *
 * ``reasons`` (optional 4th arg) is a sequence indexed by table row
 * whose entries are pre-JSON-encoded reason strings as bytes (quotes
 * and escapes included — built host-side with json.dumps once per
 * state, utils/decisions.py) or None; a violating row without one gets
 * the reference literal "Node violates".  Splicing pre-encoded bytes
 * keeps byte parity with the exact Python path's json.dumps by
 * construction.
 *
 * Byte-identical to FilterResult.to_json() over the exact Python path's
 * result for the same request (json.dumps separators/ensure_ascii):
 * candidates keep request order; a name can be emitted raw iff its slice
 * has no escapes and every byte is in [0x20,0x7e] (exactly the set
 * json.dumps re-emits unchanged); duplicate violating names collapse to
 * one FailedNodes entry at first-occurrence position (dict semantics);
 * names absent from the table never violate (they pass through).
 *
 * ``nodes_wire`` (filter_encode_nodes) answers a request that carried
 * ``Nodes``: the candidates are Nodes.items, and every passing one is
 * echoed as the slice of the request it arrived in — no decoded object,
 * nothing re-encoded (emit_filter).  Everything outside the items is
 * byte-identical to the exact path; an item is JSON-equal to it, not
 * byte-equal: it keeps the request's separators and escapes, where the
 * exact path writes json.dumps' own.  Returns None, and the exact path
 * answers, where the reference's ``available.split(" ")`` would not
 * give the names back one for one: a candidate named "" or holding a
 * space.
 *
 * ``default`` (optional 5th arg, bytes, pre-JSON-encoded like a reason)
 * takes the place of "Node violates" for a failed row the reason table
 * leaves without one: a gang member's reason for every candidate outside
 * its slice, one string for thousands of rows. */
static PyObject *filter_encode_common(PyObject *args, int nodes_wire) {
    PyObject *parsed_obj, *table_obj, *mask_obj, *reasons_obj = Py_None;
    PyObject *default_obj = Py_None;
    if (!PyArg_ParseTuple(args, "OOO|OO", &parsed_obj, &table_obj, &mask_obj,
                          &reasons_obj, &default_obj))
        return NULL;
    if (default_obj != Py_None && !PyBytes_Check(default_obj)) {
        PyErr_SetString(PyExc_TypeError, "default reason must be bytes");
        return NULL;
    }
    if (!PyObject_TypeCheck(parsed_obj, &ParsedArgs_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected ParsedArgs");
        return NULL;
    }
    if (!PyObject_TypeCheck(table_obj, &NameTable_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected NameTable");
        return NULL;
    }
    ParsedArgs *pa = (ParsedArgs *)parsed_obj;
    NameTable *t = (NameTable *)table_obj;
    Py_buffer viol;
    if (PyObject_GetBuffer(mask_obj, &viol, PyBUF_SIMPLE) < 0) return NULL;
    if (viol.len < t->n_rows) {
        PyBuffer_Release(&viol);
        PyErr_SetString(PyExc_ValueError, "violation mask shorter than table");
        return NULL;
    }
    const uint8_t *vmask = (const uint8_t *)viol.buf;
    const StrSlice *cand = nodes_wire ? pa->names : pa->nn_names;
    Py_ssize_t num = nodes_wire ? pa->num_names : pa->num_nn_names;
    const Py_ssize_t *item_spans = nodes_wire ? pa->item_spans : NULL;
    const char *body = PyBytes_AS_STRING(pa->body);
    if (nodes_wire && (!pa->nodes_present || num == 0)) {
        /* the exact path answers 404 here, and the probe never asks */
        PyBuffer_Release(&viol);
        PyErr_SetString(PyExc_ValueError, "request carries no Nodes items");
        return NULL;
    }

    /* per-candidate resolution: row (or -1) and, for slices json.dumps
     * would re-escape, a pre-encoded buffer built under the GIL */
    Py_ssize_t *rows = NULL;
    uint8_t *raw_ok = NULL;
    uint8_t *seen = NULL;          /* FailedNodes dedup by row */
    const char **enc_ptr = NULL;   /* encoded bytes for non-raw slices */
    Py_ssize_t *enc_len = NULL;
    PyObject **enc_obj = NULL;     /* owned refs backing enc_ptr */
    Py_ssize_t n_enc = 0;
    PyObject *json_mod = NULL, *res = NULL;
    PyObject *reasons_fast = NULL; /* borrowed-item view of reasons_obj */
    const char **reason_ptr = NULL; /* per-row reason bytes (borrowed) */
    Py_ssize_t *reason_len = NULL;
    Py_ssize_t n_failed = 0;
    size_t reason_bytes = 0;
    Buf out_buf = {NULL, 0, 0};
    Buf *out = &out_buf;
    int oom = 0;
    /* the reason of a failed row with none in the table, chosen once */
    char *dflt_buf = NULL;
    const char *dflt = NODE_VIOLATES;
    size_t dflt_len = sizeof(NODE_VIOLATES) - 1;

    rows = PyMem_Malloc((size_t)(num ? num : 1) * sizeof(Py_ssize_t));
    raw_ok = PyMem_Malloc((size_t)(num ? num : 1));
    seen = PyMem_Calloc((size_t)t->n_rows + 1, 1);
    if (!rows || !raw_ok || !seen) { PyErr_NoMemory(); goto done; }
    if (default_obj != Py_None) {
        size_t given = (size_t)PyBytes_GET_SIZE(default_obj);
        dflt_buf = PyMem_Malloc(given + 2);
        if (!dflt_buf) { PyErr_NoMemory(); goto done; }
        memcpy(dflt_buf, ": ", 2);
        memcpy(dflt_buf + 2, PyBytes_AS_STRING(default_obj), given);
        dflt = dflt_buf;
        dflt_len = given + 2;
    }

    size_t span_bytes = 0;
    int split_unsafe = 0;  /* Nodes wire: a name split(" ") would break */
    Py_BEGIN_ALLOW_THREADS
    for (Py_ssize_t k = 0; k < num; k++) {
        const StrSlice *sl = &cand[k];
        int ok = !sl->escaped;
        if (ok) {
            const unsigned char *p = (const unsigned char *)body + sl->off;
            for (Py_ssize_t j = 0; j < sl->len; j++) {
                if (p[j] < 0x20 || p[j] >= 0x7f) { ok = 0; break; }
            }
        }
        raw_ok[k] = (uint8_t)ok;
        if (ok) {
            rows[k] = table_lookup(t, body + sl->off, sl->len);
            span_bytes += (size_t)sl->len;
            if (nodes_wire &&
                (sl->len == 0 || memchr(body + sl->off, ' ', (size_t)sl->len)))
                split_unsafe = 1;
        } else {
            rows[k] = -1;  /* resolved under the GIL below */
            n_enc++;
        }
        if (nodes_wire)
            span_bytes += (size_t)(item_spans[2 * k + 1] - item_spans[2 * k]);
    }
    Py_END_ALLOW_THREADS

    if (n_enc) {
        enc_ptr = PyMem_Calloc((size_t)num, sizeof(char *));
        enc_len = PyMem_Calloc((size_t)num, sizeof(Py_ssize_t));
        enc_obj = PyMem_Calloc((size_t)num, sizeof(PyObject *));
        if (!enc_ptr || !enc_len || !enc_obj) { PyErr_NoMemory(); goto done; }
        json_mod = PyImport_ImportModule("json");
        if (!json_mod) goto done;
        for (Py_ssize_t k = 0; k < num; k++) {
            if (raw_ok[k]) continue;
            PyObject *u = slice_to_unicode(pa->body, &cand[k]);
            if (!u) goto done;
            Py_ssize_t ulen;
            const char *us = PyUnicode_AsUTF8AndSize(u, &ulen);
            if (!us) { Py_DECREF(u); goto done; }
            rows[k] = table_lookup(t, us, ulen);
            if (nodes_wire && (ulen == 0 || memchr(us, ' ', (size_t)ulen)))
                split_unsafe = 1;
            PyObject *e = PyObject_CallMethod(json_mod, "dumps", "O", u);
            Py_DECREF(u);
            if (!e) goto done;
            /* keep the utf-8 of the encoded form alive via a bytes ref */
            PyObject *eb = PyUnicode_AsUTF8String(e);
            Py_DECREF(e);
            if (!eb) goto done;
            enc_obj[k] = eb;
            enc_ptr[k] = PyBytes_AS_STRING(eb);
            enc_len[k] = PyBytes_GET_SIZE(eb);
            span_bytes += (size_t)enc_len[k];
        }
    }

    if (split_unsafe) {
        Py_INCREF(Py_None);
        res = Py_None;
        goto done;
    }

    if (reasons_obj != Py_None) {
        /* resolve per-row reason bytes under the GIL; the fast-sequence
         * ref keeps every bytes item alive through the GIL-free encode */
        reasons_fast = PySequence_Fast(
            reasons_obj, "reasons must be a sequence");
        if (!reasons_fast) goto done;
        Py_ssize_t rsize = PySequence_Fast_GET_SIZE(reasons_fast);
        reason_ptr = PyMem_Calloc((size_t)t->n_rows + 1, sizeof(char *));
        reason_len = PyMem_Calloc((size_t)t->n_rows + 1, sizeof(Py_ssize_t));
        if (!reason_ptr || !reason_len) { PyErr_NoMemory(); goto done; }
        for (Py_ssize_t k = 0; k < num; k++) {
            Py_ssize_t row = rows[k];
            if (row < 0 || row >= rsize || !vmask[row] || reason_ptr[row])
                continue;
            PyObject *item = PySequence_Fast_GET_ITEM(reasons_fast, row);
            if (item == Py_None || !PyBytes_Check(item)) continue;
            reason_ptr[row] = PyBytes_AS_STRING(item);
            reason_len[row] = PyBytes_GET_SIZE(item);
            reason_bytes += (size_t)reason_len[row];
        }
    }

    Py_BEGIN_ALLOW_THREADS
    /* "name", -> len+4 each; failed entry adds ': "Node violates"' (18)
     * or ': ' + its pre-encoded reason bytes (accounted in reason_bytes),
     * or a longer default reason (its excess, a candidate) */
    size_t dflt_extra = dflt_len > sizeof(NODE_VIOLATES) - 1
        ? (dflt_len - (sizeof(NODE_VIOLATES) - 1)) * (size_t)num : 0;
    out_buf = pool_get(96 + span_bytes + (size_t)num * 24 + reason_bytes
                       + dflt_extra);
    if (!out_buf.data) oom = 1;
    if (!oom && emit_filter(out, body, cand, num, rows, raw_ok, enc_ptr,
                            enc_len, vmask, reason_ptr, reason_len, dflt,
                            dflt_len, seen, item_spans, &n_failed) < 0)
        oom = 1;
    Py_END_ALLOW_THREADS

    if (oom) PyErr_NoMemory();
    else {
        PyObject *bytes =
            PyBytes_FromStringAndSize(out->data, (Py_ssize_t)out->len);
        if (bytes) res = Py_BuildValue("(Nn)", bytes, n_failed);
    }

done:
    pool_put(&out_buf);
    if (enc_obj) {
        for (Py_ssize_t k = 0; k < num; k++) Py_XDECREF(enc_obj[k]);
    }
    PyMem_Free(enc_ptr);
    PyMem_Free(enc_len);
    PyMem_Free(enc_obj);
    PyMem_Free(reason_ptr);
    PyMem_Free(reason_len);
    Py_XDECREF(reasons_fast);
    Py_XDECREF(json_mod);
    PyMem_Free(rows);
    PyMem_Free(raw_ok);
    PyMem_Free(seen);
    PyMem_Free(dflt_buf);
    PyBuffer_Release(&viol);
    return res;
}

static PyObject *wirec_filter_encode(PyObject *mod, PyObject *args) {
    (void)mod;
    return filter_encode_common(args, 0);
}

static PyObject *wirec_filter_encode_nodes(PyObject *mod, PyObject *args) {
    (void)mod;
    return filter_encode_common(args, 1);
}

/* candidate_rows(parsed, table) -> bytes | None
 *
 * The table row of every NodeNames candidate in request order, as native
 * int32 (-1: absent from the table): what a gang member's Filter counts
 * its verdict's classes over and takes its slice's clean names from
 * (tas/telemetryscheduler.py) before filter_encode answers it.  None
 * where a row would not stand for its name one for one — a candidate
 * named "", holding a space, or written with escapes (not resolved
 * here) — and the exact path answers.  Runs under the GIL: one hash
 * lookup a candidate. */
static PyObject *wirec_candidate_rows(PyObject *mod, PyObject *args) {
    (void)mod;
    PyObject *parsed_obj, *table_obj;
    if (!PyArg_ParseTuple(args, "OO", &parsed_obj, &table_obj)) return NULL;
    if (!PyObject_TypeCheck(parsed_obj, &ParsedArgs_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected ParsedArgs");
        return NULL;
    }
    if (!PyObject_TypeCheck(table_obj, &NameTable_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected NameTable");
        return NULL;
    }
    ParsedArgs *pa = (ParsedArgs *)parsed_obj;
    NameTable *t = (NameTable *)table_obj;
    Py_ssize_t num = pa->num_nn_names;
    const char *body = PyBytes_AS_STRING(pa->body);
    PyObject *out = PyBytes_FromStringAndSize(
        NULL, num * (Py_ssize_t)sizeof(int32_t));
    if (!out) return NULL;
    int32_t *rows = (int32_t *)PyBytes_AS_STRING(out);
    for (Py_ssize_t k = 0; k < num; k++) {
        const StrSlice *sl = &pa->nn_names[k];
        if (sl->escaped || sl->len == 0 ||
            memchr(body + sl->off, ' ', (size_t)sl->len)) {
            Py_DECREF(out);
            Py_RETURN_NONE;
        }
        rows[k] = (int32_t)table_lookup(t, body + sl->off, sl->len);
    }
    return out;
}

/* ------------------------------------------------------------------ */
/* interned node-name universes                                        */

/* The kube-scheduler re-sends the same ~N-node candidate list for every
 * pending pod; the per-request O(nodes) work left on the wire path —
 * name-slice bookkeeping, per-candidate hash lookups, response-body
 * assembly — is identical across those repeats.  A Universe interns one
 * candidate list ONCE: the raw span bytes (exact-match key), the
 * rebased name slices, per-candidate encode metadata (raw_ok flags +
 * pre-JSON-encoded bytes for names json.dumps would escape), a
 * lazily-materialized Python str tuple for the host paths, and a cached
 * per-NameTable row map so partitioning a verdict over the universe is
 * one pass over an int32 array with ZERO hashing.  UniverseCache is a
 * bounded MRU of universes keyed by a 64-bit content digest and
 * VERIFIED by memcmp — the digest is a prefilter, never a trust source,
 * so a hit is byte-proven and can never serve a stale candidate set.
 *
 * Universes are plain refcounted Python objects: the cache list holds
 * one ref, response-skeleton caches (tas/fastpath.py) hold more, and an
 * evicted universe stays valid for in-flight users until the last ref
 * drops.
 *
 * Concurrency: every Universe/UniverseCache mutation runs WITH the GIL
 * held and without releasing it (the row-map rebuild swaps the pointer
 * only after the new array is fully built and makes no further Python
 * calls before its user re-reads it) — renders over universe state
 * therefore never race a rebuild.  The render loops here are bounded
 * (~100 us at 10k rows) so holding the GIL through them is cheaper
 * than the synchronization a release would require. */

static uint64_t span_digest(const char *s, Py_ssize_t n) {
    /* FNV-1a over 8-byte words (collisions are harmless — memcmp
     * verifies — so word-width beats byte-at-a-time ~8x) */
    uint64_t h = 1469598103934665603ULL;
    const uint64_t prime = 1099511628211ULL;
    Py_ssize_t i = 0;
    for (; i + 8 <= n; i += 8) {
        uint64_t w;
        memcpy(&w, s + i, 8);
        h = (h ^ w) * prime;
    }
    if (i < n) {
        uint64_t tail = 0;
        memcpy(&tail, s + i, (size_t)(n - i));
        h = (h ^ tail) * prime;
    }
    h = (h ^ (uint64_t)n) * prime;
    return h;
}

typedef struct {
    PyObject_HEAD
    uint64_t digest;
    long uid;               /* monotonic id, for /debug/wire */
    int use_node_names;     /* which candidate span this interns */
    PyObject *span;         /* bytes: the exact raw span (slices point in) */
    Py_ssize_t num;         /* candidate count */
    StrSlice *slices;       /* rebased into span */
    uint8_t *raw_ok;        /* per-candidate: bytes emit verbatim in JSON */
    PyObject **enc_obj;     /* per-candidate pre-encoded bytes, or NULL */
    PyObject *names;        /* lazily-built tuple of str */
    PyObject *table;        /* the NameTable the row map was built for */
    int32_t *rows;          /* per-candidate row in ->table, or -1 */
} Universe;

static _Atomic long universe_uid = 0;

static void Universe_dealloc(Universe *self) {
    Py_XDECREF(self->span);
    free(self->slices);
    free(self->raw_ok);
    if (self->enc_obj) {
        for (Py_ssize_t k = 0; k < self->num; k++)
            Py_XDECREF(self->enc_obj[k]);
        free(self->enc_obj);
    }
    Py_XDECREF(self->names);
    Py_XDECREF(self->table);
    free(self->rows);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static PyObject *Universe_get(Universe *self, void *closure) {
    const char *which = (const char *)closure;
    if (strcmp(which, "uid") == 0) return PyLong_FromLong(self->uid);
    if (strcmp(which, "num") == 0) return PyLong_FromSsize_t(self->num);
    if (strcmp(which, "nbytes") == 0)
        return PyLong_FromSsize_t(PyBytes_GET_SIZE(self->span));
    if (strcmp(which, "use_node_names") == 0)
        return PyBool_FromLong(self->use_node_names);
    Py_RETURN_NONE;
}

/* the interned Python str tuple — built once, shared by every host-path
 * consumer of this universe (exact host fallbacks would otherwise
 * materialize N fresh unicode objects per request) */
static PyObject *Universe_names(Universe *self, PyObject *noargs) {
    (void)noargs;
    if (self->names == NULL) {
        PyObject *tup = PyTuple_New(self->num);
        if (!tup) return NULL;
        for (Py_ssize_t k = 0; k < self->num; k++) {
            PyObject *u = slice_to_unicode(self->span, &self->slices[k]);
            if (!u) { Py_DECREF(tup); return NULL; }
            PyTuple_SET_ITEM(tup, k, u);
        }
        if (self->names == NULL) self->names = tup;
        else Py_DECREF(tup);  /* a concurrent builder won */
    }
    Py_INCREF(self->names);
    return self->names;
}

/* ensure self->rows maps this universe onto ``table``; returns the live
 * row array (borrowed).  Called with the GIL held; the swap happens
 * only after the new array is complete, and callers re-read ->rows
 * after this returns and then make no GIL-yielding calls while using
 * it, so a concurrent rebuild can never free an array in use. */
static int32_t *universe_rows_for(Universe *self, NameTable *t) {
    if (self->table == (PyObject *)t && self->rows != NULL)
        return self->rows;
    int32_t *rows = malloc((size_t)(self->num ? self->num : 1) *
                           sizeof(int32_t));
    if (!rows) { PyErr_NoMemory(); return NULL; }
    const char *base = PyBytes_AS_STRING(self->span);
    for (Py_ssize_t k = 0; k < self->num; k++) {
        const StrSlice *sl = &self->slices[k];
        Py_ssize_t row;
        if (!sl->escaped) {
            row = table_lookup(t, base + sl->off, sl->len);
        } else {
            /* rare: decode exactly like the per-request encoders do */
            PyObject *u = slice_to_unicode(self->span, sl);
            if (!u) { free(rows); return NULL; }
            Py_ssize_t ulen;
            const char *us = PyUnicode_AsUTF8AndSize(u, &ulen);
            if (!us) { Py_DECREF(u); free(rows); return NULL; }
            row = table_lookup(t, us, ulen);
            Py_DECREF(u);
        }
        rows[k] = row >= 0 && row <= INT32_MAX ? (int32_t)row : -1;
    }
    int32_t *old = self->rows;
    PyObject *old_table = self->table;
    Py_INCREF((PyObject *)t);
    self->rows = rows;
    self->table = (PyObject *)t;
    free(old);
    Py_XDECREF(old_table);
    return self->rows;
}

static PyGetSetDef Universe_getset[] = {
    {"uid", (getter)Universe_get, NULL, NULL, "uid"},
    {"num", (getter)Universe_get, NULL, NULL, "num"},
    {"nbytes", (getter)Universe_get, NULL, NULL, "nbytes"},
    {"use_node_names", (getter)Universe_get, NULL, NULL, "use_node_names"},
    {NULL},
};

static PyMethodDef Universe_methods[] = {
    {"names", (PyCFunction)Universe_names, METH_NOARGS,
     "The interned candidate-name tuple (built once, then shared)."},
    {NULL},
};

static PyTypeObject Universe_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_wirec.Universe",
    .tp_basicsize = sizeof(Universe),
    .tp_dealloc = (destructor)Universe_dealloc,
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_getset = Universe_getset,
    .tp_methods = Universe_methods,
};

/* span extent of the candidate list a universe would intern; -1 start
 * when the request has no such span */
static void parsed_span(ParsedArgs *pa, int use_nn, Py_ssize_t *start,
                        Py_ssize_t *end, const StrSlice **slices,
                        Py_ssize_t *num) {
    if (use_nn) {
        *start = pa->nn_span_start;
        *end = pa->nn_span_end;
        *slices = pa->nn_names;
        *num = pa->num_nn_names;
    } else {
        *start = pa->nodes_span_start;
        *end = pa->nodes_span_end;
        *slices = pa->names;
        *num = pa->num_names;
    }
}

#define SEEN_RING 64

typedef struct {
    PyObject_HEAD
    Py_ssize_t capacity;
    PyObject *entries;        /* list of Universe, MRU first */
    /* once-seen digest ring: a universe is interned only on its SECOND
     * sighting, so a stream of one-shot candidate lists (the bench's
     * rotated miss tier, a churning cluster) never pays intern+evict
     * churn for spans that will never repeat */
    uint64_t seen_dig[SEEN_RING];
    Py_ssize_t seen_len[SEEN_RING];
    int seen_next;
} UniverseCache;

static void UniverseCache_dealloc(UniverseCache *self) {
    Py_XDECREF(self->entries);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static PyObject *UniverseCache_new(PyTypeObject *type, PyObject *args,
                                   PyObject *kwds) {
    Py_ssize_t capacity = 8;
    static char *kwlist[] = {"capacity", NULL};
    if (!PyArg_ParseTupleAndKeywords(args, kwds, "|n", kwlist, &capacity))
        return NULL;
    if (capacity < 1) {
        PyErr_SetString(PyExc_ValueError, "capacity must be >= 1");
        return NULL;
    }
    UniverseCache *self = (UniverseCache *)type->tp_alloc(type, 0);
    if (!self) return NULL;
    self->capacity = capacity;
    self->entries = PyList_New(0);
    if (!self->entries) { Py_DECREF(self); return NULL; }
    memset(self->seen_dig, 0, sizeof(self->seen_dig));
    memset(self->seen_len, 0, sizeof(self->seen_len));
    self->seen_next = 0;
    return (PyObject *)self;
}

/* the shared digest-taking internals: every public entry point computes
 * the span digest EXACTLY ONCE and hands it down (the round-1 review
 * caught lookup+note_seen+intern re-sweeping the same ~150 KB span up
 * to three times per cold request) */

static int cache_args(PyObject *args, ParsedArgs **pa_out, int *use_nn_out) {
    PyObject *parsed_obj;
    if (!PyArg_ParseTuple(args, "Op", &parsed_obj, use_nn_out)) return -1;
    if (!PyObject_TypeCheck(parsed_obj, &ParsedArgs_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected ParsedArgs");
        return -1;
    }
    *pa_out = (ParsedArgs *)parsed_obj;
    return 0;
}

/* BORROWED matching universe after MRU promotion, or NULL (not found,
 * or promotion OOM with the error set — check PyErr_Occurred) */
static Universe *cache_find(UniverseCache *self, uint64_t digest, int use_nn,
                            const char *span_ptr, Py_ssize_t span_len) {
    Py_ssize_t count = PyList_GET_SIZE(self->entries);
    for (Py_ssize_t idx = 0; idx < count; idx++) {
        Universe *u = (Universe *)PyList_GET_ITEM(self->entries, idx);
        if (u->digest != digest || u->use_node_names != use_nn ||
            PyBytes_GET_SIZE(u->span) != span_len)
            continue;
        if (memcmp(PyBytes_AS_STRING(u->span), span_ptr,
                   (size_t)span_len) != 0)
            continue;
        if (idx) {  /* MRU */
            PyObject *obj = (PyObject *)u;
            Py_INCREF(obj);
            if (PyList_SetSlice(self->entries, idx, idx + 1, NULL) < 0 ||
                PyList_Insert(self->entries, 0, obj) < 0) {
                Py_DECREF(obj);
                return NULL;
            }
            Py_DECREF(obj);
        }
        return u;
    }
    return NULL;
}

/* 1 when (digest, len) is already in the once-seen ring; else note it
 * and return 0 */
static int cache_seen(UniverseCache *self, uint64_t digest,
                      Py_ssize_t span_len) {
    for (int k = 0; k < SEEN_RING; k++) {
        if (self->seen_len[k] == span_len && self->seen_dig[k] == digest)
            return 1;
    }
    self->seen_dig[self->seen_next] = digest;
    self->seen_len[self->seen_next] = span_len;
    self->seen_next = (self->seen_next + 1) % SEEN_RING;
    return 0;
}

static Universe *cache_intern(UniverseCache *self, ParsedArgs *pa,
                              int use_nn, uint64_t digest, Py_ssize_t start,
                              Py_ssize_t end, const StrSlice *slices,
                              Py_ssize_t num, Py_ssize_t *evicted_out);

/* lookup(parsed, use_node_names) -> Universe | None.  Digest prefilter
 * + full-span memcmp verify (zero false positives), MRU reorder on
 * hit.  Runs entirely under the GIL: one call is atomic w.r.t. other
 * serving threads. */
static PyObject *UniverseCache_lookup(UniverseCache *self, PyObject *args) {
    ParsedArgs *pa;
    int use_nn;
    if (cache_args(args, &pa, &use_nn) < 0) return NULL;
    Py_ssize_t start, end, num;
    const StrSlice *slices;
    parsed_span(pa, use_nn, &start, &end, &slices, &num);
    if (start < 0) Py_RETURN_NONE;
    const char *ptr = PyBytes_AS_STRING(pa->body) + start;
    Py_ssize_t span_len = end - start;
    Universe *u = cache_find(self, span_digest(ptr, span_len), use_nn, ptr,
                             span_len);
    if (!u) {
        if (PyErr_Occurred()) return NULL;
        Py_RETURN_NONE;
    }
    Py_INCREF((PyObject *)u);
    return (PyObject *)u;
}

/* note_seen(parsed, use_node_names) -> bool: record the span digest in
 * the once-seen ring; True when it was already there (the caller should
 * intern now — this is the span's second sighting). */
static PyObject *UniverseCache_note_seen(UniverseCache *self, PyObject *args) {
    ParsedArgs *pa;
    int use_nn;
    if (cache_args(args, &pa, &use_nn) < 0) return NULL;
    Py_ssize_t start, end, num;
    const StrSlice *slices;
    parsed_span(pa, use_nn, &start, &end, &slices, &num);
    if (start < 0) Py_RETURN_FALSE;
    const char *ptr = PyBytes_AS_STRING(pa->body) + start;
    Py_ssize_t span_len = end - start;
    return PyBool_FromLong(
        cache_seen(self, span_digest(ptr, span_len), span_len));
}

/* probe(parsed, use_node_names) -> (Universe | None, interned, evicted):
 * the serving entry point — ONE digest pass covers hit lookup, the
 * once-seen check, and (on a second sighting) the intern.  A hit is
 * (u, False, 0); a first sighting notes the digest and returns
 * (None, False, 0); a second sighting interns and returns
 * (u, True, evicted). */
static PyObject *UniverseCache_probe(UniverseCache *self, PyObject *args) {
    ParsedArgs *pa;
    int use_nn;
    if (cache_args(args, &pa, &use_nn) < 0) return NULL;
    Py_ssize_t start, end, num;
    const StrSlice *slices;
    parsed_span(pa, use_nn, &start, &end, &slices, &num);
    if (start < 0) return Py_BuildValue("(OOn)", Py_None, Py_False, 0);
    const char *ptr = PyBytes_AS_STRING(pa->body) + start;
    Py_ssize_t span_len = end - start;
    uint64_t digest = span_digest(ptr, span_len);
    Universe *found = cache_find(self, digest, use_nn, ptr, span_len);
    if (found) return Py_BuildValue("(OOn)", (PyObject *)found, Py_False, 0);
    if (PyErr_Occurred()) return NULL;
    if (!cache_seen(self, digest, span_len))
        return Py_BuildValue("(OOn)", Py_None, Py_False, 0);
    Py_ssize_t evicted = 0;
    Universe *u = cache_intern(self, pa, use_nn, digest, start, end, slices,
                               num, &evicted);
    if (!u) return NULL;
    return Py_BuildValue("(NOn)", (PyObject *)u, Py_True, evicted);
}

/* intern(parsed, use_node_names) -> (Universe, evicted_count) */
static PyObject *UniverseCache_intern(UniverseCache *self, PyObject *args) {
    ParsedArgs *pa;
    int use_nn;
    if (cache_args(args, &pa, &use_nn) < 0) return NULL;
    Py_ssize_t start, end, num;
    const StrSlice *slices;
    parsed_span(pa, use_nn, &start, &end, &slices, &num);
    if (start < 0) {
        PyErr_SetString(PyExc_ValueError, "request has no candidate span");
        return NULL;
    }
    const char *ptr = PyBytes_AS_STRING(pa->body) + start;
    Py_ssize_t evicted = 0;
    Universe *u = cache_intern(self, pa, use_nn,
                               span_digest(ptr, end - start), start, end,
                               slices, num, &evicted);
    if (!u) return NULL;
    return Py_BuildValue("(Nn)", (PyObject *)u, evicted);
}

/* NEW reference to the interned universe, inserted MRU-first with the
 * cache trimmed to capacity (*evicted_out = how many dropped) */
static Universe *cache_intern(UniverseCache *self, ParsedArgs *pa,
                              int use_nn, uint64_t digest, Py_ssize_t start,
                              Py_ssize_t end, const StrSlice *slices,
                              Py_ssize_t num, Py_ssize_t *evicted_out) {
    const char *body = PyBytes_AS_STRING(pa->body);
    Py_ssize_t span_len = end - start;

    Universe *u = PyObject_New(Universe, &Universe_Type);
    if (!u) return NULL;
    u->digest = digest;
    u->uid = atomic_fetch_add_explicit(&universe_uid, 1,
                                       memory_order_relaxed) + 1;
    u->use_node_names = use_nn;
    u->span = NULL;
    u->num = num;
    u->slices = NULL;
    u->raw_ok = NULL;
    u->enc_obj = NULL;
    u->names = NULL;
    u->table = NULL;
    u->rows = NULL;
    u->span = PyBytes_FromStringAndSize(body + start, span_len);
    u->slices = malloc((size_t)(num ? num : 1) * sizeof(StrSlice));
    u->raw_ok = malloc((size_t)(num ? num : 1));
    u->enc_obj = calloc((size_t)(num ? num : 1), sizeof(PyObject *));
    if (!u->span || !u->slices || !u->raw_ok || !u->enc_obj) {
        /* span failure set its own error; raw-malloc failures need ours */
        if (u->span) PyErr_NoMemory();
        Py_DECREF(u);
        return NULL;
    }
    const char *span_base = PyBytes_AS_STRING(u->span);
    PyObject *json_mod = NULL;
    for (Py_ssize_t k = 0; k < num; k++) {
        StrSlice sl = slices[k];
        sl.off -= start;  /* rebase into the span copy */
        u->slices[k] = sl;
        int ok = !sl.escaped;
        if (ok) {
            const unsigned char *p =
                (const unsigned char *)span_base + sl.off;
            for (Py_ssize_t j = 0; j < sl.len; j++) {
                if (p[j] < 0x20 || p[j] >= 0x7f) { ok = 0; break; }
            }
        }
        u->raw_ok[k] = (uint8_t)ok;
        if (!ok) {
            /* pre-encode ONCE what the per-request encoders would
             * json.dumps per request (exact parity by construction) */
            PyObject *uni = slice_to_unicode(u->span, &u->slices[k]);
            if (!uni) goto error;
            if (!json_mod) {
                json_mod = PyImport_ImportModule("json");
                if (!json_mod) { Py_DECREF(uni); goto error; }
            }
            PyObject *e =
                PyObject_CallMethod(json_mod, "dumps", "O", uni);
            Py_DECREF(uni);
            if (!e) goto error;
            PyObject *eb = PyUnicode_AsUTF8String(e);
            Py_DECREF(e);
            if (!eb) goto error;
            u->enc_obj[k] = eb;
        }
    }
    Py_XDECREF(json_mod);
    json_mod = NULL;

    if (PyList_Insert(self->entries, 0, (PyObject *)u) < 0) goto error;
    Py_ssize_t evicted = PyList_GET_SIZE(self->entries) - self->capacity;
    if (evicted > 0) {
        if (PyList_SetSlice(self->entries, self->capacity,
                            PyList_GET_SIZE(self->entries), NULL) < 0)
            goto error;
    } else {
        evicted = 0;
    }
    *evicted_out = evicted;
    return u;

error:
    Py_XDECREF(json_mod);
    Py_DECREF(u);
    return NULL;
}

/* snapshot() -> [Universe, ...] in MRU order — the state-change warmer
 * iterates these to pre-render response skeletons off the request path */
static PyObject *UniverseCache_snapshot(UniverseCache *self,
                                        PyObject *noargs) {
    (void)noargs;
    return PyList_GetSlice(self->entries, 0,
                           PyList_GET_SIZE(self->entries));
}

static PyObject *UniverseCache_universes(UniverseCache *self,
                                         PyObject *noargs) {
    (void)noargs;
    Py_ssize_t count = PyList_GET_SIZE(self->entries);
    PyObject *out = PyList_New(count);
    if (!out) return NULL;
    for (Py_ssize_t idx = 0; idx < count; idx++) {
        Universe *u = (Universe *)PyList_GET_ITEM(self->entries, idx);
        PyObject *d = Py_BuildValue(
            "{s:l, s:s, s:n, s:n}",
            "uid", u->uid,
            "kind", u->use_node_names ? "nodenames" : "nodes",
            "names", u->num,
            "bytes", PyBytes_GET_SIZE(u->span));
        if (!d) { Py_DECREF(out); return NULL; }
        PyList_SET_ITEM(out, idx, d);
    }
    return out;
}

static PyObject *UniverseCache_get(UniverseCache *self, void *closure) {
    const char *which = (const char *)closure;
    if (strcmp(which, "capacity") == 0)
        return PyLong_FromSsize_t(self->capacity);
    if (strcmp(which, "occupancy") == 0)
        return PyLong_FromSsize_t(PyList_GET_SIZE(self->entries));
    Py_RETURN_NONE;
}

static PyGetSetDef UniverseCache_getset[] = {
    {"capacity", (getter)UniverseCache_get, NULL, NULL, "capacity"},
    {"occupancy", (getter)UniverseCache_get, NULL, NULL, "occupancy"},
    {NULL},
};

static PyMethodDef UniverseCache_methods[] = {
    {"lookup", (PyCFunction)UniverseCache_lookup, METH_VARARGS,
     "Digest + memcmp-verified universe for this request's candidate "
     "span, MRU-promoted; None on miss."},
    {"probe", (PyCFunction)UniverseCache_probe, METH_VARARGS,
     "One-digest serving probe: (universe|None, interned, evicted)."},
    {"note_seen", (PyCFunction)UniverseCache_note_seen, METH_VARARGS,
     "Record the span digest; True when already seen (intern now)."},
    {"intern", (PyCFunction)UniverseCache_intern, METH_VARARGS,
     "Intern the request's candidate span; returns (Universe, evicted)."},
    {"universes", (PyCFunction)UniverseCache_universes, METH_NOARGS,
     "Debug snapshot: [{uid, kind, names, bytes}] in MRU order."},
    {"snapshot", (PyCFunction)UniverseCache_snapshot, METH_NOARGS,
     "The live Universe objects in MRU order (skeleton pre-warming)."},
    {NULL},
};

static PyTypeObject UniverseCache_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "_wirec.UniverseCache",
    .tp_basicsize = sizeof(UniverseCache),
    .tp_new = UniverseCache_new,
    .tp_dealloc = (destructor)UniverseCache_dealloc,
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_getset = UniverseCache_getset,
    .tp_methods = UniverseCache_methods,
};

/* ------------------------------------------------------------------ */
/* universe-backed encoders                                            */

/* filter_respond(universe, table, mask, reasons) -> (bytes, n_failed)
 *
 * The universe twin of filter_encode: candidates come from the interned
 * span, rows from the cached per-table map (ONE array read per
 * candidate, zero hashing), raw_ok/escape encodings pre-resolved at
 * intern time.  Output bytes are identical to filter_encode over the
 * same request by construction — both emit the same candidate order,
 * dedup, reasons, and framing from the same per-row data.  Runs under
 * the GIL throughout (see the universe concurrency note). */
static PyObject *wirec_filter_respond(PyObject *mod, PyObject *args) {
    (void)mod;
    PyObject *universe_obj, *table_obj, *mask_obj, *reasons_obj = Py_None;
    if (!PyArg_ParseTuple(args, "OOO|O", &universe_obj, &table_obj,
                          &mask_obj, &reasons_obj))
        return NULL;
    if (!PyObject_TypeCheck(universe_obj, &Universe_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected Universe");
        return NULL;
    }
    if (!PyObject_TypeCheck(table_obj, &NameTable_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected NameTable");
        return NULL;
    }
    Universe *u = (Universe *)universe_obj;
    NameTable *t = (NameTable *)table_obj;
    if (!u->use_node_names) {
        PyErr_SetString(PyExc_ValueError,
                        "filter_respond serves NodeNames universes only");
        return NULL;
    }
    Py_buffer viol;
    if (PyObject_GetBuffer(mask_obj, &viol, PyBUF_SIMPLE) < 0) return NULL;
    if (viol.len < t->n_rows) {
        PyBuffer_Release(&viol);
        PyErr_SetString(PyExc_ValueError, "violation mask shorter than table");
        return NULL;
    }
    const uint8_t *vmask = (const uint8_t *)viol.buf;

    /* resolve the row map first (may make Python calls), THEN take the
     * live pointer and stay GIL-atomic for the rest of the call */
    if (universe_rows_for(u, t) == NULL) {
        PyBuffer_Release(&viol);
        return NULL;
    }
    PyObject *reasons_fast = NULL;
    const char **reason_ptr = NULL;
    Py_ssize_t *reason_len = NULL;
    uint8_t *seen = NULL;
    Py_ssize_t *rows = NULL;
    const char **enc_ptr = NULL;
    Py_ssize_t *enc_len = NULL;
    PyObject *res = NULL;
    size_t reason_bytes = 0;
    Buf out_buf = {NULL, 0, 0};
    Buf *out = &out_buf;
    int oom = 0;
    const int32_t *rows32 = u->rows;
    Py_ssize_t num = u->num;
    const char *span = PyBytes_AS_STRING(u->span);

    /* adapt the universe's cached per-candidate state into the shared
     * emit shape: widened rows, plus enc pointer/length views over the
     * pre-encoded bytes objects (refs held by the universe) */
    seen = PyMem_Calloc((size_t)t->n_rows + 1, 1);
    rows = PyMem_Malloc((size_t)(num ? num : 1) * sizeof(Py_ssize_t));
    enc_ptr = PyMem_Calloc((size_t)(num ? num : 1), sizeof(char *));
    enc_len = PyMem_Calloc((size_t)(num ? num : 1), sizeof(Py_ssize_t));
    if (!seen || !rows || !enc_ptr || !enc_len) {
        PyErr_NoMemory();
        goto done;
    }
    for (Py_ssize_t k = 0; k < num; k++) {
        rows[k] = rows32[k];
        if (!u->raw_ok[k]) {
            enc_ptr[k] = PyBytes_AS_STRING(u->enc_obj[k]);
            enc_len[k] = PyBytes_GET_SIZE(u->enc_obj[k]);
        }
    }
    if (reasons_obj != Py_None) {
        reasons_fast =
            PySequence_Fast(reasons_obj, "reasons must be a sequence");
        if (!reasons_fast) goto done;
        Py_ssize_t rsize = PySequence_Fast_GET_SIZE(reasons_fast);
        reason_ptr = PyMem_Calloc((size_t)t->n_rows + 1, sizeof(char *));
        reason_len = PyMem_Calloc((size_t)t->n_rows + 1, sizeof(Py_ssize_t));
        if (!reason_ptr || !reason_len) { PyErr_NoMemory(); goto done; }
        for (Py_ssize_t k = 0; k < num; k++) {
            Py_ssize_t row = rows[k];
            if (row < 0 || row >= rsize || !vmask[row] || reason_ptr[row])
                continue;
            PyObject *item = PySequence_Fast_GET_ITEM(reasons_fast, row);
            if (item == Py_None || !PyBytes_Check(item)) continue;
            reason_ptr[row] = PyBytes_AS_STRING(item);
            reason_len[row] = PyBytes_GET_SIZE(item);
            reason_bytes += (size_t)reason_len[row];
        }
    }

    {
        size_t span_bytes = (size_t)PyBytes_GET_SIZE(u->span);
        Py_ssize_t n_failed = 0;
        out_buf = pool_get(96 + span_bytes + (size_t)num * 24 + reason_bytes);
        if (!out_buf.data) oom = 1;
        if (!oom && emit_filter(out, span, u->slices, num, rows, u->raw_ok,
                                enc_ptr, enc_len, vmask, reason_ptr,
                                reason_len, NODE_VIOLATES,
                                sizeof(NODE_VIOLATES) - 1, seen, NULL,
                                &n_failed) < 0)
            oom = 1;
        if (oom) PyErr_NoMemory();
        else {
            PyObject *bytes =
                PyBytes_FromStringAndSize(out->data, (Py_ssize_t)out->len);
            if (bytes) res = Py_BuildValue("(Nn)", bytes, n_failed);
        }
    }

done:
    pool_put(&out_buf);
    PyMem_Free(reason_ptr);
    PyMem_Free(reason_len);
    Py_XDECREF(reasons_fast);
    PyMem_Free(seen);
    PyMem_Free(rows);
    PyMem_Free(enc_ptr);
    PyMem_Free(enc_len);
    PyBuffer_Release(&viol);
    return res;
}

/* select_encode_universe(universe, table, ranked, planned_row,
 *                        promotion=False) -> bytes | (bytes, promotion)
 *
 * The universe twin of select_encode: the candidate mask fills from the
 * cached row map instead of per-name hash lookups; the emit loop is
 * identical, so bytes match select_encode over the same request by
 * construction. */
static PyObject *wirec_select_encode_universe(PyObject *mod, PyObject *args) {
    (void)mod;
    PyObject *universe_obj, *table_obj, *ranked_obj;
    Py_ssize_t planned_row = -1;
    int want_promotion = 0, promotion = 0;
    if (!PyArg_ParseTuple(args, "OOO|np", &universe_obj, &table_obj,
                          &ranked_obj, &planned_row, &want_promotion))
        return NULL;
    if (!PyObject_TypeCheck(universe_obj, &Universe_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected Universe");
        return NULL;
    }
    if (!PyObject_TypeCheck(table_obj, &NameTable_Type)) {
        PyErr_SetString(PyExc_TypeError, "expected NameTable");
        return NULL;
    }
    Universe *u = (Universe *)universe_obj;
    NameTable *t = (NameTable *)table_obj;
    Py_buffer ranked;
    if (PyObject_GetBuffer(ranked_obj, &ranked, PyBUF_SIMPLE) < 0)
        return NULL;
    if (ranked.len % sizeof(int64_t) != 0) {
        PyBuffer_Release(&ranked);
        PyErr_SetString(PyExc_ValueError, "ranked must be int64 buffer");
        return NULL;
    }
    const int64_t *order = (const int64_t *)ranked.buf;
    Py_ssize_t n_ranked = ranked.len / sizeof(int64_t);

    if (universe_rows_for(u, t) == NULL) {
        PyBuffer_Release(&ranked);
        return NULL;
    }
    const int32_t *rows = u->rows;

    Buf mask_buf = pool_get((size_t)t->n_rows + 1);
    if (!mask_buf.data) {
        PyBuffer_Release(&ranked);
        return PyErr_NoMemory();
    }
    uint8_t *mask = (uint8_t *)mask_buf.data;
    memset(mask, 0, (size_t)t->n_rows + 1);
    for (Py_ssize_t k = 0; k < u->num; k++) {
        if (rows[k] >= 0) mask[rows[k]] = 1;
    }

    Buf out_buf = {NULL, 0, 0};
    Buf *out = &out_buf;
    int oom = 0;
    out_buf = pool_get(ranked_estimate(t, mask));
    if (!out_buf.data) oom = 1;
    if (!oom && emit_ranked(out, t, mask, order, n_ranked, planned_row,
                            &promotion) < 0)
        oom = 1;
    pool_put(&mask_buf);
    PyBuffer_Release(&ranked);
    if (oom) {
        pool_put(&out_buf);
        return PyErr_NoMemory();
    }
    PyObject *res = PyBytes_FromStringAndSize(out->data, (Py_ssize_t)out->len);
    pool_put(&out_buf);
    if (res && want_promotion) return Py_BuildValue("(Ni)", res, promotion);
    return res;
}

/* ------------------------------------------------------------------ */
/* recv_stamped, recv_body: socket reads that say when their bytes were
 * there; send_answer: an answer out with the GIL held where the kernel
 * takes it whole */

#include <errno.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <time.h>

static double monotonic_now(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (double)ts.tv_sec + (double)ts.tv_nsec * 1e-9;
}

/* poll(fd, events) until `deadline` (monotonic_now()'s seconds; for ever
 * where timeout_s < 0): 1 ready, 0 timed out, -1 with errno set.  Touches
 * no Python object: the reads and send_answer call it with the GIL
 * released. */
static int wait_ready(int fd, short events, double timeout_s, double deadline) {
    for (;;) {
        int wait_ms = -1;
        if (timeout_s >= 0) {
            double left = deadline - monotonic_now();
            if (left < 0) left = 0;
            /* round up: a poll that returns a millisecond early would spin */
            wait_ms = left > 2e6 ? 2000000000 : (int)(left * 1e3 + 0.999);
        }
        struct pollfd p = {fd, events, 0};
        int ready = poll(&p, 1, wait_ms);
        if (ready > 0) return 1;
        if (ready < 0) {
            if (errno == EINTR) continue;
            return -1;
        }
        if (timeout_s >= 0 && monotonic_now() < deadline) continue;
        return 0;
    }
}

/* recv_stamped(fd, max_bytes, timeout_s) -> (bytes, t_ready, t_held)
 *
 * poll + recv with the GIL released, as sock.recv does on a socket with a
 * timeout: poll, then a recv that never blocks (MSG_DONTWAIT, whatever the
 * descriptor's own mode), again on EAGAIN / EINTR.  t_ready is CLOCK_MONOTONIC taken after recv has returned
 * and BEFORE the GIL is asked back; t_held the same clock right after it is
 * held again.  Both in seconds on time.perf_counter()'s clock where that is
 * CLOCK_MONOTONIC (the caller checks; extender/server.py).  t_held - t_ready
 * is what the calling thread waited for the interpreter with its bytes in
 * hand — the one wait no Python-level stamp can see.  timeout_s < 0 waits
 * for ever.  A time-out raises TimeoutError, an error OSError, as sock.recv
 * does; a closed peer gives b"". */
static PyObject *wirec_recv_stamped(PyObject *self, PyObject *args) {
    int fd;
    Py_ssize_t max_bytes;
    double timeout_s;
    if (!PyArg_ParseTuple(args, "ind", &fd, &max_bytes, &timeout_s))
        return NULL;
    if (max_bytes < 0) {
        PyErr_SetString(PyExc_ValueError, "negative buffersize in recv_stamped");
        return NULL;
    }
    if (fd < 0) {  /* a closed socket's fileno(): poll would wait it out */
        errno = EBADF;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    PyObject *out = PyBytes_FromStringAndSize(NULL, max_bytes);
    if (!out) return NULL;
    char *data = PyBytes_AS_STRING(out);
    ssize_t got = -1;
    int err = 0, timed_out = 0;
    double t_ready, t_held;

    Py_BEGIN_ALLOW_THREADS
    double deadline = timeout_s >= 0 ? monotonic_now() + timeout_s : 0.0;
    for (;;) {
        int ready = wait_ready(fd, POLLIN, timeout_s, deadline);
        if (ready < 0) {
            err = errno;
            break;
        }
        if (ready == 0) {
            timed_out = 1;
            break;
        }
        got = recv(fd, data, (size_t)max_bytes, MSG_DONTWAIT);
        if (got >= 0) break;
        if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR) continue;
        err = errno;
        break;
    }
    t_ready = monotonic_now();
    Py_END_ALLOW_THREADS
    t_held = monotonic_now();

    if (timed_out) {
        Py_DECREF(out);
        PyErr_SetString(PyExc_TimeoutError, "timed out");
        return NULL;
    }
    if (got < 0) {
        Py_DECREF(out);
        errno = err;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    if (got != max_bytes && _PyBytes_Resize(&out, got) < 0) return NULL;
    return Py_BuildValue("(Ndd)", out, t_ready, t_held);
}

/* recv_body(fd, prefix, length, timeout_s) -> (bytes, t_ready, t_held, n_recv)
 *
 * A request's body in one release of the GIL: the bytes object of exactly
 * `length` is allocated here, `prefix` (what the head's read left over) is
 * copied to its front, and poll + recv fill the rest of it in place — as
 * many recvs as the kernel needs, none of them a trip through Python.  recv
 * is never asked for more than is still missing, so a pipelined next request
 * stays in the socket.  timeout_s is so long WITHOUT A BYTE, as each
 * recv_stamped of the same body had it, not so long for the body; < 0 waits
 * for ever.  t_ready / t_held as recv_stamped's, after the last byte; n_recv
 * counts the recvs that returned bytes.  A time-out raises TimeoutError, an
 * error OSError, a peer that closes before the last byte
 * ConnectionResetError (an OSError: the caller ends the connection on any
 * of them). */
static PyObject *wirec_recv_body(PyObject *self, PyObject *args) {
    int fd;
    Py_buffer prefix;
    Py_ssize_t length;
    double timeout_s;
    if (!PyArg_ParseTuple(args, "iy*nd", &fd, &prefix, &length, &timeout_s))
        return NULL;
    if (length < 0 || prefix.len > length) {
        PyBuffer_Release(&prefix);
        PyErr_SetString(PyExc_ValueError,
                        length < 0 ? "negative length in recv_body"
                                   : "prefix longer than length in recv_body");
        return NULL;
    }
    if (fd < 0) {
        PyBuffer_Release(&prefix);
        errno = EBADF;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    PyObject *out = PyBytes_FromStringAndSize(NULL, length);
    if (!out) {
        PyBuffer_Release(&prefix);
        return NULL;
    }
    char *data = PyBytes_AS_STRING(out);
    Py_ssize_t filled = prefix.len;
    memcpy(data, prefix.buf, (size_t)filled);
    PyBuffer_Release(&prefix);  /* no Python object is touched below */
    int err = 0, timed_out = 0, closed = 0;
    long n_recv = 0;
    double t_ready, t_held;

    Py_BEGIN_ALLOW_THREADS
    double deadline = timeout_s >= 0 ? monotonic_now() + timeout_s : 0.0;
    while (filled < length) {
        int ready = wait_ready(fd, POLLIN, timeout_s, deadline);
        if (ready < 0) {
            err = errno;
            break;
        }
        if (ready == 0) {
            timed_out = 1;
            break;
        }
        ssize_t got = recv(fd, data + filled, (size_t)(length - filled),
                           MSG_DONTWAIT);
        if (got > 0) {
            filled += got;
            n_recv++;
            if (timeout_s >= 0) deadline = monotonic_now() + timeout_s;
            continue;
        }
        if (got == 0) {
            closed = 1;
            break;
        }
        if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR) continue;
        err = errno;
        break;
    }
    t_ready = monotonic_now();
    Py_END_ALLOW_THREADS
    t_held = monotonic_now();

    if (filled < length) {
        Py_DECREF(out);
        if (timed_out) {
            PyErr_SetString(PyExc_TimeoutError, "timed out");
        } else if (closed) {
            PyErr_Format(PyExc_ConnectionResetError,
                         "peer closed %zd bytes short of the body",
                         length - filled);
        } else {
            errno = err;
            PyErr_SetFromErrno(PyExc_OSError);
        }
        return NULL;
    }
    return Py_BuildValue("(Nddl)", out, t_ready, t_held, n_recv);
}

/* The part of head + body not yet sent, from `sent` on, as iovecs: the
 * number of them (0 where nothing is left). */
static int answer_iov(struct iovec *iov, const Py_buffer *head,
                      const Py_buffer *body, size_t sent) {
    int n = 0;
    size_t head_len = (size_t)head->len, body_len = (size_t)body->len;
    if (sent < head_len) {
        iov[n].iov_base = (char *)head->buf + sent;
        iov[n++].iov_len = head_len - sent;
        sent = 0;
    } else {
        sent -= head_len;
    }
    if (sent < body_len) {
        iov[n].iov_base = (char *)body->buf + sent;
        iov[n++].iov_len = body_len - sent;
    }
    return n;
}

/* One non-blocking sendmsg of what is left: bytes the kernel took, or -1
 * with errno set.  MSG_DONTWAIT whatever the descriptor's own mode, and
 * MSG_NOSIGNAL: a peer that went is EPIPE, never SIGPIPE. */
static ssize_t send_rest(int fd, const Py_buffer *head, const Py_buffer *body,
                         size_t sent) {
    struct iovec iov[2];
    struct msghdr msg;
    memset(&msg, 0, sizeof msg);
    msg.msg_iov = iov;
    msg.msg_iovlen = (size_t)answer_iov(iov, head, body, sent);
    return sendmsg(fd, &msg, MSG_DONTWAIT | MSG_NOSIGNAL);
}

/* send_answer(fd, head, body, timeout_s) -> (sends, released)
 *
 * An answer's status line and headers (`head`) and its body, any two buffer
 * objects, sent as one: first ONE sendmsg of both with the GIL HELD — a
 * loopback or LAN socket's send buffer takes a scheduler-extender answer
 * whole, for the cost of the copy into the kernel — and only for what it
 * did not take (a partial send, EAGAIN) the GIL released ONCE, poll(POLLOUT)
 * + sendmsg looped in C until the last byte is out or timeout_s (for the
 * whole answer, as sock.sendall's; < 0 waits for ever) has run out, and
 * the GIL taken back once.  sock.sendall under a time-out releases it for a
 * poll and for each send, and each release waits up to a switch interval
 * to win it back from whatever Python thread runs.  sends counts the
 * kernel's sends that took bytes, released is 0 where the answer went whole
 * with the GIL held, else 1.  A time-out raises TimeoutError, a peer that
 * went (EPIPE, ECONNRESET) or any other error OSError, as sock.sendall
 * does; nothing raises SIGPIPE. */
static PyObject *wirec_send_answer(PyObject *self, PyObject *args) {
    int fd;
    Py_buffer head, body;
    double timeout_s;
    if (!PyArg_ParseTuple(args, "iy*y*d", &fd, &head, &body, &timeout_s))
        return NULL;
    if (fd < 0) {
        PyBuffer_Release(&head);
        PyBuffer_Release(&body);
        errno = EBADF;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    size_t total = (size_t)head.len + (size_t)body.len, sent = 0;
    long sends = 0;
    int released = 0, err = 0, timed_out = 0;

    while (sent < total) {  /* with the GIL held: no wait, no release */
        ssize_t put = send_rest(fd, &head, &body, sent);
        if (put >= 0) {
            sent += (size_t)put;
            sends++;
            break;
        }
        if (errno == EINTR) continue;
        if (errno != EAGAIN && errno != EWOULDBLOCK) err = errno;
        break;
    }
    if (sent < total && !err) {
        released = 1;
        /* the buffers stay exported (and a bytearray unresizable) until
         * they are released below, with the GIL held again */
        Py_BEGIN_ALLOW_THREADS
        double deadline = timeout_s >= 0 ? monotonic_now() + timeout_s : 0.0;
        while (sent < total) {
            int ready = wait_ready(fd, POLLOUT, timeout_s, deadline);
            if (ready < 0) {
                err = errno;
                break;
            }
            if (ready == 0) {
                timed_out = 1;
                break;
            }
            ssize_t put = send_rest(fd, &head, &body, sent);
            if (put >= 0) {
                sent += (size_t)put;
                sends++;
                continue;
            }
            if (errno == EAGAIN || errno == EWOULDBLOCK || errno == EINTR) continue;
            err = errno;
            break;
        }
        Py_END_ALLOW_THREADS
    }
    PyBuffer_Release(&head);
    PyBuffer_Release(&body);

    if (timed_out) {
        PyErr_SetString(PyExc_TimeoutError, "timed out");
        return NULL;
    }
    if (err) {
        errno = err;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    return Py_BuildValue("(li)", sends, released);
}

/* ------------------------------------------------------------------ */

static PyMethodDef wirec_methods[] = {
    {"parse_prioritize", wirec_parse_prioritize, METH_O,
     "Strict zero-copy scan of a scheduler-extender Args body."},
    {"build_table", wirec_build_table, METH_O,
     "Build a name->row table + response fragments for one state version."},
    {"select_encode", wirec_select_encode, METH_VARARGS,
     "Assemble the Prioritize response bytes from a parsed body, a name "
     "table, and the global rank order (optional planned row promotion)."},
    {"filter_encode", wirec_filter_encode, METH_VARARGS,
     "Assemble the NodeNames-mode FilterResult response from a parsed "
     "body, a name table, a per-row violation bitmask, and optional "
     "per-row pre-encoded reason bytes and default reason bytes; returns "
     "(bytes, n_failed)."},
    {"candidate_rows", wirec_candidate_rows, METH_VARARGS,
     "The table row of every NodeNames candidate as int32 bytes (-1 = "
     "absent), or None for a name that is empty, holds a space or escapes."},
    {"filter_encode_nodes", wirec_filter_encode_nodes, METH_VARARGS,
     "filter_encode for a request that carried Nodes: the passing items "
     "echoed as slices of the request's bytes; (bytes, n_failed), or None "
     "where a candidate's name is empty or holds a space."},
    {"filter_respond", wirec_filter_respond, METH_VARARGS,
     "filter_encode over an interned Universe: cached row map, zero "
     "hashing; returns (bytes, n_failed)."},
    {"select_encode_universe", wirec_select_encode_universe, METH_VARARGS,
     "select_encode over an interned Universe: candidate mask from the "
     "cached row map instead of per-name hash lookups."},
    {"recv_stamped", wirec_recv_stamped, METH_VARARGS,
     "recv_stamped(fd, max_bytes, timeout_s) -> (bytes, t_ready, t_held): "
     "poll + recv with the GIL released; CLOCK_MONOTONIC seconds when the "
     "bytes were there and when the interpreter was held again."},
    {"recv_body", wirec_recv_body, METH_VARARGS,
     "recv_body(fd, prefix, length, timeout_s) -> (bytes, t_ready, t_held, "
     "n_recv): prefix + the bytes still missing of a body of length, read "
     "in place under one release of the GIL; timeout_s without a byte."},
    {"send_answer", wirec_send_answer, METH_VARARGS,
     "send_answer(fd, head, body, timeout_s) -> (sends, released): head + "
     "body in one sendmsg with the GIL held; only what the kernel did not "
     "take waits (poll + send) under one release; timeout_s for the whole."},
    {NULL},
};

static struct PyModuleDef wirec_module = {
    PyModuleDef_HEAD_INIT, "_wirec",
    "Native wire-protocol fast path for the TPU scheduler extender.",
    -1, wirec_methods,
    NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC PyInit__wirec(void) {
    if (PyType_Ready(&ParsedArgs_Type) < 0) return NULL;
    if (PyType_Ready(&NameTable_Type) < 0) return NULL;
    if (PyType_Ready(&Universe_Type) < 0) return NULL;
    if (PyType_Ready(&UniverseCache_Type) < 0) return NULL;
    PyObject *mod = PyModule_Create(&wirec_module);
    if (!mod) return NULL;
    Py_INCREF(&UniverseCache_Type);
    if (PyModule_AddObject(mod, "UniverseCache",
                           (PyObject *)&UniverseCache_Type) < 0) {
        Py_DECREF(&UniverseCache_Type);
        Py_DECREF(mod);
        return NULL;
    }
    return mod;
}
