/* applycore: the native leg of the close's two passes over a set
 * (ledger/manager.py _apply_transactions and _process_fees_seq_nums, via
 * tx/history.transaction_rows and fee_rows): the set's history rows in
 * one native call.
 *
 * Two entry points over one body (encode_rows):
 *
 *   encode_history_rows(items) -> list
 *     items: sequence of (txid, body, result, meta) bytes 4-tuples
 *     returns [(txid_hex, body_b64, result_b64, meta_b64) str 4-tuples]
 *
 *   encode_fee_rows(ledger_seq, items) -> list
 *     items: sequence of (index, txid, changes), the last two bytes
 *     returns the rows of txfeehistory,
 *     [(txid_hex, ledger_seq, index, changes_b64)]
 *
 * The per-tx history row encode (hex + 3x base64; hex + 1x for a fee
 * row) is the dominant residual Python cost of the apply tail once the
 * stores are buffered.  This leg gathers all input pointers under the
 * GIL, then releases it for the whole batch encode, so the close's other
 * threads (bucket merges, the verify pipeline) run meanwhile.
 *
 * Encoding contract matches tx/history.py exactly: lowercase hex for
 * the txid, standard base64 alphabet WITH '=' padding for the blobs.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <stdint.h>
#include <stdlib.h>
#include <string.h>

static const char HEX[] = "0123456789abcdef";
static const char B64[] =
    "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/";

static size_t b64_len(size_t n) { return 4 * ((n + 2) / 3); }

static void hex_encode(const uint8_t *src, size_t n, char *dst) {
    for (size_t i = 0; i < n; i++) {
        dst[2 * i] = HEX[src[i] >> 4];
        dst[2 * i + 1] = HEX[src[i] & 0xf];
    }
}

static void b64_encode(const uint8_t *src, size_t n, char *dst) {
    size_t i = 0, o = 0;
    while (i + 3 <= n) {
        uint32_t v = ((uint32_t)src[i] << 16) | ((uint32_t)src[i + 1] << 8) |
                     src[i + 2];
        dst[o++] = B64[(v >> 18) & 63];
        dst[o++] = B64[(v >> 12) & 63];
        dst[o++] = B64[(v >> 6) & 63];
        dst[o++] = B64[v & 63];
        i += 3;
    }
    if (i + 1 == n) {
        uint32_t v = (uint32_t)src[i] << 16;
        dst[o++] = B64[(v >> 18) & 63];
        dst[o++] = B64[(v >> 12) & 63];
        dst[o++] = '=';
        dst[o++] = '=';
    } else if (i + 2 == n) {
        uint32_t v = ((uint32_t)src[i] << 16) | ((uint32_t)src[i + 1] << 8);
        dst[o++] = B64[(v >> 18) & 63];
        dst[o++] = B64[(v >> 12) & 63];
        dst[o++] = B64[(v >> 6) & 63];
        dst[o++] = '=';
    }
}

/* Rows of `width` bytes fields, the first to hex and the rest to base64.
 * With `seq` an item carries its index ahead of them and comes out as the
 * table's row, (hex, seq, index, base64...); without, as the strings. */
static PyObject *encode_rows(PyObject *arg, Py_ssize_t width, PyObject *seq) {
    Py_ssize_t lead = seq != NULL ? 1 : 0;
    PyObject *fast = PySequence_Fast(arg, "expected a sequence of rows");
    if (fast == NULL)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);

    /* gather pointers + lengths under the GIL (borrowed views into the
     * bytes objects, kept alive by `fast` holding the tuples) */
    const uint8_t **ptrs = NULL;
    size_t *lens = NULL, *offs = NULL;
    char *slab = NULL;
    PyObject *out = NULL;
    size_t nfields = (size_t)n * (size_t)width;

    if (n > 0) {
        ptrs = malloc(nfields * sizeof(*ptrs));
        lens = malloc(nfields * sizeof(*lens));
        offs = malloc((nfields + 1) * sizeof(*offs));
        if (ptrs == NULL || lens == NULL || offs == NULL) {
            PyErr_NoMemory();
            goto done;
        }
    }
    size_t total = 0;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *item = PySequence_Fast_GET_ITEM(fast, i);
        if (!PyTuple_Check(item) || PyTuple_GET_SIZE(item) != lead + width) {
            PyErr_Format(PyExc_TypeError,
                         "each item must be a tuple of %zd bytes fields, "
                         "the txid first%s",
                         width, lead ? ", behind its index" : "");
            goto done;
        }
        for (Py_ssize_t f = 0; f < width; f++) {
            char *buf;
            Py_ssize_t blen;
            if (PyBytes_AsStringAndSize(PyTuple_GET_ITEM(item, lead + f),
                                        &buf, &blen) < 0)
                goto done;
            size_t slot = (size_t)(i * width + f);
            ptrs[slot] = (const uint8_t *)buf;
            lens[slot] = (size_t)blen;
            offs[slot] = total;
            total += (f == 0) ? 2 * (size_t)blen : b64_len((size_t)blen);
        }
    }
    if (n > 0) {
        offs[nfields] = total;
        slab = malloc(total ? total : 1);
        if (slab == NULL) {
            PyErr_NoMemory();
            goto done;
        }
        Py_BEGIN_ALLOW_THREADS
        for (size_t slot = 0; slot < nfields; slot++) {
            if (slot % (size_t)width == 0)
                hex_encode(ptrs[slot], lens[slot], slab + offs[slot]);
            else
                b64_encode(ptrs[slot], lens[slot], slab + offs[slot]);
        }
        Py_END_ALLOW_THREADS
    }

    out = PyList_New(n);
    if (out == NULL)
        goto done;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *row = PyTuple_New(width + 2 * lead);
        if (row == NULL) {
            Py_CLEAR(out);
            goto done;
        }
        if (lead) {
            PyObject *index =
                PyTuple_GET_ITEM(PySequence_Fast_GET_ITEM(fast, i), 0);
            Py_INCREF(seq);
            PyTuple_SET_ITEM(row, 1, seq);
            Py_INCREF(index);
            PyTuple_SET_ITEM(row, 2, index);
        }
        for (Py_ssize_t f = 0; f < width; f++) {
            size_t slot = (size_t)(i * width + f);
            PyObject *s = PyUnicode_FromStringAndSize(
                slab + offs[slot], (Py_ssize_t)(offs[slot + 1] - offs[slot]));
            if (s == NULL) {
                Py_DECREF(row);
                Py_CLEAR(out);
                goto done;
            }
            /* the hex leads the row; seq and index sit behind it */
            PyTuple_SET_ITEM(row, f ? f + 2 * lead : 0, s);
        }
        PyList_SET_ITEM(out, i, row);
    }

done:
    free(slab);
    free(ptrs);
    free(lens);
    free(offs);
    Py_DECREF(fast);
    return out;
}

static PyObject *encode_history_rows(PyObject *self, PyObject *arg) {
    (void)self;
    return encode_rows(arg, 4, NULL);
}

static PyObject *encode_fee_rows(PyObject *self, PyObject *args) {
    (void)self;
    PyObject *seq, *items;
    if (!PyArg_ParseTuple(args, "OO", &seq, &items))
        return NULL;
    return encode_rows(items, 2, seq);
}

static PyMethodDef Methods[] = {
    {"encode_history_rows", encode_history_rows, METH_O,
     "Batch-encode (txid, body, result, meta) bytes rows to "
     "(hex, b64, b64, b64) str rows, releasing the GIL."},
    {"encode_fee_rows", encode_fee_rows, METH_VARARGS,
     "encode_fee_rows(ledger_seq, items): (index, txid, changes) items to "
     "(hex, ledger_seq, index, b64) rows, releasing the GIL."},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_applycore",
    "The close's history rows, a set in one GIL-released call.", -1,
    Methods, NULL, NULL, NULL, NULL,
};

PyMODINIT_FUNC PyInit__applycore(void) { return PyModule_Create(&moduledef); }
