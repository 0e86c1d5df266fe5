"""C ABI error-path contract: every abuse returns -1 with MXGetLastError
set — never a crash.

Reference contract: ``c_api_common.h`` API_BEGIN/API_END wraps every entry
point so errors surface as -1 + thread-local error string
(``include/mxnet/c_api.h:35-60`` docs). The TPU shim adds a live-handle
registry (``capi_common.h handle_reg/handle_live``) because its handles
are PyObject carriers: dereferencing a freed or garbage handle would
corrupt the embedded interpreter rather than segfault cleanly.

Runs IN-PROCESS via ctypes against the amalgamated libmxtpu.so — the
embedded-interpreter bootstrap detects the live interpreter, so a crash
here fails the suite loudly.
"""

import ctypes
import os
import subprocess

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lib(tmp_path_factory):
    out_dir = str(tmp_path_factory.mktemp("amal_abuse"))
    env = dict(os.environ)  # conftest pinned JAX_PLATFORMS=cpu for children
    r = subprocess.run(
        ["python", os.path.join(_ROOT, "tools", "amalgamation.py"),
         "--out-dir", out_dir],
        capture_output=True, text=True, cwd=_ROOT, env=env,
    )
    assert r.returncode == 0, r.stderr
    L = ctypes.CDLL(os.path.join(out_dir, "libmxtpu.so"))
    L.MXGetLastError.restype = ctypes.c_char_p
    return L


def expect_fail(lib, fn, *args):
    rc = fn(*args)
    assert rc == -1, f"{fn.__name__ if hasattr(fn, '__name__') else fn}: " \
                     f"expected -1, got {rc}"
    err = lib.MXGetLastError()
    assert err, "error string empty after failure"
    return err.decode()


def _make_nd(lib):
    shape = (ctypes.c_uint32 * 2)(2, 3)
    h = ctypes.c_void_p()
    assert lib.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(h)) == 0
    return h


def _make_sym(lib):
    import mxnet_tpu as mx

    d = mx.sym.Variable("data")
    s = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
    js = s.tojson().encode()
    h = ctypes.c_void_p()
    assert lib.MXSymbolCreateFromJSON(js, ctypes.byref(h)) == 0
    return h


def test_freed_ndarray_handle_rejected(lib):
    h = _make_nd(lib)
    assert lib.MXNDArrayFree(h) == 0
    ndim = ctypes.c_uint32()
    pdata = ctypes.POINTER(ctypes.c_uint32)()
    msg = expect_fail(lib, lib.MXNDArrayGetShape, h, ctypes.byref(ndim),
                      ctypes.byref(pdata))
    assert "handle" in msg
    expect_fail(lib, lib.MXNDArrayFree, h)  # double free
    buf = (ctypes.c_float * 6)()
    expect_fail(lib, lib.MXNDArraySyncCopyToCPU, h, buf, 6)


def test_garbage_and_null_handles_rejected(lib):
    garbage = ctypes.c_void_p(0xDEADBEF0)
    ndim = ctypes.c_uint32()
    pdata = ctypes.POINTER(ctypes.c_uint32)()
    expect_fail(lib, lib.MXNDArrayGetShape, garbage, ctypes.byref(ndim),
                ctypes.byref(pdata))
    expect_fail(lib, lib.MXNDArrayGetShape, None, ctypes.byref(ndim),
                ctypes.byref(pdata))
    expect_fail(lib, lib.MXExecutorForward, garbage, 0)
    expect_fail(lib, lib.MXSymbolFree, garbage)
    expect_fail(lib, lib.MXKVStoreFree, None)
    expect_fail(lib, lib.MXDataIterFree, garbage)
    expect_fail(lib, lib.MXPredFree, garbage)
    expect_fail(lib, lib.MXNDListFree, garbage)


def test_wrong_handle_type_returns_error(lib):
    """A live handle of the WRONG kind fails in the adapter (python-side
    type mismatch), still -1 + message, not corruption."""
    nd = _make_nd(lib)
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    expect_fail(lib, lib.MXSymbolListArguments, nd, ctypes.byref(n),
                ctypes.byref(arr))
    sym = _make_sym(lib)
    ndim = ctypes.c_uint32()
    pdata = ctypes.POINTER(ctypes.c_uint32)()
    expect_fail(lib, lib.MXNDArrayGetShape, sym, ctypes.byref(ndim),
                ctypes.byref(pdata))
    assert lib.MXNDArrayFree(nd) == 0
    assert lib.MXSymbolFree(sym) == 0


def test_null_out_pointers_rejected(lib):
    expect_fail(lib, lib.MXNDArrayCreateNone, None)
    expect_fail(lib, lib.MXSymbolCreateFromJSON, b"{}", None)
    expect_fail(lib, lib.MXListAllOpNames, None, None)
    nd = _make_nd(lib)
    expect_fail(lib, lib.MXNDArrayGetShape, nd, None, None)
    expect_fail(lib, lib.MXNDArrayGetDType, nd, None)
    assert lib.MXNDArrayFree(nd) == 0


def test_bad_inputs_return_errors(lib):
    h = ctypes.c_void_p()
    expect_fail(lib, lib.MXSymbolCreateFromJSON, b"not json at all",
                ctypes.byref(h))
    expect_fail(lib, lib.MXKVStoreCreate, b"no_such_kvstore",
                ctypes.byref(h))
    expect_fail(lib, lib.MXRecordIOReaderCreate, b"/no/such/file.rec",
                ctypes.byref(h))
    n = ctypes.c_uint32()
    keys = ctypes.POINTER(ctypes.c_char_p)()
    arrs = ctypes.POINTER(ctypes.c_void_p)()
    expect_fail(lib, lib.MXNDArrayLoad, b"/no/such/file.params",
                ctypes.byref(n), ctypes.byref(arrs), ctypes.byref(n),
                ctypes.byref(keys))


def test_oversized_shape_rejected(lib):
    # ~4e18 elements: allocation must raise inside the adapter, not abort
    shape = (ctypes.c_uint32 * 4)(2000000000, 2000000000, 1000, 1000)
    h = ctypes.c_void_p()
    expect_fail(lib, lib.MXNDArrayCreate, shape, 4, 1, 0, 0,
                ctypes.byref(h))


def test_symbol_misuse_returns_errors(lib):
    sym = _make_sym(lib)
    out = ctypes.c_void_p()
    expect_fail(lib, lib.MXSymbolGetOutput, sym, 99, ctypes.byref(out))
    # saving to an unwritable path
    expect_fail(lib, lib.MXSymbolSaveToFile, sym, b"/no/such/dir/x.json")
    assert lib.MXSymbolFree(sym) == 0


def test_bad_creator_rejected(lib):
    name = ctypes.c_char_p()
    expect_fail(lib, lib.MXSymbolGetAtomicSymbolName,
                ctypes.c_void_p(10**9), ctypes.byref(name))


def test_error_message_is_per_failure(lib):
    """MXGetLastError reflects the most recent failure."""
    h = ctypes.c_void_p()
    m1 = expect_fail(lib, lib.MXKVStoreCreate, b"bogus_type_a",
                     ctypes.byref(h))
    m2 = expect_fail(lib, lib.MXSymbolCreateFromJSON, b"][",
                     ctypes.byref(h))
    assert m1 != m2


def test_freed_handles_in_arrays_rejected(lib):
    """Handle ARRAYS are validated element-wise (kv push, save, backward)."""
    nd = _make_nd(lib)
    assert lib.MXNDArrayFree(nd) == 0
    arr = (ctypes.c_void_p * 1)(nd.value)
    expect_fail(lib, lib.MXNDArraySave, b"/tmp/x.params", 1, arr, None)
    kv = ctypes.c_void_p()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    keys = (ctypes.c_int * 1)(0)
    expect_fail(lib, lib.MXKVStoreInit, kv, 1, keys, arr)
    expect_fail(lib, lib.MXKVStorePush, kv, 1, keys, arr, 0)
    assert lib.MXKVStoreFree(kv) == 0
    sym_arr = (ctypes.c_void_p * 1)(0xDEADBEF0)
    out = ctypes.c_void_p()
    expect_fail(lib, lib.MXSymbolCreateGroup, 1, sym_arr, ctypes.byref(out))


def test_freed_executor_monitor_rejected(lib):
    CB = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                          ctypes.c_void_p)
    cb = CB(lambda n, a, h: None)
    expect_fail(lib, lib.MXExecutorSetMonitorCallback,
                ctypes.c_void_p(0xDEADBEF0), cb, None)


def test_infer_null_outs_rejected(lib):
    sym = _make_sym(lib)
    ots = ctypes.c_uint32()
    otd = ctypes.POINTER(ctypes.c_int)()
    comp = ctypes.c_int()
    # NULL in/aux out-params must fail cleanly, not be written through
    expect_fail(lib, lib.MXSymbolInferType, sym, 0, None, None,
                None, None, ctypes.byref(ots), ctypes.byref(otd),
                None, None, ctypes.byref(comp))
    assert lib.MXSymbolFree(sym) == 0


def test_cross_kind_handles_rejected(lib):
    """Handles of a DIFFERENT struct layout (predict-plane NDList /
    Predictor vs core Handle) are rejected by kind, not just liveness."""
    nd = _make_nd(lib)
    # a live core handle into predict-plane entry points
    expect_fail(lib, lib.MXPredForward, nd)
    step = ctypes.c_int()
    expect_fail(lib, lib.MXPredPartialForward, nd, 0, ctypes.byref(step))
    expect_fail(lib, lib.MXNDListFree, nd)
    expect_fail(lib, lib.MXPredFree, nd)
    # the core handle is still live and usable afterwards
    ndim = ctypes.c_uint32()
    pdata = ctypes.POINTER(ctypes.c_uint32)()
    assert lib.MXNDArrayGetShape(nd, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0
    assert lib.MXNDArrayFree(nd) == 0


def test_freed_symbol_list_and_iter_getters_rejected(lib):
    sym = _make_sym(lib)
    assert lib.MXSymbolFree(sym) == 0
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    expect_fail(lib, lib.MXSymbolListArguments, sym, ctypes.byref(n),
                ctypes.byref(arr))
    out = ctypes.c_void_p()
    expect_fail(lib, lib.MXDataIterGetData, ctypes.c_void_p(0xDEADBEF0),
                ctypes.byref(out))
    rank = ctypes.c_int()
    expect_fail(lib, lib.MXKVStoreGetRank, ctypes.c_void_p(0xDEADBEF0),
                ctypes.byref(rank))


def test_kvstore_num_dead_node(lib):
    """MXKVStoreGetNumDeadNode: live local store reports 0; freed/garbage
    handles and NULL out reject with -1."""
    kv = ctypes.c_void_p()
    assert lib.MXKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    n = ctypes.c_int(-1)
    assert lib.MXKVStoreGetNumDeadNode(kv, 7, ctypes.byref(n)) == 0
    assert n.value == 0
    expect_fail(lib, lib.MXKVStoreGetNumDeadNode, kv, 7, None)
    assert lib.MXKVStoreFree(kv) == 0
    expect_fail(lib, lib.MXKVStoreGetNumDeadNode, kv, 7, ctypes.byref(n))
