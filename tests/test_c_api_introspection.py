"""C ABI introspection tier: GetInternals / GetOutput / InferType /
SaveToFile / monitor callback / RandomSeed / NotifyShutdown.

Reference parity: this is the tier the reference's own binding generators
sit on — ``MXSymbolGetInternals`` powers feature extraction and
shared-module bucketing (reference include/mxnet/c_api.h:898,
python/mxnet/symbol.py get_internals callers), ``MXSymbolInferType``
(:1055) backs type checking, and ``MXExecutorSetMonitorCallback`` (:1269)
backs python/mxnet/monitor.py. A pure-C client binds an INTERNAL layer
output via GetInternals and installs a monitor; both are matched against
the Python framework.
"""

import os
import subprocess
import sysconfig

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_C_CLIENT = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "mxtpu.h"

#define CHK(x) if ((x) != 0) { \
  fprintf(stderr, "FAIL %s: %s\n", #x, MXGetLastError()); return 1; }

static int n_monitor_calls = 0;
static void monitor_cb(const char* name, NDArrayHandle arr, void* h) {
  uint32_t ndim;
  const uint32_t* shape;
  if (MXNDArrayGetShape(arr, &ndim, &shape) == 0 && ndim > 0)
    n_monitor_calls += 1;
  (void)name; (void)h;
}

int main(int argc, char** argv) {
  const char* sym_file = argv[1];
  const char* param_file = argv[2];
  const char* resave_file = argv[3];

  SymbolHandle sym;
  CHK(MXSymbolCreateFromFile(sym_file, &sym));

  /* --- introspect the internal graph ------------------------------- */
  SymbolHandle internals;
  CHK(MXSymbolGetInternals(sym, &internals));
  uint32_t n_int, n_out;
  const char** int_names;
  CHK(MXSymbolListOutputs(internals, &n_int, &int_names));
  CHK(MXSymbolGetNumOutputs(sym, &n_out));
  if (n_out != 1) { fprintf(stderr, "top outputs %u\n", n_out); return 1; }
  /* pick the first fully-connected output as the feature layer */
  int feat_idx = -1;
  for (uint32_t i = 0; i < n_int; ++i)
    if (strstr(int_names[i], "fc1_output")) { feat_idx = (int)i; break; }
  if (feat_idx < 0) { fprintf(stderr, "fc1_output not found\n"); return 1; }
  SymbolHandle feat;
  CHK(MXSymbolGetOutput(internals, (uint32_t)feat_idx, &feat));

  /* --- infer types over the feature subgraph ----------------------- */
  uint32_t n_args;
  const char** arg_names;
  CHK(MXSymbolListArguments(feat, &n_args, &arg_names));
  const char* tkeys[1] = {"data"};
  int tdata[1] = {0}; /* float32 */
  uint32_t in_ts, out_ts, aux_ts;
  const int *in_t, *out_t, *aux_t;
  int complete;
  CHK(MXSymbolInferType(feat, 1, tkeys, tdata, &in_ts, &in_t,
                        &out_ts, &out_t, &aux_ts, &aux_t, &complete));
  if (!complete || out_ts != 1 || out_t[0] != 0) {
    fprintf(stderr, "infer_type: complete=%d out_ts=%u t=%d\n",
            complete, out_ts, out_ts ? out_t[0] : -1);
    return 1;
  }

  /* --- save the feature symbol back to a file (roundtrip) ---------- */
  CHK(MXSymbolSaveToFile(feat, resave_file));

  /* --- bind executors with checkpoint weights ---------------------- */
  uint32_t n_params;
  const char** keys;
  NDArrayHandle* params;
  CHK(MXNDArrayLoad(param_file, &n_params, &params, &n_params, &keys));
  uint32_t dshape[2] = {4, 16};
  NDArrayHandle data_nd;
  CHK(MXNDArrayCreate(dshape, 2, 1, 0, 0, &data_nd));
  {
    float buf[64];
    for (int j = 0; j < 64; ++j) buf[j] = (float)(j % 13) / 13.0f;
    CHK(MXNDArraySyncCopyFromCPU(data_nd, buf, 64));
  }
  uint32_t lshape[1] = {4};
  NDArrayHandle label_nd;
  CHK(MXNDArrayCreate(lshape, 1, 1, 0, 0, &label_nd));
  {
    float lbuf[4] = {0, 1, 2, 3};
    CHK(MXNDArraySyncCopyFromCPU(label_nd, lbuf, 4));
  }

  /* fill an in_args list for an arbitrary symbol by argument name */
#define FILL_ARGS(SYMH, OUT_N, OUT_ARR)                                   \
  do {                                                                     \
    CHK(MXSymbolListArguments(SYMH, &(OUT_N), &arg_names));                \
    (OUT_ARR) = malloc((OUT_N) * sizeof(NDArrayHandle));                   \
    for (uint32_t i = 0; i < (OUT_N); ++i) {                               \
      if (strcmp(arg_names[i], "data") == 0) {                             \
        (OUT_ARR)[i] = data_nd;                                            \
      } else if (strstr(arg_names[i], "label")) {                          \
        (OUT_ARR)[i] = label_nd;                                           \
      } else {                                                             \
        (OUT_ARR)[i] = NULL;                                               \
        for (uint32_t k = 0; k < n_params; ++k) {                          \
          const char* kn = keys[k];                                        \
          const char* col = strchr(kn, ':');                               \
          if (col) kn = col + 1;                                           \
          if (strcmp(kn, arg_names[i]) == 0) {                             \
            (OUT_ARR)[i] = params[k];                                      \
            break;                                                         \
          }                                                                \
        }                                                                  \
        if (!(OUT_ARR)[i]) {                                               \
          fprintf(stderr, "missing param %s\n", arg_names[i]);             \
          return 1;                                                        \
        }                                                                  \
      }                                                                    \
    }                                                                      \
  } while (0)

  uint32_t n_full;
  NDArrayHandle* full_args;
  FILL_ARGS(sym, n_full, full_args);
  ExecutorHandle exe;
  CHK(MXExecutorBind(sym, 1, 0, n_full, full_args, NULL, NULL, 0, NULL,
                     &exe));
  /* full-graph executor monitors every op output */
  CHK(MXExecutorSetMonitorCallbackEX(exe, monitor_cb, NULL, 1));
  CHK(MXExecutorForward(exe, 0));
  uint32_t n_eo;
  NDArrayHandle* eouts;
  CHK(MXExecutorOutputs(exe, &n_eo, &eouts));
  if (n_monitor_calls < 3) {
    fprintf(stderr, "monitor saw %d values\n", n_monitor_calls);
    return 1;
  }
  /* uninstall, run the FEATURE executor, print its output */
  CHK(MXExecutorSetMonitorCallback(exe, NULL, NULL));

  uint32_t n_feat;
  NDArrayHandle* feat_args;
  FILL_ARGS(feat, n_feat, feat_args);
  ExecutorHandle fexe;
  CHK(MXExecutorBind(feat, 1, 0, n_feat, feat_args, NULL, NULL, 0, NULL,
                     &fexe));
  CHK(MXExecutorForward(fexe, 0));
  CHK(MXExecutorOutputs(fexe, &n_eo, &eouts));
  if (n_eo != 1) { fprintf(stderr, "feat outputs %u\n", n_eo); return 1; }
  uint32_t ndim;
  const uint32_t* oshape;
  CHK(MXNDArrayGetShape(eouts[0], &ndim, &oshape));
  uint32_t total = 1;
  for (uint32_t i = 0; i < ndim; ++i) total *= oshape[i];
  float* out = malloc(total * sizeof(float));
  CHK(MXNDArraySyncCopyToCPU(eouts[0], out, total));
  for (uint32_t i = 0; i < total; ++i) printf("%.6f\n", out[i]);

  CHK(MXRandomSeed(1234));
  CHK(MXExecutorFree(exe));
  CHK(MXExecutorFree(fexe));
  CHK(MXSymbolFree(feat));
  CHK(MXSymbolFree(internals));
  CHK(MXSymbolFree(sym));
  CHK(MXNotifyShutdown());
  return 0;
}
"""


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=5, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


@pytest.fixture(scope="module")
def amalgamated(tmp_path_factory):
    out_dir = str(tmp_path_factory.mktemp("amal"))
    env = dict(os.environ)  # conftest pinned JAX_PLATFORMS=cpu for children
    r = subprocess.run(
        ["python", os.path.join(_ROOT, "tools", "amalgamation.py"),
         "--out-dir", out_dir],
        capture_output=True, text=True, cwd=_ROOT, env=env,
    )
    assert r.returncode == 0, r.stderr
    return out_dir


def test_c_introspection_tier(amalgamated, tmp_path):
    sym = _mlp()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 16))],
             label_shapes=[("softmax_label", (4,))])
    mx.random.seed(11)
    mod.init_params(initializer=mx.init.Xavier())
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 0)

    csrc = str(tmp_path / "client.c")
    with open(csrc, "w") as f:
        f.write(_C_CLIENT)
    client = str(tmp_path / "client")
    libdir = sysconfig.get_config_var("LIBDIR")
    r = subprocess.run(
        ["gcc", "-std=c99", "-O2", csrc, "-o", client,
         f"-I{amalgamated}", os.path.join(amalgamated, "libmxtpu.so"),
         f"-Wl,-rpath,{amalgamated}", f"-Wl,-rpath,{libdir}"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stderr

    resave = str(tmp_path / "feat-symbol.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [client, prefix + "-symbol.json", prefix + "-0000.params", resave],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr + r.stdout
    got = np.array([float(x) for x in r.stdout.split()], np.float32)

    # oracle: the same internal-feature forward through the Python API
    feat = sym.get_internals()["fc1_output"]
    x = (np.arange(4 * 16, dtype=np.float32) % 13 / 13.0).reshape(4, 16)
    arg_params, aux_params = mod.get_params()
    fmod = mx.mod.Module(feat, context=mx.cpu(), label_names=None)
    fmod.bind(data_shapes=[("data", (4, 16))])
    feat_args = set(feat.list_arguments())
    fmod.set_params({k: v for k, v in arg_params.items() if k in feat_args},
                    aux_params, allow_missing=False)
    fmod.forward(mx.io.DataBatch([mx.nd.array(x)], []), is_train=False)
    expect = fmod.get_outputs()[0].asnumpy().ravel()
    assert got.shape == expect.shape
    assert_almost_equal(got, expect, rtol=1e-4, atol=1e-5)

    # the C-resaved feature symbol loads back and matches structurally
    feat2 = mx.sym.load(resave)
    assert feat2.list_outputs() == feat.list_outputs()
    assert feat2.list_arguments() == feat.list_arguments()


def test_python_side_introspection_capi():
    """The capi layer itself (what the C shims call) behaves."""
    from mxnet_tpu import capi

    sym = _mlp()
    internals = capi.sym_get_internals(sym)
    outs = internals.list_outputs()
    assert "fc1_output" in outs
    assert capi.sym_num_outputs(sym) == 1
    one = capi.sym_get_output(internals, outs.index("fc1_output"))
    assert one.list_outputs() == ["fc1_output"]
    arg_t, out_t, aux_t, complete = capi.sym_infer_type(
        sym, ["data"], [0])
    assert complete == 1 and out_t == [0]
    # unknown dtypes: incomplete inference reports complete=0, not a crash
    arg_t2, out_t2, aux_t2, c2 = capi.sym_infer_type(sym, [], [])
    assert c2 in (0, 1)
    capi.random_seed(77)
    capi.notify_shutdown()


def test_cached_op_tier(tmp_path):
    """MXCachedCreateOp/Invoke/CreateSymbol/Free (reference c_api.h:648):
    pre-parsed op handles invoke like MXImperativeInvoke and build symbol
    nodes, matched against the python imperative path."""
    import ctypes
    import subprocess

    out_dir = str(tmp_path / "amal")
    env = dict(os.environ)
    r = subprocess.run(
        ["python", os.path.join(_ROOT, "tools", "amalgamation.py"),
         "--out-dir", out_dir],
        capture_output=True, text=True, cwd=_ROOT, env=env,
    )
    assert r.returncode == 0, r.stderr
    L = ctypes.CDLL(os.path.join(out_dir, "libmxtpu.so"))
    L.MXGetLastError.restype = ctypes.c_char_p

    # find the 'transpose' creator
    n = ctypes.c_uint32()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    assert L.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(creators)) == 0
    name = ctypes.c_char_p()
    transpose_creator = None
    for i in range(n.value):
        c = ctypes.c_void_p(creators[i])
        assert L.MXSymbolGetAtomicSymbolName(c, ctypes.byref(name)) == 0
        if name.value == b"transpose":
            transpose_creator = c
    assert transpose_creator is not None

    cop = ctypes.c_void_p()
    keys = (ctypes.c_char_p * 1)(b"axes")
    vals = (ctypes.c_char_p * 1)(b"(1, 0)")
    assert L.MXCachedCreateOp(transpose_creator, 1, 1, keys, vals,
                              ctypes.byref(cop)) == 0, L.MXGetLastError()

    # invoke on a real array; compare vs numpy transpose
    shape = (ctypes.c_uint32 * 2)(2, 3)
    nd = ctypes.c_void_p()
    assert L.MXNDArrayCreate(shape, 2, 1, 0, 0, ctypes.byref(nd)) == 0
    buf = (ctypes.c_float * 6)(*range(6))
    assert L.MXNDArraySyncCopyFromCPU(nd, buf, 6) == 0
    n_out = ctypes.c_int(0)
    outs = ctypes.POINTER(ctypes.c_void_p)()
    ins = (ctypes.c_void_p * 1)(nd.value)
    assert L.MXCachedInvoke(cop, 1, ins, ctypes.byref(n_out),
                            ctypes.byref(outs)) == 0, L.MXGetLastError()
    assert n_out.value == 1
    got = (ctypes.c_float * 6)()
    out_h = ctypes.c_void_p(outs[0])
    assert L.MXNDArraySyncCopyToCPU(out_h, got, 6) == 0
    np.testing.assert_allclose(
        np.array(got).reshape(3, 2),
        np.arange(6, dtype=np.float32).reshape(2, 3).T)

    # symbol construction from the cached op
    var = ctypes.c_void_p()
    assert L.MXSymbolCreateVariable(b"x", ctypes.byref(var)) == 0
    args = (ctypes.c_void_p * 1)(var.value)
    sym = ctypes.c_void_p()
    assert L.MXCachedCreateSymbol(cop, b"t0", 1, args,
                                  ctypes.byref(sym)) == 0, L.MXGetLastError()
    n_args = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    assert L.MXSymbolListArguments(sym, ctypes.byref(n_args),
                                   ctypes.byref(arr)) == 0
    assert n_args.value == 1 and arr[0] == b"x"

    # error paths: bad creator + freed handle
    bad = ctypes.c_void_p()
    assert L.MXCachedCreateOp(ctypes.c_void_p(10**9), 0, 0, None, None,
                              ctypes.byref(bad)) == -1
    assert L.MXCachedFree(cop) == 0
    assert L.MXCachedInvoke(cop, 1, ins, ctypes.byref(n_out),
                            ctypes.byref(outs)) == -1
    assert L.MXNDArrayFree(nd) == 0
    assert L.MXNDArrayFree(out_h) == 0
    assert L.MXSymbolFree(var) == 0
    assert L.MXSymbolFree(sym) == 0


def test_atomic_symbol_info_and_recordio_cursor(amalgamated, tmp_path):
    """ROADMAP 5b slice: MXSymbolGetAtomicSymbolInfo (op parameter schema
    — the tier binding generators sit on) and the RecordIO byte cursor
    (MXRecordIOWriterTell / MXRecordIOReaderSeek — what .idx sidecars
    store), round-tripped through the amalgamated C library."""
    import ctypes

    L = ctypes.CDLL(os.path.join(amalgamated, "libmxtpu.so"))
    L.MXGetLastError.restype = ctypes.c_char_p

    n = ctypes.c_uint32()
    creators = ctypes.POINTER(ctypes.c_void_p)()
    assert L.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(creators)) == 0
    name = ctypes.c_char_p()
    by_name = {}
    for i in range(n.value):
        c = ctypes.c_void_p(creators[i])
        assert L.MXSymbolGetAtomicSymbolName(c, ctypes.byref(name)) == 0
        by_name[name.value] = c

    desc = ctypes.c_char_p()
    kv = ctypes.c_char_p()
    ret = ctypes.c_char_p()
    n_args = ctypes.c_uint32()
    anames = ctypes.POINTER(ctypes.c_char_p)()
    atypes = ctypes.POINTER(ctypes.c_char_p)()
    adescs = ctypes.POINTER(ctypes.c_char_p)()

    def info(creator):
        rc = L.MXSymbolGetAtomicSymbolInfo(
            creator, ctypes.byref(name), ctypes.byref(desc),
            ctypes.byref(n_args), ctypes.byref(anames),
            ctypes.byref(atypes), ctypes.byref(adescs),
            ctypes.byref(kv), ctypes.byref(ret))
        assert rc == 0, L.MXGetLastError()
        return {anames[i]: atypes[i] for i in range(n_args.value)}

    # the parameter SCHEMA comes back (dmlc::Parameter fields, not tensor
    # inputs): names, reference-style type strings, required/default split
    params = info(by_name[b"FullyConnected"])
    assert name.value == b"FullyConnected"
    assert params[b"num_hidden"] == b"int, required"
    assert params[b"no_bias"] == b"boolean, optional, default=False"
    assert b"data" not in params and b"weight" not in params
    assert kv.value == b""

    # variadic ops advertise their key_var_num_args (the field the
    # reference's wrapper generators key variadic call syntax on)
    info(by_name[b"Concat"])
    assert kv.value == b"num_args"

    # error contract: bad creator is -1 + message, never a crash
    assert L.MXSymbolGetAtomicSymbolInfo(
        ctypes.c_void_p(10**9), ctypes.byref(name), ctypes.byref(desc),
        ctypes.byref(n_args), ctypes.byref(anames), ctypes.byref(atypes),
        ctypes.byref(adescs), ctypes.byref(kv), ctypes.byref(ret)) == -1
    assert b"AtomicSymbolCreator" in L.MXGetLastError()

    # -- RecordIO cursor: tell on write marks a boundary seek returns to
    rec = str(tmp_path / "cursor.rec").encode()
    w = ctypes.c_void_p()
    assert L.MXRecordIOWriterCreate(rec, ctypes.byref(w)) == 0
    pos = ctypes.c_size_t()
    assert L.MXRecordIOWriterTell(w, ctypes.byref(pos)) == 0
    assert pos.value == 0
    assert L.MXRecordIOWriterWriteRecord(w, b"first", 5) == 0
    assert L.MXRecordIOWriterTell(w, ctypes.byref(pos)) == 0
    second_at = pos.value
    assert second_at > 0
    assert L.MXRecordIOWriterWriteRecord(w, b"second-rec", 10) == 0
    assert L.MXRecordIOWriterFree(w) == 0

    r = ctypes.c_void_p()
    assert L.MXRecordIOReaderCreate(rec, ctypes.byref(r)) == 0
    buf = ctypes.c_char_p()
    sz = ctypes.c_size_t()
    # skip straight to the second record via the captured offset
    assert L.MXRecordIOReaderSeek(r, ctypes.c_size_t(second_at)) == 0
    assert L.MXRecordIOReaderReadRecord(
        r, ctypes.byref(buf), ctypes.byref(sz)) == 0
    assert ctypes.string_at(buf, sz.value) == b"second-rec"
    # rewind to 0: the stream replays from the first record
    assert L.MXRecordIOReaderSeek(r, ctypes.c_size_t(0)) == 0
    assert L.MXRecordIOReaderReadRecord(
        r, ctypes.byref(buf), ctypes.byref(sz)) == 0
    assert ctypes.string_at(buf, sz.value) == b"first"
    assert L.MXRecordIOReaderFree(r) == 0
