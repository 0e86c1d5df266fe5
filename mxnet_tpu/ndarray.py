"""NDArray — the imperative array type.

Reference: ``include/mxnet/ndarray.h`` + ``python/mxnet/ndarray.py`` (2359
LoC). The reference NDArray is a mutable buffer guarded by an engine variable;
every op pushes an async closure and ``WaitToRead`` blocks on the var queue
(``src/engine/threaded_engine.h:93-195``).

TPU-native design: an NDArray is a thin mutable *handle* over an immutable
``jax.Array``. Mutation (in-place ops, ``__setitem__``, ``out=``) rebinds the
handle to a new functional array — jax's async dispatch plays the role of the
dependency engine (ordering is by data flow; ``wait_to_read`` ≈
``block_until_ready``). Ops are generated from the op registry at import
time, mirroring the reference's codegen from the NNVM registry
(``python/mxnet/ndarray.py:2204-2356``).
"""

from __future__ import annotations

import builtins
import struct
import sys

import numpy as np

from .base import MXNetError, np_dtype
from .context import Context, cpu, current_context
from .ops import registry as _reg
from .ops.registry import OpMode, platform_of
from . import random as _random
from . import telemetry as _telemetry

# every host-blocking device sync in the framework flows through one of
# these two calls; counting them is the observable "no per-batch sync"
# invariant the async pipeline is built on (tests/test_async_pipeline.py)
_SYNC_ASNUMPY = _telemetry.counter("ndarray.asnumpy")
_SYNC_WAIT = _telemetry.counter("ndarray.wait_to_read")


def _is_np_shape_scalar(x):
    return isinstance(x, (int, float, bool, np.number))


class _FnOp:
    """Tape-recordable wrapper for NDArray method/dunder math so imperative
    autograd sees them (the reference routes dunders through registered ops;
    here they call jnp directly for speed and record this shim instead)."""

    __slots__ = ("fn",)
    name = "_fn"
    need_rng = False

    def __init__(self, fn):
        self.fn = fn

    def apply(self, ins, params, mode):
        return [self.fn(*ins)], []


class NDArray:
    """Mutable handle over a jax.Array.

    ``_data`` is a property so executor outputs can be *lazy*: an executor
    hands out output handles immediately and installs ``_lazy`` — the first
    read of any handle triggers the (single, fused) XLA execution. This is
    the engine-async analogue of the reference: ``Engine::Push`` returns
    immediately and ``WaitToRead`` blocks (threaded_engine.cc:258,314).
    """

    __slots__ = ("_d", "_lazy", "_ctx", "_grad", "_autograd_entry", "__weakref__")

    def __init__(self, data, ctx=None):
        self._d = data
        self._lazy = None
        self._ctx = ctx
        self._grad = None
        self._autograd_entry = None

    @property
    def _data(self):
        # a materialization callback may itself install a new lazy thunk
        # (the executor's packed-parameter slices do), so loop to a value.
        # A callback that RAISES is re-armed: its error condition must
        # repeat on the next read, never decay into serving stale _d.
        while self._lazy is not None:
            cb = self._lazy
            self._lazy = None
            try:
                cb()
            except BaseException:
                self._lazy = cb
                raise
        return self._d

    @_data.setter
    def _data(self, value):
        self._lazy = None
        self._d = value

    def _set_lazy(self, cb):
        self._lazy = cb

    # --- basic properties -------------------------------------------------
    @property
    def shape(self):
        if self._d is None and self._lazy is not None:
            # lazy handles can carry their metadata on the thunk (see
            # executor reshape placeholders) so shape/dtype queries don't
            # force a device allocation
            s = getattr(self._lazy, "shape", None)
            if s is not None:
                return tuple(s)
        return tuple(self._data.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        if self._d is None and self._lazy is not None:
            dt = getattr(self._lazy, "dtype", None)
            if dt is not None:
                return np_dtype(dt)
        return np_dtype(self._data.dtype)

    @property
    def stype(self):
        return "default"

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        try:
            dev = list(self._data.devices())[0]
        except Exception:
            return cpu()
        if dev.platform == "cpu":
            return Context("cpu", dev.id)
        return Context("tpu", getattr(dev, "id", 0))

    ctx = context

    @property
    def grad(self):
        return self._grad

    # --- conversion -------------------------------------------------------
    def asnumpy(self):
        _SYNC_ASNUMPY.inc()
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def astype(self, dtype):
        dt = np_dtype(dtype)
        return self._record_unary(
            NDArray(self._data.astype(dt), self._ctx), lambda x: x.astype(dt)
        )

    def copy(self):
        import jax.numpy as jnp

        return NDArray(jnp.asarray(self._data), self._ctx)

    def copyto(self, other):
        import jax

        if isinstance(other, NDArray):
            if other is self:
                return other
            # zeros not made yet (executor._lazy_placeholder): nothing to
            # read, so do not make them just to drop them
            lazy = other._lazy if other._d is None else None
            placement = getattr(lazy, "placement", None)
            if placement is not None:
                dtype = lazy.dtype
            else:
                tgt = other._data
                dtype = tgt.dtype
                placement = tgt.sharding if hasattr(tgt, "sharding") else list(tgt.devices())[0]
            other._data = jax.device_put(self._data.astype(dtype), placement)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        raise MXNetError(f"copyto does not support type {type(other)}")

    def as_in_context(self, context):
        if self.context == context:
            return self
        return self.copyto(context)

    def to_device(self, context):
        return self.as_in_context(context)

    # --- engine facade ----------------------------------------------------
    def wait_to_read(self):
        import jax

        _SYNC_WAIT.inc()
        jax.block_until_ready(self._data)

    def wait_to_write(self):
        self.wait_to_read()

    # --- shape ops --------------------------------------------------------
    def reshape(self, shape, **kwargs):
        from .ops.defs_tensor import infer_reshape

        if isinstance(shape, int):
            shape = (shape,)
        out_shape = infer_reshape(self.shape, tuple(shape), kwargs.get("reverse", False))
        return self._record_unary(
            NDArray(self._data.reshape(out_shape), self._ctx),
            lambda x: x.reshape(out_shape),
        )

    @property
    def T(self):
        return self._record_unary(
            NDArray(self._data.T, self._ctx), lambda x: x.T
        )

    def transpose(self, axes=None):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.transpose(self._data, axes), self._ctx),
            lambda x: jnp.transpose(x, axes),
        )

    def flatten(self):
        return self.reshape((self.shape[0], -1))  # reshape records the tape entry

    def expand_dims(self, axis):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.expand_dims(self._data, axis), self._ctx),
            lambda x: jnp.expand_dims(x, axis),
        )

    def broadcast_to(self, shape):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.broadcast_to(self._data, shape), self._ctx),
            lambda x: jnp.broadcast_to(x, shape),
        )

    def slice(self, begin, end):
        return NDArray(
            self._data[tuple(builtins.slice(b, e) for b, e in zip(begin, end))]
        )

    def slice_axis(self, axis, begin, end):
        import jax.lax as lax

        return NDArray(lax.slice_in_dim(self._data, begin, end, axis=axis))

    # --- indexing ---------------------------------------------------------
    def __getitem__(self, key):
        return self._record_unary(
            NDArray(self._data[key], self._ctx), lambda x: x[key]
        )

    def __setitem__(self, key, value):
        import jax
        import jax.numpy as jnp

        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, (np.ndarray, list, tuple, int, float)):
            v = jnp.asarray(value, dtype=self.dtype)
        else:
            v = value
        old = self._data
        if key is Ellipsis or (
            isinstance(key, builtins.slice) and key == builtins.slice(None)
        ):
            new = jnp.broadcast_to(jnp.asarray(v, dtype=self.dtype), self.shape)
        else:
            new = old.at[key].set(v)
        # Assignment writes INTO the existing buffer in the reference, so the
        # device/sharding placement must survive a full-slice assignment —
        # critical for mesh-sharded executor arrays.
        if hasattr(old, "sharding") and hasattr(new, "sharding") and \
                new.sharding != old.sharding and tuple(new.shape) == tuple(old.shape):
            new = jax.device_put(new, old.sharding)
        self._data = new

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __repr__(self):
        return f"{self.asnumpy()!r}\n<NDArray {'x'.join(map(str, self.shape))} @{self.context}>"

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    # --- arithmetic -------------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        if isinstance(other, NDArray):
            o = other._data
        else:
            o = other
        a, b = (o, self._data) if reverse else (self._data, o)
        out = NDArray(fn(a, b), self._ctx)
        from . import autograd

        if autograd.is_recording():
            if isinstance(other, NDArray):
                ins = [other, self] if reverse else [self, other]
                autograd.record_op(_FnOp(fn), {}, ins, [out])
            else:
                g = (lambda x: fn(o, x)) if reverse else (lambda x: fn(x, o))
                autograd.record_op(_FnOp(g), {}, [self], [out])
        return out

    def _record_unary(self, out, fn):
        from . import autograd

        if autograd.is_recording():
            autograd.record_op(_FnOp(fn), {}, [self], [out])
        return out

    def __add__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.add)

    __radd__ = __add__

    def __sub__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.subtract)

    def __rsub__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.subtract, reverse=True)

    def __mul__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.divide)

    def __rtruediv__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.divide, reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.mod)

    def __pow__(self, o):
        import jax.numpy as jnp

        return self._binary(o, jnp.power)

    def __neg__(self):
        return self._record_unary(NDArray(-self._data, self._ctx), lambda x: -x)

    def __abs__(self):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.abs(self._data), self._ctx), jnp.abs
        )

    def _inplace(self, other, fn):
        from . import autograd

        if isinstance(other, NDArray):
            o = other._data
            ins = [self, other]
            g = fn
        else:
            o = other
            ins = [self]
            g = lambda x: fn(x, o)
        new = fn(self._data, o)
        if autograd.is_recording():
            # self is input AND output: sequential tape replay reads the
            # pre-entry value, then rebinds — mirroring in-place mutation.
            autograd.record_op(_FnOp(g), {}, ins, [self])
        self._data = new
        return self

    def __iadd__(self, o):
        import jax.numpy as jnp

        return self._inplace(o, jnp.add)

    def __isub__(self, o):
        import jax.numpy as jnp

        return self._inplace(o, jnp.subtract)

    def __imul__(self, o):
        import jax.numpy as jnp

        return self._inplace(o, jnp.multiply)

    def __itruediv__(self, o):
        import jax.numpy as jnp

        return self._inplace(o, jnp.divide)

    def _cmp(self, o, fn):
        import jax.numpy as jnp

        r = self._binary(o, fn)
        return NDArray(r._data.astype(self.dtype), self._ctx)

    def __eq__(self, o):
        import jax.numpy as jnp

        if o is None:
            return False
        return self._cmp(o, jnp.equal)

    def __ne__(self, o):
        import jax.numpy as jnp

        if o is None:
            return True
        return self._cmp(o, jnp.not_equal)

    def __gt__(self, o):
        import jax.numpy as jnp

        return self._cmp(o, jnp.greater)

    def __ge__(self, o):
        import jax.numpy as jnp

        return self._cmp(o, jnp.greater_equal)

    def __lt__(self, o):
        import jax.numpy as jnp

        return self._cmp(o, jnp.less)

    def __le__(self, o):
        import jax.numpy as jnp

        return self._cmp(o, jnp.less_equal)

    __hash__ = object.__hash__

    # --- reductions (method forms) ---------------------------------------
    def sum(self, axis=None, keepdims=False):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.sum(self._data, axis=axis, keepdims=keepdims)),
            lambda x: jnp.sum(x, axis=axis, keepdims=keepdims),
        )

    def mean(self, axis=None, keepdims=False):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.mean(self._data, axis=axis, keepdims=keepdims)),
            lambda x: jnp.mean(x, axis=axis, keepdims=keepdims),
        )

    def max(self, axis=None, keepdims=False):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.max(self._data, axis=axis, keepdims=keepdims)),
            lambda x: jnp.max(x, axis=axis, keepdims=keepdims),
        )

    def min(self, axis=None, keepdims=False):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.min(self._data, axis=axis, keepdims=keepdims)),
            lambda x: jnp.min(x, axis=axis, keepdims=keepdims),
        )

    def clip(self, a_min, a_max):
        import jax.numpy as jnp

        return self._record_unary(
            NDArray(jnp.clip(self._data, a_min, a_max)),
            lambda x: jnp.clip(x, a_min, a_max),
        )

    def abs(self):
        return self.__abs__()

    def argmax(self, axis=None):
        import jax.numpy as jnp

        return NDArray(jnp.argmax(self._data, axis=axis).astype(self.dtype))

    def argmin(self, axis=None):
        import jax.numpy as jnp

        return NDArray(jnp.argmin(self._data, axis=axis).astype(self.dtype))

    # --- autograd (imperative) -------------------------------------------
    def attach_grad(self, grad_req="write"):
        from . import autograd

        autograd.mark_variable(self, grad_req)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from . import autograd

        autograd.backward([self], [out_grad] if out_grad is not None else None)

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out


# ---------------------------------------------------------------------------
# creation helpers
# ---------------------------------------------------------------------------
def _place(data, ctx):
    import jax

    if ctx is None:
        return data
    return jax.device_put(data, ctx.jax_device())


def array(source_array, ctx=None, dtype=None):
    import jax.numpy as jnp

    if isinstance(source_array, NDArray):
        src = source_array._data
        if dtype is not None:
            src = src.astype(np_dtype(dtype))
        return NDArray(_place(src, ctx), ctx)
    arr = np.asarray(source_array, dtype=np_dtype(dtype) if dtype else None)  # graftlint: allow=host-sync(NDArray inputs took the branch above; this converts host lists/numpy on the ingest path — no device handle involved)
    if arr.dtype == np.float64 and dtype is None:
        arr = arr.astype(np.float32)
    if arr.dtype == np.int64 and dtype is None and not isinstance(source_array, np.ndarray):
        arr = arr.astype(np.float32)  # mxnet default dtype is float32
    return NDArray(_place(jnp.asarray(arr), ctx), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.zeros(shape, np_dtype(dtype)), ctx), ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.ones(shape, np_dtype(dtype)), ctx), ctx)


def full(shape, val, ctx=None, dtype=None):
    import jax.numpy as jnp

    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.full(shape, val, np_dtype(dtype)), ctx), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    import jax.numpy as jnp

    out = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return NDArray(_place(out, ctx), ctx)


def onehot_encode(indices, out):
    import jax

    depth = out.shape[1]
    out._data = jax.nn.one_hot(
        indices._data.astype("int32"), depth, dtype=out.dtype
    )
    return out


def concatenate(arrays, axis=0, always_copy=True):
    import jax.numpy as jnp

    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis))


def moveaxis(tensor, source, destination):
    import jax.numpy as jnp

    return NDArray(jnp.moveaxis(tensor._data, source, destination))


def waitall():
    import jax

    jax.effects_barrier()


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    raise MXNetError("imdecode: use mxnet_tpu.image instead")


# ---------------------------------------------------------------------------
# save / load — REFERENCE-BINARY-COMPATIBLE .params format
# (src/ndarray/ndarray.cc:806+ NDArray::Save V2, container :1004-1030;
# container magic kMXAPINDArrayListMagic=0x112 :1002; legacy V1/V0 load paths
# :871-918 so reference-era checkpoints and model-zoo files load directly)
# ---------------------------------------------------------------------------
_LIST_MAGIC = 0x112
_ND_V2_MAGIC = 0xF993FAC9
_ND_V1_MAGIC = 0xF993FAC8
_OLD_CUSTOM_MAGIC = b"MXTPU001"  # round-1 container, still readable

# mshadow type flags (mshadow/base.h); 100+ are our extensions for dtypes
# the CUDA-era reference cannot represent
_TYPE_FLAG_TO_NP = {
    0: "float32", 1: "float64", 2: "float16", 3: "uint8", 4: "int32",
    5: "int8", 6: "int64", 100: "bfloat16",
}
_NP_TO_TYPE_FLAG = {v: k for k, v in _TYPE_FLAG_TO_NP.items()}
_STYPE_TO_ID = {"default": 0, "row_sparse": 1, "csr": 2}
_ID_TO_STYPE = {v: k for k, v in _STYPE_TO_ID.items()}


def _np_of(arr):
    np_arr = arr if isinstance(arr, np.ndarray) else np.asarray(arr)
    return np.ascontiguousarray(np_arr)


def _write_shape(f, shape):
    # nnvm::Tuple::Save: uint32 ndim + int64 dims (nnvm dim_t = int64_t;
    # the reference's "version 1, with int64_t TShape" comment at
    # ndarray.cc:800 — only the V0 magic-is-ndim legacy path is uint32)
    f.write(struct.pack("<I", len(shape)))
    f.write(struct.pack(f"<{len(shape)}q", *shape))


def _read_shape(f):
    (ndim,) = struct.unpack("<I", f.read(4))
    if not ndim:
        return ()
    dims = struct.unpack(f"<{ndim}q", f.read(8 * ndim))
    # a pre-r3 file's uint32 dim pair merges into one int64 >= 2^32 (the
    # high word is a dim >= 1), so this bound catches old files on the
    # very first shape read
    if any(d < 0 or d >= (1 << 32) for d in dims):
        raise MXNetError(
            "corrupt TShape while loading .params (dims read as int64 per "
            "the reference format); files written by pre-r3 builds of this "
            "framework used uint32 dims and must be re-saved"
        )
    return tuple(int(d) for d in dims)


def _dtype_np(buf, dtype_name, shape):
    if dtype_name == "bfloat16":
        import ml_dtypes

        return np.frombuffer(buf, dtype=ml_dtypes.bfloat16).reshape(shape)
    return np.frombuffer(buf, dtype=dtype_name).reshape(shape)


def _save_one(f, arr):
    """One NDArray in the reference V2 layout (ndarray.cc:806-870)."""
    from .sparse_ndarray import BaseSparseNDArray

    stype = arr.stype
    f.write(struct.pack("<I", _ND_V2_MAGIC))
    f.write(struct.pack("<i", _STYPE_TO_ID[stype]))
    if isinstance(arr, BaseSparseNDArray):
        values = _np_of(arr._values)
        # aux written as int64 — the reference's aux dtype — so its loader
        # accepts our sparse checkpoints (we use int32 on device); _aux is
        # already in the reference's order ([kIndPtr, kIdx] for csr,
        # ndarray.h:62)
        aux = [_np_of(a).astype(np.int64) for a in arr._aux]
        _write_shape(f, values.shape)  # storage shape
    else:
        values = _np_of(arr.asnumpy())
        aux = []
    if values.ndim == 0:
        # reference TShape has no rank-0; scalars serialize as (1,)
        values = values.reshape(1)
    _write_shape(f, values.shape if not aux else arr.shape)
    f.write(struct.pack("<ii", 1, 0))  # Context: kCPU, dev_id 0
    dtype_name = np.dtype(values.dtype).name
    if dtype_name not in _NP_TO_TYPE_FLAG:  # unknown dtypes fall back
        values = values.astype(np.float32)
        dtype_name = "float32"
    f.write(struct.pack("<i", _NP_TO_TYPE_FLAG[dtype_name]))
    for a in aux:
        f.write(struct.pack("<i", _NP_TO_TYPE_FLAG["int64"]))
        _write_shape(f, a.shape)
    f.write(values.tobytes())
    for a in aux:
        f.write(a.tobytes())


def _load_one(f):
    from . import sparse_ndarray as _sp

    (magic,) = struct.unpack("<I", f.read(4))
    if magic == _ND_V2_MAGIC:
        (stype_id,) = struct.unpack("<i", f.read(4))
        stype = _ID_TO_STYPE[stype_id]
        nad = {"default": 0, "row_sparse": 1, "csr": 2}[stype]
        storage_shape = _read_shape(f) if nad else None
        shape = _read_shape(f)
        if not shape:
            return array(np.zeros((0,), np.float32))
        f.read(8)  # Context (ignored: arrays land on the default device)
        (type_flag,) = struct.unpack("<i", f.read(4))
        dtype_name = _TYPE_FLAG_TO_NP[type_flag]
        aux_meta = []
        for _ in range(nad):
            (aux_flag,) = struct.unpack("<i", f.read(4))
            aux_meta.append((_TYPE_FLAG_TO_NP[aux_flag], _read_shape(f)))
        data_shape = storage_shape if nad else shape
        nbytes = int(np.prod(data_shape, dtype=np.int64)) * np.dtype(
            "uint16" if dtype_name == "bfloat16" else dtype_name
        ).itemsize
        values = _dtype_np(f.read(nbytes), dtype_name, data_shape)
        auxes = []
        for dt, sh in aux_meta:
            n = int(np.prod(sh, dtype=np.int64)) * np.dtype(dt).itemsize
            auxes.append(np.frombuffer(f.read(n), dtype=dt).reshape(sh))
        if stype == "row_sparse":
            return _sp.row_sparse(values, auxes[0].astype(np.int32), shape)
        if stype == "csr":
            return _sp.csr(values, auxes[0].astype(np.int32),
                           auxes[1].astype(np.int32), shape)
        return array(values, dtype=values.dtype)
    # legacy V1 / V0 dense layouts (ndarray.cc LegacyLoad :888-918)
    if magic == _ND_V1_MAGIC:
        shape = _read_shape(f)
    else:
        ndim = magic  # V0: the magic word IS ndim
        shape = struct.unpack(f"<{ndim}I", f.read(4 * ndim)) if ndim else ()
    if not shape:
        return array(np.zeros((0,), np.float32))
    f.read(8)  # Context
    (type_flag,) = struct.unpack("<i", f.read(4))
    dtype_name = _TYPE_FLAG_TO_NP[type_flag]
    nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype_name).itemsize
    values = _dtype_np(f.read(nbytes), dtype_name, shape)
    return array(values, dtype=values.dtype)


def save(fname, data):
    """Save NDArrays in the reference's binary .params container — files are
    interchangeable with the reference's ``mx.nd.save`` (ndarray.cc:1004)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        items = list(data.items())
        names = [k for k, _ in items]
    elif isinstance(data, (list, tuple)):
        items = [("", d) for d in data]
        names = []
    else:
        raise MXNetError("save: data must be NDArray, list or dict")
    for _, arr in items:
        if not isinstance(arr, NDArray):
            raise MXNetError("save: values must be NDArray")
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _LIST_MAGIC, 0))
        f.write(struct.pack("<Q", len(items)))
        for _, arr in items:
            _save_one(f, arr)
        f.write(struct.pack("<Q", len(names)))
        for n in names:
            nb = n.encode()
            f.write(struct.pack("<Q", len(nb)))
            f.write(nb)


def load(fname):
    """Load a .params file (reference container, legacy V1/V0 arrays, or the
    round-1 custom container). Returns list or dict."""
    with open(fname, "rb") as f:
        return _load_stream(f, fname)


def load_buffer(data):
    """Load NDArrays from an in-memory .params blob (reference
    ``MXNDArrayLoadFromBytes`` / the c_predict_api param-bytes input)."""
    import io

    return _load_stream(io.BytesIO(data), "<buffer>")


def _load_stream(f, fname):
    head = f.read(8)
    if head == _OLD_CUSTOM_MAGIC:
        return _load_old_custom(f)
    (header,) = struct.unpack("<Q", head)
    (reserved,) = struct.unpack("<Q", f.read(8))
    if header != _LIST_MAGIC:
        raise MXNetError(f"{fname}: not a valid NDArray file")
    (count,) = struct.unpack("<Q", f.read(8))
    arrays = [_load_one(f) for _ in range(count)]
    (ncount,) = struct.unpack("<Q", f.read(8))
    names = []
    for _ in range(ncount):
        (nlen,) = struct.unpack("<Q", f.read(8))
        names.append(f.read(nlen).decode())
    if names:
        if len(names) != len(arrays):
            raise MXNetError(f"{fname}: name/array count mismatch")
        return dict(zip(names, arrays))
    return arrays


def _load_old_custom(f):
    """Round-1 container (magic MXTPU001), kept readable."""
    (count,) = struct.unpack("<q", f.read(8))
    names, arrays = [], []
    for _ in range(count):
        (nlen,) = struct.unpack("<q", f.read(8))
        name = f.read(nlen).decode()
        (hlen,) = struct.unpack("<q", f.read(8))
        parts = f.read(hlen).decode().split("|")
        dtype_s, shape_s = parts[0], parts[1]
        stype = parts[2] if len(parts) > 2 else "default"
        shape = tuple(int(x) for x in shape_s.split(",")) if shape_s else ()
        (blen,) = struct.unpack("<q", f.read(8))
        buf = f.read(blen)
        arr = _dtype_np(buf, dtype_s, shape)
        out_arr = array(arr, dtype=arr.dtype)
        if stype != "default":
            from .sparse_ndarray import cast_storage as _cast

            out_arr = _cast(out_arr, stype)
        names.append(name)
        arrays.append(out_arr)
    if any(names):
        return dict(zip(names, arrays))
    return arrays


# ---------------------------------------------------------------------------
# op codegen from the registry
# ---------------------------------------------------------------------------
def _make_ndarray_function(opdef, func_name):
    def generic_op(*args, **kwargs):
        out = kwargs.pop("out", None)
        kwargs.pop("name", None)
        tensor_kwargs = {}
        param_kwargs = {}
        for k, v in kwargs.items():
            if isinstance(v, NDArray):
                tensor_kwargs[k] = v
            else:
                param_kwargs[k] = v
        pos = list(args)
        if "num_args" in opdef.param_schema and "num_args" not in param_kwargs:
            param_kwargs["num_args"] = len(pos) + len(tensor_kwargs)
        params = opdef.parse_params(param_kwargs)
        names = opdef.arg_names(params) + opdef.aux_names(params)
        inputs = []
        for nm in names:
            if nm in tensor_kwargs:
                inputs.append(tensor_kwargs.pop(nm))
            elif pos:
                inputs.append(pos.pop(0))
            else:
                raise MXNetError(f"{func_name}: missing input {nm!r}")
        if pos and not callable(opdef._arg_names):
            raise MXNetError(f"{func_name}: too many positional inputs")
        inputs.extend(pos)  # variadic tail
        arrays = [i._data if isinstance(i, NDArray) else i for i in inputs]
        from . import autograd

        mode = OpMode(
            is_train=autograd.is_training(),
            rng=_random.next_key() if opdef.need_rng else None,
            platform=platform_of(arrays),
        )
        outputs, new_aux = opdef.apply(arrays, params, mode)
        # write aux updates back into their handles (mutable aux semantics)
        n_args = len(opdef.arg_names(params))
        for i, na in enumerate(new_aux):
            handle = inputs[n_args + i]
            if isinstance(handle, NDArray):
                handle._data = na
        # mutable-input rebinding (optimizer state)
        arg_names = opdef.arg_names(params)
        for in_name, out_idx in opdef.mutate:
            idx = arg_names.index(in_name)
            if isinstance(inputs[idx], NDArray):
                inputs[idx]._data = outputs[out_idx]
        nvis = opdef.num_visible_outputs(params)
        vis = outputs[:nvis]
        if autograd.is_recording():
            in_nds = [i for i in inputs if isinstance(i, NDArray)]
            out_nds = [NDArray(o) for o in vis]
            autograd.record_op(opdef, params, in_nds, out_nds, rng=mode.rng)
        else:
            out_nds = [NDArray(o) for o in vis]
        if out is not None:
            outs = out if isinstance(out, (list, tuple)) else [out]
            for o_handle, o_val in zip(outs, vis):
                o_handle._data = o_val
            return out
        if len(out_nds) == 1:
            return out_nds[0]
        return out_nds

    generic_op.__name__ = func_name
    generic_op.__doc__ = opdef.doc or f"{func_name} (op {opdef.name})"
    return generic_op


def _init_ops():
    module = sys.modules[__name__]
    for name in _reg.list_ops():
        opdef = _reg.get(name)
        if hasattr(module, name):
            continue  # don't clobber hand-written helpers
        setattr(module, name, _make_ndarray_function(opdef, name))


_init_ops()


# --- sparse-aware dispatch over the generated dense ops ---------------------
# (the reference dispatches on storage type to FComputeEx kernels,
# c_api_ndarray.cc:436-458; here the handful of sparse kernels live in
# sparse_ndarray and everything else dense-falls-back automatically)
_dense_dot = dot  # noqa: F821  (generated above)


def dot(lhs, rhs, transpose_a=False, transpose_b=False, **kwargs):
    from .sparse_ndarray import BaseSparseNDArray, dot as _sp_dot

    if isinstance(lhs, BaseSparseNDArray) or isinstance(rhs, BaseSparseNDArray):
        return _sp_dot(lhs, rhs, transpose_a, transpose_b)
    return _dense_dot(
        lhs, rhs, transpose_a=transpose_a, transpose_b=transpose_b, **kwargs
    )


def cast_storage(arr, storage_type="default", stype=None):
    from .sparse_ndarray import cast_storage as _cast

    return _cast(arr, stype or storage_type)


def sparse_retain(data, indices):
    from .sparse_ndarray import sparse_retain as _retain

    return _retain(data, indices)


_dense_elemwise_add = elemwise_add  # noqa: F821


def elemwise_add(lhs, rhs, **kwargs):
    from .sparse_ndarray import BaseSparseNDArray, elemwise_add as _sp_add

    if isinstance(lhs, BaseSparseNDArray) or isinstance(rhs, BaseSparseNDArray):
        return _sp_add(lhs, rhs)
    return _dense_elemwise_add(lhs, rhs, **kwargs)
