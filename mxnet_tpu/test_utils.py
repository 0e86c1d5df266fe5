"""Testing oracles.

Reference: ``python/mxnet/test_utils.py`` — the numeric keystone of the test
strategy (SURVEY.md §4): ``check_numeric_gradient`` (finite differences,
test_utils.py:470), ``check_symbolic_forward/backward`` (:591,656),
``assert_almost_equal`` with per-dtype tolerances, ``check_consistency``
(:838) cross-context/dtype checks, ``check_speed`` (:764).
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from .base import MXNetError, np_dtype
from .context import Context, cpu, current_context
from .ndarray import NDArray, array, zeros
from .symbol import Symbol

_rng = np.random.RandomState(1234)

default_dtype = np.float32


def default_context():
    return current_context()


def set_default_context(ctx):
    Context._default_ctx.value = ctx


def default_numeric_eps():
    return 1e-2


def random_arrays(*shapes):
    arrays = [np.array(_rng.randn(), dtype=default_dtype) if len(s) == 0
              else _rng.randn(*s).astype(default_dtype) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def rand_shape_2d(dim0=10, dim1=10):
    return (_rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (
        _rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1),
        _rng.randint(1, dim2 + 1),
    )


def np_reduce(dat, axis, keepdims, numpy_reduce_func):
    """Apply a numpy reduce with MXNet axis/keepdims semantics."""
    if isinstance(axis, int):
        axis = [axis]
    else:
        axis = list(axis) if axis is not None else range(len(dat.shape))
    ret = dat
    for i in reversed(sorted(axis)):
        ret = numpy_reduce_func(ret, axis=i)
    if keepdims:
        keepdims_shape = list(dat.shape)
        for i in axis:
            keepdims_shape[i] = 1
        ret = ret.reshape(tuple(keepdims_shape))
    return ret


def same(a, b):
    return np.array_equal(a, b)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    rtol = rtol or 1e-5
    atol = atol or 1e-20
    return np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def find_max_violation(a, b, rtol, atol):
    diff = np.abs(a - b)
    tol = atol + rtol * np.abs(b)
    violation = diff / (tol + 1e-20)
    loc = np.unravel_index(np.argmax(violation), violation.shape)
    return loc, violation[loc]


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    rtol = rtol or 1e-5
    atol = atol or 1e-20
    if isinstance(a, NDArray):
        a = a.asnumpy()
    if isinstance(b, NDArray):
        b = b.asnumpy()
    a = np.asarray(a, dtype=np.float64) if np.asarray(a).dtype.kind == "V" else np.asarray(a)
    b = np.asarray(b)
    if a.dtype.name == "bfloat16":
        a = a.astype(np.float32)
    if b.dtype.name == "bfloat16":
        b = b.astype(np.float32)
    if almost_equal(a, b, rtol, atol, equal_nan=equal_nan):
        return
    loc, viol = find_max_violation(a.astype(np.float64), b.astype(np.float64), rtol, atol)
    raise AssertionError(
        f"Error {viol:f} exceeds tolerance rtol={rtol:e}, atol={atol:e} at "
        f"location {loc}.\n{names[0]}: {a[loc]}\n{names[1]}: {b[loc]}"
    )


def assert_allclose(a, b, rtol=1e-5, atol=1e-20):
    assert_almost_equal(a, b, rtol=rtol, atol=atol)


def rand_ndarray(shape, stype="default", density=None, dtype=None):
    """Random dense or sparse NDArray (reference test_utils.py:106)."""
    if stype == "default":
        return array(_rng.randn(*shape).astype(dtype or default_dtype))
    from .sparse_ndarray import cast_storage

    density = 0.5 if density is None else density
    dn = _rng.randn(*shape).astype(dtype or default_dtype)
    if stype == "row_sparse":
        mask = _rng.rand(shape[0]) < density
        dn[~mask] = 0
    elif stype == "csr":
        dn[_rng.rand(*shape) >= density] = 0
    else:
        raise MXNetError(f"unknown stype {stype!r}")
    return cast_storage(array(dn), stype)


def _parse_location(sym, location, ctx=None):
    if isinstance(location, dict):
        names = sym.list_arguments()
        for k in location:
            if k not in names:
                raise ValueError(f"Symbol does not have argument {k}")
        location = {k: (v if isinstance(v, NDArray) else array(v)) for k, v in location.items()}
    else:
        location = {
            k: (v if isinstance(v, NDArray) else array(v))
            for k, v in zip(sym.list_arguments(), location)
        }
    return location


def _parse_aux_states(sym, aux_states, ctx=None):
    if aux_states is None:
        return None
    if isinstance(aux_states, dict):
        return {k: (v if isinstance(v, NDArray) else array(v)) for k, v in aux_states.items()}
    return {
        k: (v if isinstance(v, NDArray) else array(v))
        for k, v in zip(sym.list_auxiliary_states(), aux_states)
    }


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Central finite differences of sum(outputs) wrt each location entry
    (reference numeric_grad, test_utils.py:423)."""
    approx_grads = {k: np.zeros(v.shape, dtype=np.float64)
                    for k, v in location.items()}
    for k, v in location.items():
        executor.arg_dict[k][:] = v
    for k in location:
        old_value = location[k].asnumpy().copy()
        flat = old_value.reshape(-1)
        ap = approx_grads[k].reshape(-1)
        for i in range(flat.size):
            # f(x+eps)
            pert = flat.copy()
            pert[i] += eps
            executor.arg_dict[k][:] = array(pert.reshape(old_value.shape))
            executor.forward(is_train=use_forward_train)
            f_peps = sum(out.asnumpy().astype(np.float64).sum()
                         for out in executor.outputs)
            pert[i] = flat[i] - eps
            executor.arg_dict[k][:] = array(pert.reshape(old_value.shape))
            executor.forward(is_train=use_forward_train)
            f_neps = sum(out.asnumpy().astype(np.float64).sum()
                         for out in executor.outputs)
            ap[i] = (f_peps - f_neps) / (2 * eps)
        executor.arg_dict[k][:] = array(old_value)
    return approx_grads


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-2,
                           rtol=1e-2, atol=None, grad_nodes=None,
                           use_forward_train=True, ctx=None,
                           grad_stype_dict=None):
    """Verify executor gradients against finite differences
    (reference check_numeric_gradient, test_utils.py:470)."""
    ctx = ctx or default_context()
    atol = atol if atol is not None else 1e-4

    location = _parse_location(sym, location, ctx)
    location_npy = {k: v.asnumpy() for k, v in location.items()}
    aux_states = _parse_aux_states(sym, aux_states, ctx)

    if grad_nodes is None:
        grad_nodes = [k for k in location]
        grad_req = {k: "write" for k in location}
    elif isinstance(grad_nodes, (list, tuple)):
        grad_nodes = list(grad_nodes)
        grad_req = {k: "write" for k in grad_nodes}
    elif isinstance(grad_nodes, dict):
        grad_req = grad_nodes.copy()
        grad_nodes = list(grad_nodes.keys())
    else:
        raise ValueError("Invalid grad_nodes")

    # random-projection head so multi-output & non-scalar heads reduce to a
    # scalar objective (reference wraps sym with MakeLoss(sum(sym * proj)))
    args_grad = {
        k: zeros(location[k].shape) for k in grad_nodes if k in location
    }
    executor = sym.bind(
        ctx, args=location, args_grad=args_grad, grad_req=grad_req,
        aux_states=aux_states,
    )
    executor.forward(is_train=use_forward_train)
    executor.backward(
        [NDArray(__import__("jax").numpy.ones_like(o._data))
         for o in executor.outputs]
    )
    analytic = {k: executor.grad_dict[k].asnumpy() for k in grad_nodes}

    fd_exe = sym.bind(
        ctx, args={k: array(v) for k, v in location_npy.items()},
        aux_states=aux_states, grad_req="null",
    )
    numeric = numeric_grad(
        fd_exe, {k: array(v) for k, v in location_npy.items()},
        aux_states, eps=numeric_eps, use_forward_train=use_forward_train,
    )
    for name in grad_nodes:
        if grad_req[name] == "null":
            continue
        assert_almost_equal(
            analytic[name], numeric[name], rtol, atol,
            (f"analytic_{name}", f"numeric_{name}"),
        )


def check_symbolic_forward(sym, location, expected, rtol=1e-5, atol=None,
                           aux_states=None, ctx=None):
    """Compare executor outputs to expected numpy arrays
    (reference check_symbolic_forward, test_utils.py:591)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux_states = _parse_aux_states(sym, aux_states, ctx)
    executor = sym.bind(ctx, args=location, aux_states=aux_states, grad_req="null")
    executor.forward(is_train=False)
    outputs = [x.asnumpy() for x in executor.outputs]
    for output_name, expect, output in zip(sym.list_outputs(), expected, outputs):
        assert_almost_equal(
            expect, output, rtol, atol,
            (f"EXPECTED_{output_name}", output_name),
        )
    return outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None):
    """Compare executor gradients to expected numpy arrays
    (reference check_symbolic_backward, test_utils.py:656)."""
    ctx = ctx or default_context()
    location = _parse_location(sym, location, ctx)
    aux_states = _parse_aux_states(sym, aux_states, ctx)
    if isinstance(expected, (list, tuple)):
        expected = {k: v for k, v in zip(sym.list_arguments(), expected)}
    args_grad_data = {
        k: (array(np.zeros(v.shape, dtype=default_dtype)) if
            (grad_req if isinstance(grad_req, str) else grad_req.get(k, "write")) != "add"
            else array(_rng.normal(size=v.shape).astype(default_dtype)))
        for k, v in location.items()
    }
    add_base = {k: v.asnumpy().copy() for k, v in args_grad_data.items()}
    executor = sym.bind(
        ctx, args=location, args_grad=args_grad_data, aux_states=aux_states,
        grad_req=grad_req,
    )
    executor.forward(is_train=True)
    if isinstance(out_grads, (tuple, list)):
        out_grads = [array(v) if not isinstance(v, NDArray) else v for v in out_grads]
    elif out_grads is not None:
        raise ValueError("out_grads must be a list or None")
    executor.backward(out_grads)
    grads = {k: v.asnumpy() for k, v in args_grad_data.items()}
    for name in expected:
        if (grad_req if isinstance(grad_req, str) else grad_req.get(name)) == "write":
            assert_almost_equal(
                expected[name], grads[name], rtol, atol,
                (f"EXPECTED_{name}", name),
            )
        elif (grad_req if isinstance(grad_req, str) else grad_req.get(name)) == "add":
            assert_almost_equal(
                expected[name] + add_base[name], grads[name], rtol, atol,
                (f"EXPECTED_{name}", name),
            )
    return grads


def check_speed(sym, location=None, ctx=None, N=20, grad_req=None,
                typ="whole", **kwargs):
    """Time forward(+backward) throughput (reference check_speed)."""
    import jax

    ctx = ctx or default_context()
    if grad_req is None:
        grad_req = "write"
    if location is None:
        exe = sym.simple_bind(ctx=ctx, grad_req=grad_req, **kwargs)
        location = {
            k: array(_rng.normal(size=arr.shape, scale=1.0).astype(default_dtype))
            for k, arr in exe.arg_dict.items()
        }
    else:
        assert isinstance(location, dict)
        exe = sym.simple_bind(
            ctx=ctx, grad_req=grad_req,
            **{k: v.shape for k, v in location.items()},
        )
    for name, arr in location.items():
        exe.arg_dict[name][:] = arr

    def ones_heads():
        # arbitrary symbols need explicit head grads (backward() with no
        # out_grads is reserved for loss-layer heads)
        return [NDArray(jax.numpy.ones_like(o._data)) for o in exe.outputs]

    if typ == "whole":
        exe.forward(is_train=True)
        exe.backward(ones_heads())
        for o in exe.outputs:
            o.wait_to_read()
        tic = time.time()
        for _ in range(N):
            exe.forward(is_train=True)
            exe.backward(ones_heads())
        for o in exe.outputs:
            o.wait_to_read()
        jax.effects_barrier()
        return (time.time() - tic) / N
    elif typ == "forward":
        exe.forward(is_train=False)
        for o in exe.outputs:
            o.wait_to_read()
        tic = time.time()
        for _ in range(N):
            exe.forward(is_train=False)
        for o in exe.outputs:
            o.wait_to_read()
        return (time.time() - tic) / N
    raise ValueError(f"typ can only be 'whole' or 'forward', got {typ}")


def check_consistency(sym, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, aux_params=None, tol=None,
                      raise_on_err=True, ground_truth=None, equal_nan=False):
    """Run the symbol under several contexts/dtypes and cross-check outputs
    and gradients (reference check_consistency, test_utils.py:838).

    ctx_list entries: dict of bind kwargs including 'ctx' and optionally
    'type_dict'. On TPU the interesting axes are cpu-vs-tpu and f32-vs-bf16.
    """
    if tol is None:
        tol = {
            np.dtype(np.float16): 1e-1, np.dtype(np.float32): 1e-3,
            np.dtype(np.float64): 1e-5, np.dtype(np.uint8): 0,
            np.dtype(np.int32): 0,
        }
        try:
            import ml_dtypes

            tol[np.dtype(ml_dtypes.bfloat16)] = 1e-1
        except ImportError:
            pass
    elif isinstance(tol, (float, int)):
        tol = {d: tol for d in map(np.dtype, [np.float16, np.float32, np.float64, np.uint8, np.int32])}

    assert len(ctx_list) > 1
    if isinstance(sym, Symbol):
        sym = [sym] * len(ctx_list)
    else:
        assert len(sym) == len(ctx_list)

    output_names = sym[0].list_outputs()
    arg_names = sym[0].list_arguments()
    exe_list = []
    for s, ctx in zip(sym, ctx_list):
        assert s.list_arguments() == arg_names
        assert s.list_outputs() == output_names
        exe_list.append(s.simple_bind(grad_req=grad_req, **ctx))

    arg_params = {} if arg_params is None else arg_params
    aux_params = {} if aux_params is None else aux_params
    for n, arr in exe_list[0].arg_dict.items():
        if n not in arg_params:
            arg_params[n] = np.random.normal(
                size=arr.shape, scale=scale
            ).astype(default_dtype)
    for n, arr in exe_list[0].aux_dict.items():
        if n not in aux_params:
            aux_params[n] = 0
    for exe in exe_list:
        for name, arr in exe.arg_dict.items():
            arr[:] = array(arg_params[name].astype(np.float64).astype(np.float32)) \
                if hasattr(arg_params[name], "astype") else arg_params[name]
        for name, arr in exe.aux_dict.items():
            arr[:] = aux_params[name]

    dtypes = [np.dtype(exe.outputs[0].dtype) for exe in exe_list]
    max_idx = np.argmax([dt.num if dt.name != "bfloat16" else 11 for dt in dtypes])
    gt = ground_truth
    if gt is None:
        gt = {
            name: exe_list[max_idx].output_dict[name].asnumpy().astype(np.float64)
            for name in output_names
        }
    for exe in exe_list:
        exe.forward(is_train=False)
    for i, exe in enumerate(exe_list):
        if i == max_idx and ground_truth is None:
            continue
        rtol = tol.get(dtypes[i], 1e-3)
        atol = tol.get(dtypes[i], 1e-3)
        for name, out in zip(output_names, exe.outputs):
            try:
                assert_almost_equal(
                    out.asnumpy().astype(np.float64), gt[name], rtol=rtol,
                    atol=atol, equal_nan=equal_nan,
                )
            except AssertionError as e:
                print(f"Predict Err: ctx {i} vs ctx {max_idx} at {name}")
                print(e)
                if raise_on_err:
                    raise
    return gt


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    """One-shot forward: numpy in, numpy out (reference simple_forward)."""
    ctx = ctx or default_context()
    inputs = {k: array(v) for k, v in inputs.items()}
    exe = sym.bind(ctx, args=inputs)
    exe.forward(is_train=is_train)
    outputs = [o.asnumpy() for o in exe.outputs]
    if len(outputs) == 1:
        outputs = outputs[0]
    return outputs


@contextlib.contextmanager
def infer_memo_table(cap=0):
    """Tests only: shape and type inference against a fresh memo table of
    ``cap`` entries while the block runs. 0 stores nothing, so every
    question is evaluated anew: the path without the memo."""
    from .ops import registry

    saved = registry._MEMO
    registry._MEMO = table = registry._InferMemo(cap=cap)
    try:
        yield table
    finally:
        registry._MEMO = saved
