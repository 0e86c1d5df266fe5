"""The selective scan of a Mamba-1 mixer (Gu and Dao 2023, arXiv:2312.00752,
``selective_scan_fn``): a diagonal state-space recurrence whose step, input
and output maps depend on the token.

Per row, x (T, C) the mixer's channels, ``h`` a (C x N) state that starts at 0
and is never reset inside a row::

    delta_t[c] = softplus(dt_t[c] + dt_bias[c])            A = -exp(A_log)
    h_t[c, n]  = exp(delta_t[c] A[c, n]) h_{t-1}[c, n] + delta_t[c] B_t[n] x_t[c]
    y_t[c]     = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

Every element of the state fades by its OWN ``exp(delta_t[c] A[c, n])``: a
gate that depends on the channel and the state index at once, which
``GatedDeltaRule`` (a scalar a head, or a vector over a head's key channels,
around a rank-1 delta) cannot say; and a channel's state is N numbers, not a
matrix, so there is no product for the MXU in it: the work is the VPU's and
the EUP's (an ``exp`` an element). The gate ``y * silu(z)`` of the mixer is
the graph's, outside the operator, so that one signature serves a layer
whose scan another layer reads before the gate.

What is float32 whatever the operands' dtype: ``delta``, ``A``, every decay,
the state and the sum over it. ``y`` comes back in x's dtype.

Two forms of the same arithmetic:

* ``selective_scan_chunked``: ``jax.numpy`` and ``lax``, ``chunk`` tokens at
  a time. A chunk's states come from an associative scan over its (decay,
  input) pairs (no division by a cumulative decay, which overflows: over 64
  tokens at ``delta A`` = -1.6 a token it is e^102), the chunks from a
  ``lax.scan`` whose body is checkpointed: backward keeps a (C x N) state a
  chunk and makes a chunk's (chunk, C, N) states again. The CPU, a float32
  trunk, several chips. Of ``gated_delta.py`` it reuses ``chunks_of``; its
  ``_decay`` and cumulative-decay helpers form ``exp(c_i - c_j)`` of a
  running sum over a chunk for a Gram matrix, and there is no Gram matrix
  here (the decay of a pair sits inside the sum over n AND differs by
  channel), so they do not fit.
* ``selective_scan`` with a ``Plan``: two Pallas TPU kernels, where the rule
  (``kernel_plan``) says so. Forward: a grid step holds ``time`` rows of ALL
  the channels; the (N x C) state lives in VMEM scratch from a row's first
  position to its last (N on sublanes, channels on lanes), and ``lanes``
  channels of it ride in registers over the grid step's rows. x, dt and y
  cross HBM once; B and C arrive broadcast over 128 lanes (made by XLA:
  (T, N, 128) bfloat16, 16 MiB a tensor at T 4096, read once, since one grid
  step serves every channel), because a value that varies along sublanes and
  is constant along lanes cannot be made from a row of lanes in the kernel
  without a relayout a token. It writes the state every grid step starts
  from (T / time x N x C float32: 5 MiB at T 4096), which the operator names
  (``registry.keep``), so under per-operator recomputation the forward
  kernel runs once a step. Backward walks the grid steps from the last to
  the first: it makes a step's states again from the kept one into VMEM
  (time x N x lanes float32), then walks the rows backward with the state's
  cotangent in registers; ``dB`` and ``dC`` leave as sums over the channels
  of a lane (T, N, 128 float32: XLA adds the 128 lanes), ``dA``, ``dD`` and
  ``d dt_bias`` accumulate in output blocks that stay in VMEM over the grid.
  No (T, C, N) array reaches HBM in either pass.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_support as _ps
from .gated_delta import chunks_of
from .registry import keep

_LANES = 128
# Rows a grid step and channels a register-resident slab (the widest of
# ``_SLABS`` that divides the channels). On a v5e at (1,
# 4096, 5120), 16 states, ms forward / forward + backward: slabs of 128
# channels 1.51 / 4.87, 256 1.34 / 4.18, 512 1.10 / 3.64 (a wider slab shares
# a row's B and C over more lanes' work; its state is 8 registers of 64);
# grid steps of 128 rows 1.30 / 4.11 against 256's 1.34 / 4.18 at slabs of
# 256; 2, 4 or 8 rows a trip of the loop within 0.5% of each other (PERF.md
# section 6, PR 65).
_TIME = 256
_SLABS = (512, 256, 128)
_UNROLL = 4
# Tokens a chunk of the ``jax.numpy`` form: a chunk's (chunk, C, N) float32
# states are what it holds at once, forward and again backward.
_CHUNK = 64


def softplus(x):
    """``log(1 + exp(x))`` in the form both paths lower: no overflow."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


# --- the jax.numpy form ---------------------------------------------------------
def _combine(left, right):
    """Two steps of ``h <- a h + u`` as one, ``left`` first."""
    return left[0] * right[0], right[0] * left[1] + right[1]


@functools.partial(jax.jit, static_argnames=("chunk",))
def selective_scan_chunked(x, dt, a_log, b, c, d, dt_bias, chunk=_CHUNK):
    """y (B, T, C) in x's dtype of x, dt (B, T, C), a_log (C, N), b, c (B, T,
    N), d, dt_bias (C,): the module's equations, ``chunk`` tokens at a time.
    A T that is no multiple of ``chunk`` is padded with ``delta`` = 0: tokens
    that fade nothing and write nothing."""
    f32 = jnp.float32
    B, T, C = x.shape
    N = a_log.shape[1]
    xf = x.astype(f32)
    delta = softplus(dt.astype(f32) + dt_bias.astype(f32))
    A = -jnp.exp(a_log.astype(f32))
    n = chunks_of(T, chunk)
    pad = n * chunk - T

    def chunks(z):
        """(B, T, W) -> (n, B, chunk, W), zeros after the end."""
        z = jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z
        return z.reshape(B, n, chunk, z.shape[-1]).swapaxes(0, 1)

    @jax.checkpoint
    def step(h, blk):
        dl, u, bl, cl = blk
        decay = jnp.exp(dl[..., None] * A)                    # (B, L, C, N)
        write = u[..., None] * bl[:, :, None, :]
        fade, own = lax.associative_scan(_combine, (decay, write), axis=1)
        hs = fade * h[:, None] + own
        return hs[:, -1], jnp.einsum("blcn,bln->blc", hs, cl,
                                     precision=lax.Precision.HIGHEST)

    _, ys = lax.scan(step, jnp.zeros((B, C, N), f32),
                     (chunks(delta), chunks(delta * xf),
                      chunks(b.astype(f32)), chunks(c.astype(f32))))
    y = ys.swapaxes(0, 1).reshape(B, n * chunk, C)[:, :T]
    return (y + d.astype(f32) * xf).astype(x.dtype)


# --- the rule -------------------------------------------------------------------
class Plan(NamedTuple):
    """A grid step takes ``time`` rows of every channel and walks them with
    ``lanes`` channels of the state in registers."""

    time: int
    lanes: int
    vmem_limit: int


def kernel_plan(dtype, x_shape, states, platform=None) -> Optional[Plan]:
    """The rule: the kernels' blocks for a ``SelectiveScan`` over ``data`` of
    ``x_shape`` (B, T, C) and ``dtype`` with ``states`` numbers a channel in
    a program lowered for ``platform`` (the executor's, through
    ``OpMode.platform``; None: jax's default backend), or None: the
    ``jax.numpy`` form. They engage where the program is lowered for the one
    TPU the process holds (XLA cannot partition a Mosaic call over several),
    ``data`` is bfloat16 (a float32 trunk keeps the ``jax.numpy`` form), 128
    divides the channels, and the state of a channel is one or two float32
    registers' sublanes (8 or 16). The op and its launch counts ask it with
    the same arguments."""
    vmem = _ps.attached_vmem_bytes()
    if (platform or jax.default_backend()) != "tpu" or not vmem:
        return None
    B, T, C = x_shape
    if (jnp.dtype(dtype) != jnp.bfloat16 or C % _LANES
            or states not in (8, 16)):
        return None
    lanes = next(w for w in _SLABS if C % w == 0)
    time = min(_TIME, -(-T // 16) * 16)
    # backward: x, dt, dy, dx, d dt (bfloat16) and B, C with their float32
    # sums, two buffers each; the slab's states again; the state, its
    # cotangent and the (N, C) accumulators; and room for the rest
    need = 5 * 2 * time * C * 2 + 2 * 2 * time * states * _LANES * (2 + 4) \
        + (time + 1) * states * lanes * 4 + 8 * time * lanes * 4 \
        + 6 * states * C * 4 + (8 << 20)
    if need > vmem * 3 // 4:
        return None
    return Plan(time, lanes, need)


# --- the kernels ----------------------------------------------------------------
def _padded(z, plan):
    """z (B, T, ...) with T padded to whole grid steps, zeros after the end:
    rows after the last real one, which no real row reads and whose
    cotangent is zero."""
    pad = -z.shape[1] % plan.time
    return jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2)) \
        if pad else z


def _over_lanes(z):
    """(B, T, N) -> (B, T, N, 128), a number the same over a register's
    lanes."""
    return jnp.broadcast_to(z[..., None], z.shape + (_LANES,))


def _slabs(lanes):
    """The lanes of slab s of a ref."""
    pl, _ = _ps._pallas()
    return lambda s: pl.ds(pl.multiple_of(s * lanes, lanes), lanes)


def _for_rows(time, step, carry):
    """``carry = step(t, carry)`` for t = 0 .. time - 1, ``_UNROLL`` rows a
    trip of the loop (Mosaic unrolls a loop wholly or not at all)."""
    def trip(i, carry):
        for j in range(_UNROLL):
            carry = step(i * _UNROLL + j, carry)
        return carry

    return lax.fori_loop(0, time // _UNROLL, trip, carry)


def _for_slabs(count, slab):
    """``slab(s)`` for s = 0 .. count - 1, in order, as one loop whose steps
    store into the kernel's refs."""
    lax.fori_loop(0, count, lambda s, carry: slab(s), None)


def _row(ref, t):
    pl, _ = _ps._pallas()
    return ref[pl.ds(t, 1), :]


def _tiled(ref, t, lanes):
    """Row t of a (time, N, 128) block as float32 (N, lanes)."""
    return jnp.tile(ref[t].astype(jnp.float32), (1, lanes // _LANES))


def _folded(z):
    """(N, lanes) -> (N, 128): the lane groups added."""
    return functools.reduce(jnp.add, [
        z[:, g * _LANES:(g + 1) * _LANES]
        for g in range(z.shape[1] // _LANES)])


def _cost(x, states, passes):
    pl, _ = _ps._pallas()
    return pl.CostEstimate(
        flops=9 * x.size * states * passes,
        transcendentals=x.size * states * passes,
        bytes_accessed=x.size * x.dtype.itemsize * (3 if passes == 1 else 7))


@functools.partial(jax.jit, static_argnames=(
    "time", "lanes", "vmem_limit", "interpret"))
def _fwd(x, dt, bb, cb, a, d, bias, *, time, lanes, vmem_limit, interpret):
    """(y in x's shape and dtype, the state every grid step starts from (B,
    T / time, N, C) float32): x, dt (B, T, C) with T whole grid steps, bb, cb
    (B, T, N, 128), a (N, C) = A transposed, d, bias (1, C) float32."""
    pl, pltpu = _ps._pallas()
    B, T, C = x.shape
    N = a.shape[0]
    f32 = jnp.float32
    at = _slabs(lanes)

    def kernel(x_ref, dt_ref, bb_ref, cb_ref, a_ref, d_ref, bias_ref,
               y_ref, st_ref, h, delta, dtx, yacc):
        @pl.when(pl.program_id(1) == 0)
        def _():
            h[...] = jnp.zeros_like(h)

        st_ref[...] = h[...]

        def slab(s):
            ch = at(s)
            xs = x_ref[:, ch].astype(f32)
            dl = softplus(dt_ref[:, ch].astype(f32) + bias_ref[:, ch])
            delta[...] = dl
            dtx[...] = dl * xs
            A = a_ref[:, ch]

            def step(t, hs):
                hs = jnp.exp(_row(delta, t) * A) * hs \
                    + _row(dtx, t) * _tiled(bb_ref, t, lanes)
                yacc[pl.ds(t, 1), :] = jnp.sum(
                    hs * _tiled(cb_ref, t, lanes), axis=0, keepdims=True)
                return hs

            h[:, ch] = _for_rows(time, step, h[:, ch])
            y_ref[:, ch] = (yacc[...] + d_ref[:, ch] * xs).astype(y_ref.dtype)

        _for_slabs(C // lanes, slab)

    rows = pl.BlockSpec((None, time, C), lambda b, i: (b, i, 0))
    wide = pl.BlockSpec((None, time, N, _LANES), lambda b, i: (b, i, 0, 0))
    whole = pl.BlockSpec((N, C), lambda b, i: (0, 0))
    one = pl.BlockSpec((1, C), lambda b, i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, T // time, N, C), f32)),
        grid=(B, T // time),
        in_specs=[rows, rows, wide, wide, whole, one, one],
        out_specs=[rows, pl.BlockSpec((None, None, N, C),
                                      lambda b, i: (b, i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((N, C), f32),
                        pltpu.VMEM((time, lanes), f32),
                        pltpu.VMEM((time, lanes), f32),
                        pltpu.VMEM((time, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(x, N, 1),
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, dt, bb, cb, a, d, bias)


@functools.partial(jax.jit, static_argnames=(
    "time", "lanes", "vmem_limit", "interpret"))
def _bwd(x, dt, bb, cb, a, d, bias, states, dy, *, time, lanes, vmem_limit,
         interpret):
    """(dx, d dt in x's shape and dtype; d bb, d cb (B, T, N, 128) float32,
    sums over the channels of a lane; dA (N, C), dD, d bias (1, C) float32):
    the grid steps of a row from the last to the first, each making its
    states again from ``states``' entry."""
    pl, pltpu = _ps._pallas()
    B, T, C = x.shape
    N = a.shape[0]
    blocks = T // time
    f32 = jnp.float32
    at = _slabs(lanes)

    def kernel(x_ref, dt_ref, bb_ref, cb_ref, a_ref, d_ref, bias_ref, st_ref,
               dy_ref, dx_ref, ddt_ref, dbb_ref, dcb_ref, da_ref, dd_ref,
               dbias_ref, g, hist, delta, dtx, dyf, us, vs):
        @pl.when(pl.program_id(1) == 0)
        def _():
            g[...] = jnp.zeros_like(g)

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            da_ref[...] = jnp.zeros_like(da_ref)
            dd_ref[...] = jnp.zeros_like(dd_ref)
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

        dbb_ref[...] = jnp.zeros_like(dbb_ref)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

        def slab(s):
            ch = at(s)
            xs = x_ref[:, ch].astype(f32)
            raw = dt_ref[:, ch].astype(f32) + bias_ref[:, ch]
            dl = softplus(raw)
            gy = dy_ref[:, ch].astype(f32)
            delta[...] = dl
            dtx[...] = dl * xs
            dyf[...] = gy
            A = a_ref[:, ch]

            # the states of the step again: hist[t + 1] = h_t
            def again(t, hs):
                hs = jnp.exp(_row(delta, t) * A) * hs \
                    + _row(dtx, t) * _tiled(bb_ref, t, lanes)
                hist[t + 1] = hs
                return hs

            hist[0] = st_ref[:, ch]
            _for_rows(time, again, hist[0])

            def back(k, carry):
                """``later``: the cotangent of h_t from the rows after t."""
                later, da = carry
                t = time - 1 - k
                dlt, gyt = _row(delta, t), _row(dyf, t)
                gt = later + _tiled(cb_ref, t, lanes) * gyt
                ga = gt * jnp.exp(dlt * A)         # into h_{t-1}
                faded = ga * hist[t]               # d / d(delta_t A)
                us[pl.ds(t, 1), :] = jnp.sum(
                    gt * _tiled(bb_ref, t, lanes), axis=0, keepdims=True)
                vs[pl.ds(t, 1), :] = jnp.sum(faded * A, axis=0,
                                             keepdims=True)
                dbb_ref[t] += _folded(gt * _row(dtx, t))
                dcb_ref[t] += _folded(hist[t + 1] * gyt)
                return ga, da + faded * dlt

            later, da = _for_rows(
                time, back, (g[:, ch], jnp.zeros((N, lanes), f32)))
            g[:, ch] = later
            da_ref[:, ch] += da
            u = us[...]
            ddt = (xs * u + vs[...]) * jax.nn.sigmoid(raw)
            ddt_ref[:, ch] = ddt.astype(ddt_ref.dtype)
            dx_ref[:, ch] = (dl * u + d_ref[:, ch] * gy).astype(dx_ref.dtype)
            dbias_ref[:, ch] += jnp.sum(ddt, axis=0, keepdims=True)
            dd_ref[:, ch] += jnp.sum(gy * xs, axis=0, keepdims=True)

        _for_slabs(C // lanes, slab)

    rows = pl.BlockSpec((None, time, C), lambda b, i: (b, blocks - 1 - i, 0))
    wide = pl.BlockSpec((None, time, N, _LANES),
                        lambda b, i: (b, blocks - 1 - i, 0, 0))
    whole = pl.BlockSpec((N, C), lambda b, i: (0, 0))
    one = pl.BlockSpec((1, C), lambda b, i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, dt.dtype),
                   jax.ShapeDtypeStruct(bb.shape, f32),
                   jax.ShapeDtypeStruct(cb.shape, f32),
                   jax.ShapeDtypeStruct((N, C), f32),
                   jax.ShapeDtypeStruct((1, C), f32),
                   jax.ShapeDtypeStruct((1, C), f32)),
        grid=(B, blocks),
        in_specs=[rows, rows, wide, wide, whole, one, one,
                  pl.BlockSpec((None, None, N, C),
                               lambda b, i: (b, blocks - 1 - i, 0, 0)),
                  rows],
        out_specs=[rows, rows, wide, wide, whole, one, one],
        scratch_shapes=[pltpu.VMEM((N, C), f32),
                        pltpu.VMEM((time + 1, N, lanes), f32)]
        + [pltpu.VMEM((time, lanes), f32)] * 5,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(x, N, 3),
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, dt, bb, cb, a, d, bias, states, dy)


# --- what SelectiveScan calls ------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def selective_scan(x, dt, a_log, b, c, d, dt_bias, plan, interpret=False):
    """The module's equations in the Pallas kernels at ``plan``'s blocks: x,
    dt (B, T, C) bfloat16, a_log (C, N), b, c (B, T, N), d, dt_bias (C,) ->
    y (B, T, C) in x's dtype. Backward keeps the operands and the state
    every grid step started from, which the operator names
    (``registry.keep``): under per-operator recomputation
    (``MXNET_BACKWARD_DO_MIRROR``) the forward kernel runs once a step.
    ``interpret`` runs the kernels in Pallas's interpreter (tests on the
    CPU)."""
    return _scan_fwd(x, dt, a_log, b, c, d, dt_bias, plan, interpret)[0]


def _operands(x, dt, a_log, b, c, d, dt_bias, plan):
    f32 = jnp.float32
    return (_padded(x, plan), _padded(dt.astype(x.dtype), plan),
            _over_lanes(_padded(b.astype(x.dtype), plan)),
            _over_lanes(_padded(c.astype(x.dtype), plan)),
            -jnp.exp(a_log.astype(f32)).T, d.astype(f32)[None],
            dt_bias.astype(f32)[None])


def _static(plan, interpret):
    return dict(plan._asdict(), interpret=interpret)


def _scan_fwd(x, dt, a_log, b, c, d, dt_bias, plan, interpret):
    y, states = _ps._kernel(
        _fwd, _operands(x, dt, a_log, b, c, d, dt_bias, plan),
        **_static(plan, interpret))
    return y[:, :x.shape[1]], (x, dt, a_log, b, c, d, dt_bias, keep(states))


def _scan_bwd(plan, interpret, res, dy):
    x, dt, a_log, b, c, d, dt_bias, states = res
    T = x.shape[1]
    operands = _operands(x, dt, a_log, b, c, d, dt_bias, plan)
    dx, ddt, dbb, dcb, da, dd, dbias = _ps._kernel(
        _bwd, (*operands, states, _padded(dy.astype(x.dtype), plan)),
        **_static(plan, interpret))
    # A = -exp(A_log): dA/dA_log = A
    return (dx[:, :T], ddt[:, :T].astype(dt.dtype),
            (da * operands[4]).T.astype(a_log.dtype),
            dbb.sum(-1)[:, :T].astype(b.dtype),
            dcb.sum(-1)[:, :T].astype(c.dtype),
            dd[0].astype(d.dtype), dbias[0].astype(dt_bias.dtype))


selective_scan.defvjp(_scan_fwd, _scan_bwd)
