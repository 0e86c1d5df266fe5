"""The depthwise causal convolution as Pallas TPU kernels: every (B, T, C)
array crosses HBM once in each pass.

``causal_conv(x, w, bias, act, plan)`` is the depthwise form of
``CausalConv1D`` (``ops/defs_transformer._causal_conv1d``, ``num_group`` 0)
where the rule (``kernel_plan``) says so: x (B, T, C) bfloat16, channels last,
w (C, K), bias (C,) or None -> ``act(bias + sum_j w[:, j] x_{t-K+1+j})`` in
x's dtype, ``x_{<0} = 0``. The arithmetic is the ``jax.numpy`` form's and no
other: x, the taps and the bias float32, the K products added in the order
tap 0 first, the bias, the activation in float32, one rounding.

What the kernels do that the ``jax.numpy`` form does not:

* Forward reads x and writes y, once each. A grid step holds a (time x
  channels) block; a loop inside walks it ``rows`` rows at a time, 128
  channels wide, in vector registers: the rows are cast to float32 once and
  the K - 1 shifts along time are sublane rotations of that float32 tile
  (a bfloat16 register packs 16 rows: it is never sliced at a row that is
  no multiple of 16). The 8 rows before a tile are carried: in registers
  from one tile to the next, in VMEM scratch from one grid step to the next
  along the time axis, which is walked in order.
* Backward is one kernel that reads x and dy and writes dx, once each, and
  keeps nothing but the operator's inputs: it walks time from the last block
  to the first, makes a tile's float32 pre-activation again (the 16 rows
  before a block come through a second small ``BlockSpec`` on x), ``dpre =
  dy * act'(pre)``, and the K shifts of ``dpre`` TOWARD earlier rows, which
  both ``dx_t = sum_s w[:, K-1-s] dpre_{t+s}`` and ``dw[:, K-1-s] = sum_t
  dpre_{t+s} x_t`` read; the 8 rows of ``dpre`` after a tile are carried as
  forward carries x. ``dw`` and ``dbias`` accumulate in float32, 8 partial
  sums a channel in registers over a block, in an output block that stays in
  VMEM over the batch and the time axis and is written once a channel block.
  The ``jax.numpy`` form's transpose costs XLA the pad's transpose at K
  offsets, K column reductions over T and the pre-activation, each an array
  pass, some of them float32: eight to nine times the bytes' time on a v5e
  (PERF.md section 6, PR 46).

``kernel_plan`` is the one rule that says whether the kernels engage and with
which blocks, as ``gated_delta.kernel_plan`` is the gated delta rule's;
traced kernels are kept by ``pallas_support._kernel``'s store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_support as _ps

_LANES = 128
# Rows carried from one tile to the next: one float32 register's sublanes,
# so a kernel holds at most 9 taps.
_HALO = 8
# Rows of the small block that hands backward the rows before a time block:
# a bfloat16 register's.
_PACKED = 16
# Rows a tile, time rows and channels a grid step. On a v5e at (1, 8192,
# 8192), 4 taps, SiLU, ms forward / backward: tiles of 16 rows 1.13 / 1.58,
# 32 0.65 / 1.06, 64 0.51 / 0.85, 128 0.46 / 0.84, 256 0.47 / 0.93 (the 8
# carried rows are worked again with every tile; past 128 the tile no longer
# fits the registers); blocks of 512 to 4096 rows and 256 to 1024 channels
# read within 3% of each other, 128 channels 6% slower (PERF.md section 6,
# PR 46).
_ROWS = 128
_TIME = 2048
_CHANNELS = (512, 256, 128)
ACTS = {"silu": jax.nn.silu, "none": lambda x: x}


class Plan(NamedTuple):
    """A grid step takes ``time`` rows of ``channels`` channels and walks
    them ``rows`` rows at a time."""

    time: int
    channels: int
    rows: int
    vmem_limit: int


def kernel_plan(dtype, x_shape, taps, platform=None,
                num_group=0) -> Optional[Plan]:
    """The rule: the kernels' blocks for a ``CausalConv1D`` of ``taps`` taps
    over ``data`` of ``x_shape`` (B, T, C) and ``dtype`` in a program lowered
    for ``platform`` (the executor's, through ``OpMode.platform``; None:
    jax's default backend), or None: the ``jax.numpy`` form. They engage
    where the program is lowered for the one TPU the process holds (XLA
    cannot partition a Mosaic call over several), the convolution is
    depthwise (``num_group`` 0), ``data`` is bfloat16 (a float32 trunk keeps
    the ``jax.numpy`` form), 128 divides the channels, the taps before t
    fit the carried rows, and ``data`` is at least a quarter of the chip's
    VMEM: a smaller array XLA can hold there between its fusions, where the
    ``jax.numpy`` form's passes cost less than HBM's and fuse with the
    nodes around them, while a Mosaic call reads and writes HBM (on a v5e,
    128 MiB: at 20 MiB, ZAYA1's 1280 channels, the form runs forward and
    backward in 0.10 ms, under the 0.13 its bytes would take across HBM,
    and the cell's step is 1.4 ms longer with the kernels; at 40 MiB, T 4096
    over a Mamba mixer's 5120 channels, forward / forward + backward 0.33 /
    1.35 ms against the kernels' 0.23 / 0.58; at 128 MiB, Qwen3-Next's 8192
    channels, 5.04 ms against the kernels' 1.28; PERF.md section 6, PR 46
    and PR 65: the threshold was a half until PR 65 measured between the
    two, and no other cell's convolution lies between a quarter and a
    half). The op and its launch counts ask it with the same
    arguments."""
    vmem = _ps.attached_vmem_bytes()
    if (platform or jax.default_backend()) != "tpu" or not vmem:
        return None
    B, T, C = x_shape
    if (num_group or jnp.dtype(dtype) != jnp.bfloat16 or C % _LANES
            or not 1 <= taps <= _HALO + 1 or B * T * C * 2 < vmem // 4):
        return None
    channels = next(c for c in _CHANNELS if C % c == 0)
    # the fewest blocks of at most _TIME rows, T padded to whole tiles
    blocks = -(-T // _TIME)
    time = -(-T // (blocks * _ROWS)) * _ROWS
    # backward: x, dy and dx, two buffers each, and room for the rest
    need = 3 * 2 * time * channels * 2 + (8 << 20)
    return Plan(time, channels, _ROWS, min(vmem * 3 // 4, need))


def _moved(x, by):
    """``x`` (rows, lanes) float32 with row t at row t + ``by``, the rows
    that leave at one end coming back at the other."""
    _, pltpu = _ps._pallas()
    return pltpu.roll(x, by % x.shape[0], 0) if by else x


def _taps(w_ref, lanes, rows):
    """The K taps of a lane group, each one row broadcast over a tile."""
    return [jnp.broadcast_to(w_ref[j:j + 1, lanes], (rows, _LANES))
            for j in range(w_ref.shape[0])]


def _pre(before, cur, w, bias):
    """The float32 pre-activation of a tile ``cur`` (rows, lanes) whose 8
    rows before are ``before``: the products in the order tap 0 first."""
    K = len(w)
    ext = jnp.concatenate([before, cur], axis=0)
    out = None
    for j in range(K):
        term = _moved(ext, K - 1 - j)[_HALO:] * w[j]
        out = term if out is None else out + term
    return out if bias is None else out + bias


def _padded(x, plan):
    """x (B, T, C) with T padded to whole grid steps, zeros after the end:
    rows no real row reads, whose cotangent is zero."""
    pad = -x.shape[1] % plan.time
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _cost(x, taps, passes, act):
    pl, _ = _ps._pallas()
    return pl.CostEstimate(
        flops=x.size * (2 * taps + 8) * passes,
        transcendentals=x.size * (act == "silu"),
        bytes_accessed=x.size * x.dtype.itemsize * (passes + 1))


# --- forward -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "act", "time", "channels", "rows", "vmem_limit", "interpret"))
def _fwd(x, wt, bias, *, act, time, channels, rows, vmem_limit, interpret):
    """y in x's shape and dtype: x (B, T, C) with T whole grid steps, wt (K,
    C) and bias (1, C) or None float32."""
    pl, pltpu = _ps._pallas()
    B, T, C = x.shape
    K = wt.shape[0]
    f = ACTS[act]
    biased = bias is not None

    def kernel(*refs):
        refs = iter(refs)
        x_ref, w_ref = next(refs), next(refs)
        b_ref = next(refs) if biased else None
        y_ref, halo = refs

        @pl.when(pl.program_id(2) == 0)
        def _():
            halo[...] = jnp.zeros_like(halo)

        for g in range(channels // _LANES):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            w = _taps(w_ref, lanes, rows)
            b = None if b_ref is None else jnp.broadcast_to(
                b_ref[:, lanes], (rows, _LANES))

            def tile(n, before):
                at = pl.ds(pl.multiple_of(n * rows, rows), rows)
                cur = x_ref[at, lanes].astype(jnp.float32)
                out = f(_pre(before, cur, w, b)).astype(y_ref.dtype)
                y_ref[at, lanes] = out  # graftlint: allow=trace-purity(a store into a Pallas output ref is the kernel's output, not Python state)
                return cur[rows - _HALO:]

            halo[:, lanes] = lax.fori_loop(0, time // rows, tile,
                                           halo[:, lanes])

    block = pl.BlockSpec((None, time, channels), lambda b, c, i: (b, i, c))
    row = pl.BlockSpec((wt.shape[0], channels), lambda b, c, i: (0, c))
    one = pl.BlockSpec((1, channels), lambda b, c, i: (0, c))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(B, C // channels, T // time),
        in_specs=[block, row] + [one] * biased,
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((_HALO, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(x, K, 1, act),
        interpret=interpret,
        name="causal_conv_fwd",
    )(x, wt, *[bias] * biased)


# --- backward ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "act", "time", "channels", "rows", "vmem_limit", "interpret"))
def _bwd(x, wt, bias, dy, *, act, time, channels, rows, vmem_limit,
         interpret):
    """(dx in x's shape and dtype, dwt (K, C) and dbias (1, C) or None
    float32): the time blocks of a (channel block, batch) from the last to
    the first. With ``act`` ``none`` the pre-activation is not made again
    and the rows before a block are not read."""
    pl, pltpu = _ps._pallas()
    B, T, C = x.shape
    K = wt.shape[0]
    blocks = T // time
    tiles = time // rows
    again = act != "none"
    biased = bias is not None

    def kernel(*refs):
        refs = iter(refs)
        x_ref, dy_ref, w_ref = next(refs), next(refs), next(refs)
        before_ref = next(refs) if again else None
        b_ref = next(refs) if biased else None
        dx_ref, dw_ref = next(refs), next(refs)
        db_ref = next(refs) if biased else None
        after, = refs
        first = pl.program_id(2) == blocks - 1   # the row's first block

        @pl.when(pl.program_id(2) == 0)
        def _():
            after[...] = jnp.zeros_like(after)

        @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)
            if db_ref is not None:
                db_ref[...] = jnp.zeros_like(db_ref)

        for g in range(channels // _LANES):
            lanes = slice(g * _LANES, (g + 1) * _LANES)
            w = _taps(w_ref, lanes, rows)
            b = jnp.broadcast_to(b_ref[:, lanes], (rows, _LANES)) \
                if again and biased else None

            def tile(n, before, carry):
                """The tile ``n`` of the block, ``before`` the 16 rows of x
                before it as stored; ``carry``: the 8 rows of dpre after
                it, and the partial sums of dw (and dbias)."""
                dpre_after, sums = carry[0], carry[1:]
                at = pl.ds(pl.multiple_of(n * rows, rows), rows)
                cur = x_ref[at, lanes].astype(jnp.float32)
                dpre = dy_ref[at, lanes].astype(jnp.float32)
                if again:
                    pre = _pre(before.astype(jnp.float32)[_PACKED - _HALO:],
                               cur, w, b)
                    sig = jax.nn.sigmoid(pre)
                    dpre = dpre * (sig * (1.0 + pre * (1.0 - sig)))
                ext = jnp.concatenate([dpre, dpre_after], axis=0)
                moved = [_moved(ext, -s)[:rows] for s in range(K)]
                dx = moved[0] * w[K - 1]
                for s in range(1, K):
                    dx = dx + moved[s] * w[K - 1 - s]
                dx_ref[at, lanes] = dx.astype(dx_ref.dtype)

                def folded(t):
                    return functools.reduce(
                        jnp.add, [t[r:r + _HALO]
                                  for r in range(0, rows, _HALO)])

                sums = [acc + folded(moved[K - 1 - j] * cur)
                        for j, acc in enumerate(sums[:K])] \
                    + [acc + folded(dpre) for acc in sums[K:]]
                return (dpre[:_HALO], *sums)

            zero = jnp.zeros((_HALO, _LANES), jnp.float32)
            carry = (after[:, lanes],) + (zero,) * (K + biased)

            def inner(i, carry):
                n = tiles - 1 - i
                before = x_ref[pl.ds(pl.multiple_of(
                    n * rows - _PACKED, _PACKED), _PACKED), lanes] \
                    if again else None
                return tile(n, before, carry)

            carry = lax.fori_loop(0, tiles - 1, inner, carry)
            before = None
            if again:
                before = before_ref[:, lanes]
                before = jnp.where(first, jnp.zeros_like(before), before)
            carry = tile(0, before, carry)
            after[:, lanes] = carry[0]
            for j in range(K):
                dw_ref[j:j + 1, lanes] += jnp.sum(carry[1 + j], axis=0,
                                                  keepdims=True)
            if db_ref is not None:
                db_ref[:, lanes] += jnp.sum(carry[1 + K], axis=0,
                                            keepdims=True)

    block = pl.BlockSpec((None, time, channels),
                         lambda c, b, i: (b, blocks - 1 - i, c))
    # the 16 rows before the block; the first block's are masked
    before = pl.BlockSpec(
        (None, _PACKED, channels),
        lambda c, b, i: (b, jnp.maximum(
            (blocks - 1 - i) * (time // _PACKED) - 1, 0), c))
    row = pl.BlockSpec((K, channels), lambda c, b, i: (0, c))
    one = pl.BlockSpec((1, channels), lambda c, b, i: (0, c))
    out = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((K, C), jnp.float32))
        + (jax.ShapeDtypeStruct((1, C), jnp.float32),) * biased,
        grid=(C // channels, B, blocks),
        in_specs=[block, block, row] + [before] * again + [one] * biased,
        out_specs=[block, row] + [one] * biased,
        scratch_shapes=[pltpu.VMEM((_HALO, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=_cost(x, K, 2 + again, act),
        interpret=interpret,
        name="causal_conv_bwd",
    )(x, dy, wt, *[x] * again, *[bias] * biased)
    return out if biased else (*out, None)


# --- what CausalConv1D calls ---------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_conv(x, w, bias, act, plan, interpret=False):
    """The depthwise causal convolution of x (B, T, C) with w (C, K) and
    bias (C,) or None, then ``act`` (``silu`` or ``none``), in x's dtype: the
    forward kernel at ``plan``'s blocks. Backward keeps the operands and
    nothing else, so under per-operator recomputation
    (``MXNET_BACKWARD_DO_MIRROR``) the forward that runs again has no live
    output. ``interpret`` runs the kernels in Pallas's interpreter (tests on
    the CPU)."""
    return _conv_fwd(x, w, bias, act, plan, interpret)[0]


def _operands(w, bias):
    """The taps a row a tap and the bias one row, float32."""
    return (w.astype(jnp.float32).T,
            None if bias is None else bias.astype(jnp.float32)[None])


def _static(act, plan, interpret):
    return dict(plan._asdict(), act=act, interpret=interpret)


def _conv_fwd(x, w, bias, act, plan, interpret):
    y = _ps._kernel(_fwd, (_padded(x, plan), *_operands(w, bias)),
                     **_static(act, plan, interpret))
    return y[:, :x.shape[1]], (x, w, bias)


def _conv_bwd(act, plan, interpret, res, dy):
    x, w, bias = res
    dx, dwt, dbias = _ps._kernel(
        _bwd, (_padded(x, plan), *_operands(w, bias),
               _padded(dy.astype(x.dtype), plan)),
        **_static(act, plan, interpret))
    return (dx[:, :x.shape[1]], dwt.T.astype(w.dtype),
            None if bias is None else dbias[0].astype(bias.dtype))


causal_conv.defvjp(_conv_fwd, _conv_bwd)
