"""Transformer-block operators: ``RMSNorm``, ``RotaryEmbedding``, the
sparse-expert layer ``MoE``, the linear-attention pair ``CausalConv1D``
and ``GatedDeltaRule`` (``gated_delta.py``) and the state-space mixer's
``SelectiveScan`` (``selective_scan.py``). (Attention is ``RingAttention``
in ``defs_contrib.py``, whose one-device path is blockwise.)

No reference twin: MXNet 0.x has none of them. The equations are those of
the public OLMoE model (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe.py``). All are plain jax lowered by XLA, but where an
operator's rule says its Pallas kernels engage, asked with the platform the
program is lowered for (``OpMode.platform``): the grouped matmuls of ``MoE``
(``grouped_matmul.py``, else ``jax.lax.ragged_dot``) and the two row sums of
its held rounds (``row_sum_kernels.py``, else XLA's scatter-add),
``GatedDeltaRule``, ``SelectiveScan``,
the depthwise ``CausalConv1D`` and ``RotaryEmbedding`` (``rotary_kernels.py``:
one TPU, a bfloat16 ``data`` of at least half the chip's VMEM, the size from
which a v5e no longer holds the array between XLA's fusions, whose heads of
128 turn whole in rotate-half pairs; PERF.md section 6, PR 59). Each of the
five also declares, beside its ``fn`` and asking the same rule with the same
arguments, what one launch of a train program that holds it counts
(``OpDef.launch_counts``). Where the whole head turns ``RotaryEmbedding``
is differentiated by no autodiff, in either form: its backward is the
rotation by the negated angle.

What is float32 whatever the trunk's dtype: the statistics of ``RMSNorm``,
the angles and the rotation of ``RotaryEmbedding``, in ``MoE`` the
router (logits, softmax, top-k, both regularisers), and in
``GatedDeltaRule`` the decays (a head or a key channel), ``beta``, the
chunks' triangular inverse and the state, and in ``SelectiveScan`` the
steps, the decays, the state and the sum over it. Outputs come back in the
dtype of ``data``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..base import (MXNetError, parse_bool, parse_float, parse_int,
                    parse_str)
from . import causal_conv_kernels as _cck
from . import gated_delta as _gdr
from . import grouped_matmul as _gmm
from . import pallas_support as _ps
from . import rotary_kernels as _rk
from . import selective_scan as _ssm
from . import row_sum_kernels as _rs
from .defs_nn import _castp, _prec
from .registry import Param, keep, register


# --- RMSNorm ---------------------------------------------------------------
def _rms_norm(ins, params, mode):
    """``x * rsqrt(mean(x^2, last axis) + eps) * gamma``."""
    x, gamma = ins
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + params["eps"]) * gamma.astype(jnp.float32)
    return out.astype(x.dtype)


register(
    "RMSNorm",
    _rms_norm,
    arg_names=["data", "gamma"],
    param_schema={"eps": Param(parse_float, 1e-5)},
    fill_in_shapes=lambda shapes, p: [
        shapes[0],
        shapes[1] or (shapes[0] and (shapes[0][-1],)),
    ],
)


# --- LayerNorm -------------------------------------------------------------
def _layer_norm(ins, params, mode):
    """``(x - mean) * rsqrt(var + eps) * gamma + beta`` over ``axis``
    (MXNet's later ``LayerNorm``: ``axis``, ``eps``, a gain and a bias of
    that axis' length); the statistics in float32 as ``RMSNorm``'s."""
    x, gamma, beta = ins
    axis = params["axis"] % x.ndim
    along = [1] * x.ndim
    along[axis] = x.shape[axis]
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    centred = xf - mean
    var = jnp.mean(centred * centred, axis=axis, keepdims=True)
    out = centred * jax.lax.rsqrt(var + params["eps"]) \
        * gamma.astype(jnp.float32).reshape(along) \
        + beta.astype(jnp.float32).reshape(along)
    return out.astype(x.dtype)


def _layer_norm_fill(shapes, p):
    width = shapes[0] and (shapes[0][p["axis"] % len(shapes[0])],)
    return [shapes[0], shapes[1] or width, shapes[2] or width]


register(
    "LayerNorm",
    _layer_norm,
    arg_names=["data", "gamma", "beta"],
    param_schema={"axis": Param(parse_int, -1),
                  "eps": Param(parse_float, 1e-5)},
    fill_in_shapes=_layer_norm_fill,
)


# --- RotaryEmbedding -------------------------------------------------------
class _Schedule(NamedTuple):
    """What a node's tables are made from: pair ``i`` of a head of ``2 half``
    turns by ``t * f_i`` and cos and sin carry the amplitude ``a``. With
    ``scaling`` "" ``f_i = base^(-i/half)`` and ``a = 1`` (the other fields
    are then left at these defaults, so that every such node of a ``base``
    asks for the same tables). "yarn" (Peng et al. 2023, arXiv:2309.00071;
    ``transformers``' ``_compute_yarn_parameters`` with ``truncate``): with
    ``d = 2 half``, ``L = original_max_position``, ``c(r) = d ln(L / (2 pi
    r)) / (2 ln base)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
    min(ceil(c(beta_slow)), d - 1)`` and ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``: ``f_i = (1 - ramp_i) base^(-i/half) + ramp_i
    base^(-i/half) / factor``, the fast pairs as they were trained and the
    slow ones stretched ``factor`` times, and ``a = attention_factor`` (0:
    ``0.1 ln(factor) + 1``)."""

    base: float
    scaling: str = ""
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0

    def inv_freq(self, half):
        """``f_i`` (half,) float64."""
        plain = self.base ** (-np.arange(half, dtype=np.float64) / half)
        if not self.scaling:
            return plain
        d = 2 * half

        def pair_of(rotations):     # the pair that turns so often over L
            return d * math.log(self.original_max_position / (
                rotations * 2 * math.pi)) / (2 * math.log(self.base))

        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), d - 1)
        high += 0.001 * (low == high)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        return (1.0 - ramp) * plain + ramp * plain / self.factor

    def amplitude(self):
        if not self.scaling:
            return 1.0
        return self.attention_factor or 0.1 * math.log(self.factor) + 1.0


def _schedule(params):
    """A node's :class:`_Schedule`, or an ``MXNetError`` for one the
    operator does not define."""
    scaling = params["scaling"]
    if not scaling:
        return _Schedule(params["base"])
    if scaling != "yarn":
        raise MXNetError(f"RotaryEmbedding: scaling {scaling!r} (\"\" or "
                         "\"yarn\")")
    schedule = _Schedule(params["base"], scaling, params["factor"],
                         params["original_max_position"],
                         params["beta_fast"], params["beta_slow"],
                         params["attention_factor"])
    if schedule.factor < 1.0 or schedule.original_max_position <= 0 \
            or not 0.0 < schedule.beta_slow <= schedule.beta_fast \
            or schedule.attention_factor < 0.0:
        raise MXNetError(f"RotaryEmbedding: {schedule}")
    return schedule


@functools.lru_cache(maxsize=16)
def _rotary_tables(t, half, schedule, lanes=False):
    """``a cos`` and ``a sin`` (t, half) float32 of the angles ``t * f_i``
    of ``schedule`` (a :class:`_Schedule`: the frequencies and the
    amplitude), made on the host (``_rotary``'s docstring says in which
    precision); ``lanes``: as the kernel reads them
    (``rotary_kernels.lane_tables``). Kept, read-only, under the whole
    schedule: every node of a program asks for its layer's tables again,
    once a direction, and the float64 cosines of a (16 384, 64) table take
    a host core 40 ms (made a node and a direction, 2.6 s of the
    Keye-VL-2.0 cell's set-up and 4.9 of the Ouro cell's: PERF.md section
    6, PR 59). The depth is for one program's tables: its lengths
    (buckets) x its head widths (a model's heads and its indexer's) x its
    schedules (a model whose window and full layers turn by two) x the two
    layouts; sixteen hold two of each."""
    if lanes:
        tables = _rk.lane_tables(*_rotary_tables(t, half, schedule))
    else:
        inv_freq = schedule.inv_freq(half).astype(np.float32)
        angle = (np.arange(t, dtype=np.float32)[:, None] * inv_freq[None, :]
                 ).astype(np.float64)
        a = schedule.amplitude()
        tables = ((a * np.cos(angle)).astype(np.float32),
                  (a * np.sin(angle)).astype(np.float32))
    for table in tables:
        table.setflags(write=False)
    return tables


def _turned(x, schedule, interleaved, back, kernels):
    """Every head of x (..., T, D) turned whole by ``schedule``'s tables,
    ``back``: by the negated angles (the sine's terms change sign; the
    tables are forward's, so a program holds one pair a length and
    schedule); in the Pallas kernel at the blocks ``kernels`` or, None, as
    ``jax.numpy``: the array float32, cut at the half (or into neighbours),
    four products, joined again, one rounding."""
    t, d = x.shape[-2:]
    half = d // 2
    if kernels is not None:
        return _rk.turn(x, *_rotary_tables(t, half, schedule, True), back,
                        kernels)
    cos, sin = _rotary_tables(t, half, schedule)
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
    if back:
        out = [x1 * cos + x2 * sin, x2 * cos - x1 * sin]
    else:
        out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if interleaved:
        out = jnp.stack(out, axis=-1).reshape(x.shape)
    else:
        out = jnp.concatenate(out, axis=-1)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rotate(x, schedule, interleaved, back, kernels):
    """``_turned`` under a derivative of its own: the operator is linear, a
    pair's ``a R(theta)`` with the amplitude ``a`` in the tables, whose
    transpose is ``a R(-theta)``: its pull-back is itself with ``back``
    flipped over the same tables, applied to the cotangent, and keeps
    nothing. (Orthogonal only at ``a = 1``: it is the transpose that the
    pull-back needs, not the inverse.)"""
    return _turned(x, schedule, interleaved, back, kernels)


_rotate.defvjp(
    lambda x, *how: (_rotate(x, *how), None),
    lambda schedule, interleaved, back, kernels, _, g: (
        _rotate(g, schedule, interleaved, not back, kernels),))


def _rotary(ins, params, mode):
    """Rotary position embedding of ``data`` (..., T, D): pair ``i`` of
    position ``t`` turns by ``t * base^(-2i/D)``, positions 0..T-1. The pair
    is ``(i, i + D/2)`` (rotate-half), or with ``interleaved`` the
    neighbours ``(2i, 2i + 1)`` (``rope_interleave`` of the DeepSeek-V3
    family, the original RoFormer pairing), turned in place. (That family's
    public code leaves its output de-interleaved, the evens before the odds:
    the same permutation of queries and keys, which no score sees.)
    With ``rotary_dim`` R (0: the whole head) only the first R of the D
    turn, as a head of R would, and dims ``[R, D)`` pass through.
    ``scaling="yarn"`` turns the pairs by another schedule of frequencies
    (the geometric ones blended, pair by pair, with the same divided by
    ``factor``, from ``original_max_position``, ``beta_fast`` and
    ``beta_slow``) and multiplies cos and sin by ``attention_factor`` (0:
    ``0.1 ln(factor) + 1``), so that a score of two turned vectors carries
    its square: :class:`_Schedule` has the equations. The amplitude is in
    the tables and nowhere else: the arithmetic below, the derivative and
    the kernel are the same for every schedule.

    The cos/sin tables are made on the host when the op is traced (T and D
    are static) and enter the program as constants: the frequencies in
    float64 rounded to float32, the angle their float32 product with the
    position (the published model's arithmetic), its cosine and sine
    through float64, times the amplitude there, one rounding. On the v5e a
    float32 ``power`` and ``sin`` of an angle of some thousand radians were
    off by 6e-3 at T = 4096 (PERF.md, PR 26).

    Where the whole head turns, backward is the operator itself at the
    negated angle (``_rotate``, a ``custom_vjp``: ``dx1 = dy1 cos + dy2
    sin``, ``dx2 = dy2 cos - dy1 sin``), rotate-half and ``interleaved``
    alike: the same float32 products of the same tables, one add and one
    rounding as forward, no residual. (Autodiff's transpose of the slices
    and the join was pads, slices and an add, array passes that cost more
    than forward did: PERF.md section 6, PR 59.) A partial ``rotary_dim``
    stays with autodiff: alone on the chip its nodes too read faster under
    the derivative (Qwen3-Next's 64 MiB queries 1.76 -> 0.53 ms forward +
    backward), but that cell's step read 0.8% SLOWER with it, in three
    forms of it: with the rotation's backward another program, XLA no
    longer holds the head's input in VMEM for the head's weight gradient,
    which costs 1.6 ms for the 0.06 the two nodes gain (PERF.md section 6,
    PR 59).

    Where the rule says so (``rotary_kernels.kernel_plan``, asked with the
    platform the program is lowered for: one TPU, a bfloat16 ``data`` of at
    least half its VMEM whose heads of 128 turn whole in rotate-half pairs)
    either direction is one Pallas kernel (``ops/rotary_kernels.py``: the
    same arithmetic, the array across HBM once). Half the VMEM is 64 MiB on
    a v5e: measured there, the cells' 128 MiB queries read 2.44 / 4.82 ms
    forward / forward + backward in the ``jax.numpy`` form (5.62 under
    autodiff) against the kernel's 0.45 / 0.88, and their 16 MiB keys run
    in the form at what the kernel reads, held in VMEM between XLA's
    fusions (``rotary_kernels.kernel_plan`` has every size; PERF.md section
    6, PR 59). Everywhere else (the CPU, several chips, a float32 trunk,
    ``rotary_dim``, ``interleaved``, heads of 64 or 192, smaller arrays) the
    ``jax.numpy`` form, as it was."""
    (x,) = ins
    r = params["rotary_dim"]
    schedule = _schedule(params)
    kernels = _rk.kernel_plan(x.dtype, x.shape, r, params["interleaved"],
                              mode.platform)
    if not r or r == x.shape[-1]:
        return _rotate(x, schedule, params["interleaved"], False, kernels)
    if r % 2 or not 0 < r < x.shape[-1]:
        raise MXNetError(f"RotaryEmbedding: rotary_dim {r} of a head of "
                         f"{x.shape[-1]}")
    turned = _turned(x[..., :r], schedule, params["interleaved"], False,
                     None)
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


def _rotary_counts(ins, outs, params, platform):
    """A launch's counts for one node: itself, whether a train program
    runs it in the Pallas kernel (``_rotary``'s own ask of
    ``rotary_kernels.kernel_plan``), and whether its schedule is another
    than the geometric one."""
    (x,) = ins
    kernels = _rk.kernel_plan(x.dtype, x.shape, params["rotary_dim"],
                              params["interleaved"], platform)
    return {"executor.rotary_nodes": 1,
            "executor.rotary_kernel_nodes": int(kernels is not None),
            "executor.rotary_scaled_nodes": int(bool(params["scaling"]))}


register(
    "RotaryEmbedding",
    _rotary,
    arg_names=["data"],
    param_schema={"base": Param(parse_float, 10000.0),
                  "rotary_dim": Param(parse_int, 0),  # 0: the whole head
                  "interleaved": Param(parse_bool, False),  # (2i, 2i + 1)
                  # the schedule of frequencies and the amplitude
                  # (``_Schedule``): "" the geometric one, or "yarn"
                  "scaling": Param(parse_str, ""),
                  "factor": Param(parse_float, 1.0),
                  "original_max_position": Param(parse_int, 0),
                  "beta_fast": Param(parse_float, 32.0),
                  "beta_slow": Param(parse_float, 1.0),
                  "attention_factor": Param(parse_float, 0.0)},
    launch_counts=_rotary_counts,
    launch_instruments=("executor.rotary_nodes",
                        "executor.rotary_kernel_nodes",
                        "executor.rotary_scaled_nodes"),
)


# --- CausalConv1D ------------------------------------------------------------
_CONV_ACTS = _cck.ACTS  # "silu", "none"


def _causal_conv1d(ins, params, mode):
    """Causal convolution over time of ``data`` (B, T, C), channels last as
    a projection leaves them, ``x_{<0} = 0``, the last of the ``kernel``
    taps at t; then ``act_type`` (``silu``, or ``none``) in float32 before
    the one rounding; ``bias`` (C,) is added first unless ``no_bias``.

    Depthwise (``num_group`` 0, a linear-attention mixer's short
    convolution): ``y_t[c] = sum_j w[c, j] x_{t-K+1+j}[c]``, ``weight``
    (C, K). K shifted multiply-adds in float32.
    Where the rule says so (``causal_conv_kernels.kernel_plan``, asked with
    the platform the program is lowered for: one TPU, a bfloat16 ``data``
    of at least a quarter of its VMEM whose channels 128 divides) forward and
    backward are one Pallas kernel each (``ops/causal_conv_kernels.py``,
    PR 46), the same arithmetic in the same order. Everywhere else (the
    CPU, several chips, a float32 trunk, other widths, smaller arrays) the
    ``jax.numpy`` form below, as it was:
    the pad is made in ``data``'s dtype and each shifted slice cast where
    it is used: padding a float32 copy made XLA write the four products to
    HBM in float32 before adding them (1 x 8192 x 8192 bfloat16 on a v5e,
    ms forward / forward + backward: 2.58 / 8.05 against 1.02 / 4.23, the
    same bits; a grouped ``lax.conv_general_dilated`` 3.35 / 13.7; my chip
    run, PR 34). ``Convolution`` would want (B, C, T) and a group a
    channel.

    Grouped (``num_group`` g: the second convolution of compressed
    convolutional attention, a group a head): channels mix inside each of
    the g groups of C/g, ``y_t[h, o] = sum_j sum_i w[h, o, i, j]
    x_{t-K+1+j}[h, i]``, ``weight`` (g, C/g, C/g, K) (torch's ``Conv1d(C,
    C, K, groups=g)`` weight with its rows split by group). Each tap is one
    product batched over the groups, operands in ``data``'s dtype,
    accumulated in float32."""
    if params["act_type"] not in _CONV_ACTS:
        raise MXNetError(f"CausalConv1D: act_type {params['act_type']!r} is "
                         "neither 'silu' nor 'none'")
    x, w = ins[:2]
    taps, t = w.shape[-1], x.shape[1]
    kernels = _cck.kernel_plan(x.dtype, x.shape, taps, mode.platform,
                               params["num_group"])
    if kernels is not None:
        return _cck.causal_conv(x, w, None if params["no_bias"] else ins[2],
                                params["act_type"], kernels)
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    if params["num_group"]:
        g = params["num_group"]
        xp = xp.reshape(xp.shape[:2] + (g, -1))
        wx = _castp(w, x)
        out = sum(jnp.einsum("bthi,hoi->btho", xp[:, j:j + t], wx[..., j],
                             precision=_prec(x.dtype),
                             preferred_element_type=jnp.float32)
                  for j in range(taps)).reshape(x.shape)
    else:
        wf = w.astype(jnp.float32)
        out = sum(xp[:, j:j + t].astype(jnp.float32) * wf[:, j]
                  for j in range(taps))
    if not params["no_bias"]:
        out = out + ins[2].astype(jnp.float32)
    return _CONV_ACTS[params["act_type"]](out).astype(x.dtype)


def _causal_conv1d_fill(shapes, p):
    data = shapes[0]
    if data is not None:
        c, g = data[-1], p["num_group"]
        if g and c % g:
            raise MXNetError(f"CausalConv1D: {c} channels in {g} groups")
        shapes[1] = shapes[1] or (
            (g, c // g, c // g, p["kernel"]) if g else (c, p["kernel"]))
        if not p["no_bias"]:
            shapes[2] = shapes[2] or (c,)
    return shapes


def _causal_conv1d_counts(ins, outs, params, platform):
    """A launch's counts for one node: whether it mixes channels inside
    groups (``num_group``) and is not depthwise, and whether a train
    program runs it in the Pallas kernels: ``_causal_conv1d``'s own ask of
    ``causal_conv_kernels.kernel_plan``."""
    x, w = ins[:2]
    kernels = _cck.kernel_plan(x.dtype, x.shape, w.shape[-1], platform,
                               params["num_group"])
    return {"executor.conv_grouped_layers": int(params["num_group"] > 0),
            "executor.conv_kernel_layers": int(kernels is not None)}


register(
    "CausalConv1D",
    _causal_conv1d,
    arg_names=lambda p: ["data", "weight"] + ["bias"] * (not p["no_bias"]),
    param_schema={"kernel": Param(parse_int),  # taps, the last at t
                  "act_type": Param(parse_str, "silu"),  # or "none"
                  "no_bias": Param(parse_bool, True),
                  # 0: depthwise; g: channels mix inside each of g groups
                  "num_group": Param(parse_int, 0)},
    fill_in_shapes=_causal_conv1d_fill,
    launch_counts=_causal_conv1d_counts,
    launch_instruments=("executor.conv_grouped_layers",
                        "executor.conv_kernel_layers"),
)


# --- GatedDeltaRule ----------------------------------------------------------
def _gated_delta_rule(ins, params, mode):
    """Linear attention by the gated delta rule (``gated_delta.py`` has the
    equations and what is float32): ``query``, ``key`` (B, Hk, T, Dk),
    ``value`` (B, Hv, T, Dv), ``beta`` (B, Hv, T) and ``g`` (the log of
    the decay, <= 0), (B, Hv, T) for a gate a head or (B, Hv, T, Dk) for a
    gate a key channel -> (B, Hv, T, Dv); value head n reads key head ``n
    // (Hv / Hk)``. Each head's query and key are first divided by their
    length (eps 1e-6) and the query by ``sqrt(Dk)``. Computed ``chunk``
    tokens at a time; the state starts at 0 in every row and is never reset
    inside one. Where the rule says so (``gated_delta.kernel_plan``, asked
    with the platform the program is lowered for and whether the gate is
    one a channel) the chunk-local algebra and the scan over chunks run in
    Pallas kernels, with either gate; a gate a channel's two Gram matrices
    and its running sum then do too, all six kernels under one
    differentiation rule."""
    q, k, v, g, beta = ins
    q, k = _gdr.l2_normalize(q), _gdr.l2_normalize(k)
    q = (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(q.dtype)
    kernels = _gdr.kernel_plan(q.dtype, k.shape, v.shape, params["chunk"],
                               mode.platform, g.ndim == 4)
    return _gdr.chunk_gated_delta_rule(
        q, k, v, g, beta, chunk=params["chunk"],
        kernels=kernels).astype(v.dtype)


def _gated_delta_rule_counts(ins, outs, params, platform):
    """A launch's counts for one node: the chunks its rows are cut into
    (batch x T / chunk: the scan's trips; T would mean a token at a time)
    and whether a train program runs its chunk-local algebra and its scan
    over chunks in the Pallas kernels: ``_gated_delta_rule``'s own ask of
    ``gated_delta.kernel_plan`` (one rule: all four kernels or none); and
    whether its gate is one a key channel (``g`` of rank 4: a model
    rewritten onto a gate a head reads 0 here)."""
    q, k, v, g = ins[:4]
    channel = len(g.shape) == 4
    kernels = int(_gdr.kernel_plan(q.dtype, k.shape, v.shape, params["chunk"],
                                   platform, channel) is not None)
    return {"executor.linear_attention_layers": 1,
            "executor.linear_attention_chunks":
                v.shape[0] * _gdr.chunks_of(v.shape[2], params["chunk"]),
            "executor.linear_attention_kernel_layers": kernels,
            "executor.linear_attention_scan_kernel_layers": kernels,
            "executor.linear_attention_channel_gated_layers": int(channel)}


register(
    "GatedDeltaRule",
    _gated_delta_rule,
    arg_names=["query", "key", "value", "g", "beta"],
    param_schema={"chunk": Param(parse_int, 64)},  # tokens, a power of two
    launch_counts=_gated_delta_rule_counts,
    launch_instruments=("executor.linear_attention_layers",
                        "executor.linear_attention_chunks",
                        "executor.linear_attention_kernel_layers",
                        "executor.linear_attention_scan_kernel_layers",
                        "executor.linear_attention_channel_gated_layers"),
)


# --- SelectiveScan -------------------------------------------------------------
def _selective_scan(ins, params, mode):
    """The selective scan of a Mamba-1 mixer (``selective_scan.py`` has the
    equations and what is float32): ``data`` and ``dt`` (B, T, C), ``A_log``
    (C, N), ``B`` and ``C`` (B, T, N), ``D`` and ``dt_bias`` (C,) -> (B, T,
    C) in ``data``'s dtype. ``dt`` is the step's projection as it leaves its
    ``FullyConnected``, bias-free: the operator adds ``dt_bias`` and takes
    the softplus itself, in float32, so that no float32 (B, T, C) array of
    steps stands between the two nodes. The mixer's gate ``y * silu(z)`` is
    the graph's. The state starts at 0 in every row and is never reset inside
    one. Where the rule says so (``selective_scan.kernel_plan``, asked with
    the platform the program is lowered for) forward and backward are one
    Pallas kernel each; everywhere else the ``jax.numpy`` form, a chunk of
    tokens at a time."""
    x, dt, a_log, b, c, d, dt_bias = ins
    kernels = _ssm.kernel_plan(x.dtype, x.shape, a_log.shape[1],
                               mode.platform)
    if kernels is not None:
        return _ssm.selective_scan(x, dt, a_log, b, c, d, dt_bias, kernels)
    return _ssm.selective_scan_chunked(x, dt, a_log, b, c, d, dt_bias)


def _selective_scan_fill(shapes, p):
    data, a_log = shapes[0], shapes[2]
    if data is not None:
        shapes[1] = shapes[1] or data
        for i in (5, 6):
            shapes[i] = shapes[i] or (data[-1],)
        if a_log is not None:
            for i in (3, 4):
                shapes[i] = shapes[i] or (data[0], data[1], a_log[1])
    return shapes


def _selective_scan_counts(ins, outs, params, platform):
    """A launch's counts for one node: the state elements it updates (batch
    x T x channels x states: one decay, one multiply-add and one share of
    the output's sum each) and whether a train program runs it in the
    Pallas kernels: ``_selective_scan``'s own ask of
    ``selective_scan.kernel_plan``."""
    x, a_log = ins[0], ins[2]
    kernels = _ssm.kernel_plan(x.dtype, x.shape, a_log.shape[1], platform)
    return {"executor.selective_scan_layers": 1,
            "executor.selective_scan_kernel_layers": int(kernels is not None),
            "executor.selective_scan_state_updates":
                int(np.prod(x.shape)) * a_log.shape[1]}


register(
    "SelectiveScan",
    _selective_scan,
    arg_names=["data", "dt", "A_log", "B", "C", "D", "dt_bias"],
    fill_in_shapes=_selective_scan_fill,
    launch_counts=_selective_scan_counts,
    launch_instruments=("executor.selective_scan_layers",
                        "executor.selective_scan_kernel_layers",
                        "executor.selective_scan_state_updates"),
)


# --- MoE -------------------------------------------------------------------
@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows, whose gradient is
    the gather ``g[inverse]`` and not the scatter autodiff would write. The
    permutations are arguments and not a closure, like the share of
    ``_attach_router_losses``."""
    return x[perm]


_permute_rows.defvjp(lambda x, perm, inverse: (x[perm], inverse),
                     lambda inverse, g: (g[inverse], None, None))


def _attach_router_losses(logits, routed_share, lb_coef, z_coef):
    """Identity on the router's ``logits`` (N, E) whose backward adds the
    gradient of ``N * (lb_coef * E * sum_e f_e P_e + z_coef * mean_t
    logsumexp(logits_t)^2)``: ``f_e = routed_share`` is a constant, ``P_e``
    the mean over tokens of ``softmax(logits)``. MXNet's
    ``IdentityAttachKLSparseReg`` idiom. The factor N (the rows) puts the
    two terms on the scale of ``SoftmaxOutput``'s gradient, which is of the
    cross-entropy SUMMED over rows."""
    if not (lb_coef or z_coef):
        return logits
    n, e = logits.shape

    def penalty(z, share):
        lb = e * jnp.sum(share * jnp.mean(jax.nn.softmax(z, -1), 0))
        zl = jnp.mean(jax.nn.logsumexp(z, axis=-1) ** 2)
        return n * (lb_coef * lb + z_coef * zl)

    # the share is an argument and not a closure: a traced value the
    # backward closed over is another trace's under ``jax.checkpoint``
    # (MXNET_BACKWARD_DO_MIRROR), which runs this forward again
    @jax.custom_vjp
    def f(z, share):
        return z

    f.defvjp(lambda z, share: (z, (z, share)),
             lambda res, g: (g + jax.grad(penalty)(*res),
                             jnp.zeros_like(res[1])))
    return f(logits, routed_share)


def _expert_plans(platform, rows_dtype, m, weights, vmem_bytes=None):
    """{(K, N): tiles} of one layer's expert matmuls, ``m`` rows of
    ``rows_dtype`` times each of ``weights`` (E, K, N) (anything with a
    shape and a dtype), where ``grouped_matmul.plan`` has tiles for every
    one of them, else None: a layer's nine matmuls run the kernels or none
    does. ``platform``: what the program is lowered for (``OpMode.platform``;
    None: jax's default backend); ``vmem_bytes``: of the one TPU the process
    holds, read from its kind unless given. ``_expert_matmul`` and the
    layer's launch counts ask it, with the same arguments."""
    plans = {w.shape[1:]: _gmm.plan(
        platform or jax.default_backend(),
        vmem_bytes or _ps.attached_vmem_bytes(), rows_dtype,
        weights[0].dtype, m, *w.shape[1:]) for w in weights}
    return None if None in plans.values() else plans


def _expert_matmul(counts, rows_dtype, m, weights, platform=None,
                   vmem_bytes=None, interpret=False):
    """``(f, kernels)``: ``f(rows, w)`` for one layer, ``rows`` (M, K)
    sorted by expert times ``w`` (E, K, N), one of ``weights``, as the
    parameter is stored, ``counts`` rows an expert. Where ``_expert_plans``
    has tiles ``f`` is the Pallas kernels (they cast a weight tile in VMEM;
    ``kernels`` True), anywhere else ``_castp`` + ``ragged_dot``: decided
    here, once, in Python. The kernels own the rows past ``counts.sum()``
    (zeros there forward and backward, none read into a live row:
    ``grouped_matmul.py``); what ``ragged_dot`` leaves there is not
    specified."""
    plans = _expert_plans(platform, rows_dtype, m, weights, vmem_bytes)
    if plans is None:
        return lambda rows, w: jax.lax.ragged_dot(
            rows, _castp(w, rows), counts, precision=_prec(rows.dtype)), False
    groups = _gmm.groups(counts, m, plans[weights[0].shape[1:]])
    return lambda rows, w: _gmm.grouped_matmul(
        rows, w, groups, plans[w.shape[1:]], interpret), True


def _router_logits(x, w_router):
    """(N, E) float32: the router that is one product inside the operator
    (``router="weight"``), kept under per-operator recomputation."""
    return keep(jnp.dot(x.astype(jnp.float32),
                        w_router.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST))


def _chosen(expert, e):
    """(N, k, E) bool, True at ``[n, j, expert[n, j]]``: the routing as a
    mask over ``e`` experts. It is only ever read inside a reduction, which
    XLA fuses it into, so no array of that size reaches memory."""
    return expert[:, :, None] == jnp.arange(e, dtype=expert.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _select_scores(scores, expert, e):
    """``take_along_axis(scores, expert, 1)`` (N, k) of ``scores`` (N, e)
    as a dense select: each score is compared against the k experts of its
    token and the one that is chosen comes out of a reduction over the
    experts, the gathered value bit for bit. The chip's gather moves a
    scalar an index at 7-10 ns; e compares a scalar run at the vector
    unit's rate. The reduction is a maximum over ``-inf`` and not a sum
    over zeros: XLA merges a sum with ``route_norm``'s sum over k into one
    over (k, E), which adds a token's weights by expert and not by j, an
    ulp from the gathered form. Backward is the same select the other way
    and keeps nothing but ``expert``."""
    return jnp.max(jnp.where(_chosen(expert, e), scores[:, None, :],
                             -jnp.inf), axis=-1)


def _select_scores_bwd(e, expert, g):
    # a token's k experts are distinct: at most one term an element
    return jnp.sum(jnp.where(_chosen(expert, e), g[:, :, None], 0),
                   axis=1), None


_select_scores.defvjp(
    lambda scores, expert, e: (_select_scores(scores, expert, e), expert),
    _select_scores_bwd)


def _router(logits, bias, params):
    """(expert (N * k,) int32, weights (N, k) float32, rows an expert (E,))
    from the router's ``logits`` (N, E) float32 (``_router_logits``, or an
    input the graph computed: ``router="graph"``):
    the ``top_k`` experts of each token and what their outputs are weighted
    by, in float32 whatever the trunk.

    ``score_func`` softmax: the experts of largest ``p = softmax(logits)``,
    weighted by ``p``. sigmoid: ``s = sigmoid(logits)``, the experts of
    largest ``s + expert_bias`` (the bias steers the choice only: it has no
    gradient and is not in the weights), weighted by ``s``. Then, either
    way: ``route_norm`` divides a token's k weights by their sum (+ 1e-20)
    and ``route_scale`` multiplies them. The weights and the rows an
    expert are dense selects over (N, E) (``_select_scores``; the counts
    sum the same compares), never a gather or a scatter-add of N * k
    scalars. The logits (N x E float32, a six-pass product), the experts,
    their counts and the weights as selected are kept under per-operator
    recomputation (``registry.keep``): backward then runs neither that
    product nor ``top_k``'s sort, the count or the select again."""
    k = params["top_k"]
    n, e = logits.shape

    def choose(scores):
        expert = jax.lax.top_k(scores, k)[1]                  # (N, k)
        counts = jnp.sum(_chosen(expert, e), axis=(0, 1), dtype=jnp.int32)
        return keep(expert), keep(counts)

    if params["score_func"] == "sigmoid":
        if params["lb_coef"] or params["z_coef"]:
            raise MXNetError("MoE: lb_coef and z_coef are defined on a "
                             "softmax router, not score_func='sigmoid'")
        scores = jax.nn.sigmoid(logits)
        expert, counts = choose(
            scores if bias is None else scores + jax.lax.stop_gradient(
                bias.astype(jnp.float32)))
    elif params["score_func"] == "softmax":
        if bias is not None:
            raise MXNetError("MoE: expert_bias needs score_func='sigmoid'")
        expert, counts = choose(logits)
        logits = _attach_router_losses(
            logits, counts.astype(jnp.float32) / n,
            params["lb_coef"], params["z_coef"])
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise MXNetError(f"MoE: score_func {params['score_func']!r} is "
                         "neither 'softmax' nor 'sigmoid'")
    p = keep(_select_scores(scores, expert, e))
    if params["route_norm"]:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20)
    if params["route_scale"] != 1.0:
        p = p * params["route_scale"]
    return expert.reshape(-1), p, counts


# A round of the held experts' rows is this many times the rows a balanced
# router sends them, in whole row tiles of the kernels.
_ROUND_FACTOR = 2
_ROUND_TILE = 512


def held_round_rows(assignments, held, experts):
    """Rows of one round of ``MoE``'s dispatch where ``held`` of
    ``experts`` experts live here (``_held_rounds``): ``_ROUND_FACTOR`` x
    the balanced share of the ``assignments`` (tokens x top_k), in row
    tiles, at most all of them. All of them where every expert is held."""
    if held == experts:
        return assignments
    rows = _ROUND_FACTOR * -(-assignments * held // experts)
    tile = _ROUND_TILE if rows >= _ROUND_TILE else 8
    return min(assignments, -(-rows // tile) * tile)


def _row_sum_plan(platform, dtype, rows, n, h, weights, top_k,
                  vmem_bytes=None):
    """``row_sum_kernels``' blocks for the two row sums of a held round of
    ``rows`` rows of ``dtype`` (., ``h``) into ``n`` tokens, or None:
    XLA's scatter-add. The kernel engages where the layer's grouped
    matmuls do (``_expert_plans``) and ``row_sum_kernels.kernel_plan`` has
    blocks for the round (its size among what it asks). ``_moe``,
    ``_held_round`` and the layer's launch counts ask it, with the same
    arguments."""
    if _expert_plans(platform, dtype, rows, weights, vmem_bytes) is None:
        return None
    return _rs.kernel_plan(
        platform or jax.default_backend(),
        vmem_bytes or _ps.attached_vmem_bytes(), dtype, rows, n, h,
        weights[0].shape[0], top_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _take_rows(n, plan, x, tok, runs):
    """``x[tok]`` for the rows of a held round, whose gradient is the row
    sum kernel over the round's ``runs`` (float32 sums rounded once to x's
    dtype) and not the scatter-add autodiff would write."""
    return x[tok]


_take_rows.defvjp(
    lambda n, plan, x, tok, runs: (x[tok], (tok, runs)),
    lambda n, plan, res, g: (
        _rs.sum_rows(g, res[0], None, res[1], n, g.dtype, plan), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sum_weighted_rows(n, plan, y, weight, tok, runs):
    """(n, H) float32 ``zeros.at[tok].add(y * weight[:, None])`` over a held
    round's ``runs`` in the row sum kernel; backward is what autodiff
    writes of that form: the gather ``g[tok]`` times the weight, and its
    product with ``y`` summed over a row for the weight."""
    return _rs.sum_rows(y, tok, weight, runs, n, jnp.float32, plan)


def _sum_weighted_rows_bwd(n, plan, res, g):
    y, weight, tok = res
    g = g[tok]
    return ((g * weight[:, None]).astype(y.dtype),
            jnp.sum(g * y.astype(jnp.float32), axis=1), None, None)


_sum_weighted_rows.defvjp(
    lambda n, plan, y, weight, tok, runs: (
        _sum_weighted_rows(n, plan, y, weight, tok, runs), (y, weight, tok)),
    _sum_weighted_rows_bwd)


def _held_round(first, rows, platform, x, order, weight, counts, w_gate, w_up,
                w_down, runs=None, looped=False):
    """(N, H) float32: what rows ``[first, first + rows)`` of the held
    assignments add to the layer's output. ``order``: the assignments
    (token ``// k``, its j-th expert ``% k``) sorted by expert, the dead
    tail and then padding after the held ones; ``weight`` (N * k,) the
    routing weight of every assignment, unsorted; ``counts`` (L,) rows an
    expert over the whole list. The round slices its window of ``order``
    and gathers that window's weights alone: ``rows`` scalars, not N * k.
    A dead or padded row's weight only has to be finite. The dead rows
    are the grouped matmul's: the kernels return zeros there, forward and
    dgrad, and read none of them into a live row, so ``silu(gate) * up``,
    ``y``, every cotangent a kernel writes and the weight's gradient are
    zeros there and no select is traced around a matmul (the cotangent of
    ``y`` is finite and not zero there: dgrad does not read it into a live
    row and wgrad zeroes it in VMEM). ``ragged_dot`` specifies nothing past
    its groups, so on that path each matmul is masked on both sides.
    ``looped``: a round of the backward's loop over the further rounds
    (``_looped_rounds_bwd``), which keeps the select of ``y``.
    ``runs`` (``row_sum_kernels.block_runs`` of the whole list), where
    ``_row_sum_plan`` has blocks: the round's rows are summed into their
    tokens, here and in the backward of ``x[tok]``, by the row sum kernel
    over the runs that fall in the round, and no scatter is traced."""
    ends = jnp.cumsum(counts)
    here = (jnp.clip(ends, first, first + rows)
            - jnp.clip(ends - counts, first, first + rows)).astype(jnp.int32)
    matmul, kernels = _expert_matmul(here, x.dtype, rows,
                                     (w_gate, w_up, w_down), platform)
    live = (jnp.arange(rows) < jnp.sum(here))[:, None]
    live_matmul = matmul
    if not kernels:   # ragged_dot: what its rows past the groups hold
        def live_matmul(r, w):
            return jnp.where(live, matmul(jnp.where(live, r, 0), w), 0)

    order = jax.lax.dynamic_slice_in_dim(order, first, rows)
    top_k = weight.shape[0] // x.shape[0]
    tok = order // top_k
    weight = keep(weight[order])
    # the gathered rows and what each matmul's backward reads, kept under
    # per-operator recomputation (``registry.keep``); casts and the float32
    # product (ragged_dot's masks too) are made again from them
    kernel = None   # (tokens, blocks) of the row sum kernel, where it runs
    if runs is not None:
        kernel = (x.shape[0], _row_sum_plan(
            platform, x.dtype, rows, *x.shape, (w_gate, w_up, w_down),
            top_k))
        runs = jnp.clip(runs - first, 0, rows)
    r = keep(x[tok] if kernel is None else _take_rows(*kernel, x, tok, runs))
    gate, up = keep((live_matmul(r, w_gate), live_matmul(r, w_up)))
    y = live_matmul(keep(jax.nn.silu(gate) * up), w_down)
    if kernels and looped:
        # zeros selected where zeros are. Its transpose makes the cotangent
        # of ``y`` a fusion of its own in the loop's body, without which
        # libtpu 0.0.34 dies compiling a layer's output beside its
        # gradients at the Keye-VL-2.0 and SDAR shapes (memory space
        # assignment's repacker; test_row_sum_kernel_compiles_for_a_v5e).
        # The loop runs where routing has collapsed and nowhere else.
        y = jnp.where(live, y, 0)
    y = keep(y)
    if kernel is None:
        return jnp.zeros(x.shape, jnp.float32).at[tok].add(
            y.astype(jnp.float32) * weight[:, None])
    return _sum_weighted_rows(*kernel, y, weight, tok, runs)


def _held_rounds(rows, platform, x, weight, w_gate, w_up, w_down, order,
                 counts, runs):
    """The sum of ``_held_round`` over the rounds of ``rows`` rows that
    hold a live row, ``order`` a whole number of rounds long. One round
    (``held_round_rows`` gave every assignment): no loop is traced, the
    round is differentiated as it stands and its residuals are the ones
    it marks. More: ``_looped_rounds``, the first round and a loop over
    the others whose trip count is read on the device and is zero unless
    routing has collapsed onto the experts held here."""
    if order.shape[0] == rows:
        return _held_round(0, rows, platform, x, order, weight, counts,
                           w_gate, w_up, w_down, runs)
    return _looped_rounds(rows, platform, x, weight, w_gate, w_up, w_down,
                          order, counts, runs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _looped_rounds(rows, platform, x, weight, w_gate, w_up, w_down, order,
                   counts, runs):
    """The first round and a loop over the further live ones. Nothing is
    differentiated through the loop: forward keeps the first round's
    residuals as autodiff would, backward recomputes each further round
    before its cotangents, so memory and the program's size are one
    round's, however many run. Backward's loop sits under a branch that
    the first round's cotangents pass through where no further round is
    live, the expert weights' in the dtype their wgrads were made in: a
    loop's operands are buffers in memory, and a float32 copy of a
    bfloat16 wgrad written for a loop that does not run is 6 bytes a
    held parameter that the optimizer's fusion would not have moved."""
    return _looped_rounds_fwd(rows, platform, x, weight, w_gate, w_up,
                              w_down, order, counts, runs)[0]


def _round_of(first, rows, platform, order, counts, runs=None, looped=False):
    """``_held_round`` at ``first`` as a function of what it is
    differentiated in: x, the routing weights, the three expert weights."""
    return lambda x, weight, *w: _held_round(first, rows, platform, x, order,
                                             weight, counts, *w, runs, looped)


def _looped_rounds_fwd(rows, platform, x, weight, w_gate, w_up, w_down,
                       order, counts, runs):
    wrt = (x, weight, w_gate, w_up, w_down)
    out, vjp = jax.vjp(_round_of(0, rows, platform, order, counts, runs),
                       *wrt)
    rounds = (jnp.sum(counts) + rows - 1) // rows

    def further(out):
        return jax.lax.fori_loop(
            1, rounds,
            lambda r, acc: acc + _round_of(r * rows, rows, platform, order,
                                           counts, runs)(*wrt),
            out)

    out = jax.lax.cond(rounds > 1, further, lambda out: out, out)
    return out, (vjp, wrt, order, counts, runs, rounds)


def _looped_rounds_bwd(rows, platform, res, g):
    vjp, wrt, order, counts, runs, rounds = res
    # a wgrad is made in the rows' dtype (the kernel writes it, ``_castp``'s
    # transpose rounds to it) and widened to the weight's: narrowing it
    # back is exact, and the narrow one is what crosses the branch
    wide = [w.dtype for w in wrt]
    made = wide[:2] + [min(d, wrt[0].dtype, key=lambda d: d.itemsize)
                       for d in wide[2:]]

    def cast(cts, dtypes):
        return tuple(c.astype(d) for c, d in zip(cts, dtypes))

    def more(r, cts):
        back = jax.vjp(_round_of(r * rows, rows, platform, order, counts,
                                 runs, looped=True), *wrt)[1]
        return jax.tree.map(jnp.add, cts, back(g))

    def further(cts):   # summed in the weights' dtype, rounded once
        return cast(jax.lax.fori_loop(1, rounds, more, cast(cts, wide)), made)

    cts = jax.lax.cond(rounds > 1, further, lambda cts: cts,
                       cast(vjp(g), made))
    return cast(cts, wide) + (None, None, None)


_looped_rounds.defvjp(_looped_rounds_fwd, _looped_rounds_bwd)


def _moe(ins, params, mode):
    """Sparse mixture of SiLU-gated experts, drop-free.

    ``data`` (..., H) is N rows of tokens. ``router_weight`` (E, H), or
    with ``router="graph"`` ``router_logits`` (..., E) in its place: the
    scores of a router that is a graph of its own (an MLP, a state carried
    down the layers), taken in float32, whose gradient goes back to it;
    ``gate_weight`` and ``up_weight`` (L, H, F) and ``down_weight``
    (L, F, H): expert-major, input features before output features, the
    layout the grouped matmul reads; L = ``num_local_experts`` (E where it
    is 0: every expert lives here); ``expert_bias`` (E,) where
    ``expert_bias=True``. Each token goes to the ``top_k`` experts the
    router gives it (``_router``) and receives ``sum w_e *
    down_e(silu(gate_e t) * up_e t)`` over those of them that are held
    here, experts ``[expert_offset, expert_offset + L)``. The assignments
    are sorted by expert and each expert multiplies exactly its own rows
    (``_expert_matmul``): no capacity, no token dropped, none computed for
    an expert it was not routed to. The routing's bookkeeping (a token's
    k weights, the rows an expert) is dense selects over (N, E)
    (``_router``), and where a share of the experts is held a round
    gathers only its own window's weights (``_held_round``): nothing
    moves N * k scalars one index at a time. What is traced: every expert
    held, one pass over all the rows; a held range whose round
    (``held_round_rows``) is every assignment, that one round and no loop;
    any other held range, the first round and a loop over the further
    ones (``_held_rounds``). A held round gathers its rows, runs the three
    grouped matmuls and sums the rows into their tokens; where
    ``_row_sum_plan`` has blocks (the grouped matmuls' kernels engage, the
    round is whole long copies and the tokens whole blocks: every
    held-range cell of the benchmark) that sum and the backward of the
    gather are one Pallas kernel
    (``row_sum_kernels.py``) and no scatter is traced, elsewhere XLA's
    scatter-add.
    """
    x, router, w_gate, w_up, w_down = ins[:5]    # its weight, or logits
    bias = ins[5] if params["expert_bias"] else None
    k = params["top_k"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n, e = x.shape[0], params["num_experts"]
    held = w_gate.shape[0]
    if params["router"] == "graph":
        logits = router.reshape(n, e).astype(jnp.float32)
    elif params["router"] == "weight":
        logits = _router_logits(x, router)
    else:
        raise MXNetError(f"MoE: router {params['router']!r} is neither "
                         "'weight' nor 'graph'")
    expert, p, counts = _router(logits, bias, params)
    # rows of one round of the expert matmuls: what the rule is asked with,
    # here and by the layer's launch counts
    m = held_round_rows(n * k, held, e)

    if held == e and not params["expert_offset"]:
        order = keep(jnp.argsort(expert, stable=True))        # by expert
        inverse = keep(jnp.argsort(order))
        # what the three matmuls' backward reads, kept under per-operator
        # recomputation like the held range's first round
        rows = keep(_permute_rows(jnp.repeat(x, k, axis=0), order, inverse))
        matmul, _ = _expert_matmul(counts, x.dtype, m,
                                   (w_gate, w_up, w_down), mode.platform)
        gate, up = keep((matmul(rows, w_gate), matmul(rows, w_up)))
        out = keep(matmul(keep(jax.nn.silu(gate) * up), w_down))
        out = _permute_rows(out, inverse, order).reshape(n, k, -1)
        out = jnp.sum(out.astype(jnp.float32) * p[..., None], axis=1)
        return out.astype(x.dtype).reshape(shape)

    # the share of the experts held here
    local = expert - params["expert_offset"]
    key = jnp.where(jnp.logical_and(local >= 0, local < held), local, held)
    order = keep(jnp.argsort(key, stable=True))  # held first, by expert
    counts = counts[params["expert_offset"]:params["expert_offset"] + held]
    rounds = -(-n * k // m)
    if rounds * m > n * k:   # whole rounds: more of the dead tail
        order = jnp.pad(order, (0, rounds * m - n * k))
    # where the round's row sums run their kernel: each token block's runs
    # of the sorted list, from a count a block of who chose whom
    plan = _row_sum_plan(mode.platform, x.dtype, m, *x.shape,
                         (w_gate, w_up, w_down), k)
    runs = None if plan is None else keep(_rs.block_runs(
        jnp.any(_chosen(local.reshape(n, k), held), axis=1),
        jnp.cumsum(counts) - counts, plan.block))
    out = _held_rounds(m, mode.platform, x, p.reshape(-1), w_gate, w_up,
                       w_down, order, counts, runs)
    return out.astype(x.dtype).reshape(shape)


def _moe_fill(shapes, params):
    data = shapes[0]
    if data is not None:
        e, f, h = params["num_experts"], params["num_hidden"], data[-1]
        held = params["num_local_experts"] or e
        if params["expert_offset"] + held > e:
            raise MXNetError(
                f"MoE: experts [{params['expert_offset']}, "
                f"{params['expert_offset'] + held}) of {e}")
        router = tuple(data[:-1]) + (e,) if params["router"] == "graph" \
            else (e, h)
        for i, s in enumerate([router, (held, h, f), (held, h, f),
                               (held, f, h)] + [(e,)] * params["expert_bias"],
                              1):
            shapes[i] = shapes[i] or s
    return shapes


def _moe_counts(ins, outs, params, platform):
    """A launch's counts for one layer: the rows through its grouped
    matmuls (tokens x ``top_k``), the experts held here, whether its logits
    are an input the graph computed (``router="graph"``), whether one round
    holds every assignment (every expert held, or ``held_round_rows`` gave
    them all: ``_moe`` traces no loop over rounds), and how many of
    its nine expert matmuls (forward, dgrad and wgrad of gate, up and down)
    a train program runs in the Pallas kernels, all nine or none: ``_moe``'s
    own ask of ``_expert_plans``, at the rows of one round; how many of
    those nine a held round runs with no row select around them, nine
    where the kernels run (they own the round's dead rows; the first
    round's count, the one every step runs: a round of the backward's loop
    keeps the select of ``y``) or none (``ragged_dot``; every expert held:
    no dead row); and how many of
    a held round's two row sums (the combine, the dispatch's backward) run
    the row sum kernel, both or none: ``_moe``'s own ask of
    ``_row_sum_plan``."""
    x, weights = ins[0], tuple(ins[2:5])
    tokens = int(np.prod(x.shape[:-1]))
    routed = tokens * params["top_k"]
    held = weights[0].shape[0]
    m = held_round_rows(routed, held, params["num_experts"])
    kernels = _expert_plans(platform, x.dtype, m, weights) is not None
    all_held = held == params["num_experts"] and not params["expert_offset"]
    row_sums = not all_held and _row_sum_plan(
        platform, x.dtype, m, tokens, x.shape[-1], weights,
        params["top_k"]) is not None
    return {"executor.moe_layers": 1,
            "executor.moe_assignments": routed,
            "executor.moe_local_experts": held,
            "executor.moe_graph_routed_layers":
                int(params["router"] == "graph"),
            "executor.moe_one_round_layers": int(m == routed),
            "executor.moe_kernel_matmuls": 9 * kernels,
            "executor.moe_kernel_row_sums": 2 * row_sums,
            "executor.moe_unmasked_matmuls": 9 * (kernels and not all_held)}


register(
    "MoE",
    _moe,
    arg_names=lambda p: [
        "data", "router_logits" if p["router"] == "graph"
        else "router_weight", "gate_weight", "up_weight",
        "down_weight"] + ["expert_bias"] * p["expert_bias"],
    param_schema={
        "num_experts": Param(parse_int),  # the router's width
        "num_hidden": Param(parse_int),  # width of one expert
        "top_k": Param(parse_int),
        # router regularisers, attached in backward (forward unchanged)
        "lb_coef": Param(parse_float, 0.0),
        "z_coef": Param(parse_float, 0.0),
        "score_func": Param(parse_str, "softmax"),  # or "sigmoid"
        "route_norm": Param(parse_bool, False),
        "route_scale": Param(parse_float, 1.0),
        "expert_bias": Param(parse_bool, False),  # a sixth input (E,)
        # "weight": input 1 is router_weight (E, H), one product in here;
        # "graph": input 1 is router_logits (..., E), computed by the graph
        "router": Param(parse_str, "weight"),
        # the experts held here: [expert_offset, + num_local_experts) of
        # num_experts; 0 = all of them
        "num_local_experts": Param(parse_int, 0),
        "expert_offset": Param(parse_int, 0),
    },
    fill_in_shapes=_moe_fill,
    launch_counts=_moe_counts,
    launch_instruments=("executor.moe_layers", "executor.moe_assignments",
                        "executor.moe_local_experts",
                        "executor.moe_graph_routed_layers",
                        "executor.moe_one_round_layers",
                        "executor.moe_kernel_matmuls",
                        "executor.moe_kernel_row_sums",
                        "executor.moe_unmasked_matmuls"),
)
