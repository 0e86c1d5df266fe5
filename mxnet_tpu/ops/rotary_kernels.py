"""The rotary embedding as one Pallas TPU kernel: the array crosses HBM once
a pass, forward and backward alike.

``turn(x, *lane_tables(cos, sin), back, plan)`` is ``RotaryEmbedding``'s
rotation (``ops/defs_transformer._turned``) where the rule (``kernel_plan``)
says so:
x (..., T, 128) bfloat16, every head turned whole in rotate-half pairs (i,
i + 64) by the operator's host-made float32 tables ``cos`` / ``sin`` (T, 64)
-> the turned array in x's dtype. The arithmetic is the ``jax.numpy`` form's
and no other: x float32, ``x1 cos - x2 sin`` and ``x2 cos + x1 sin`` in
float32, one rounding.

Over a head's 128 lanes, with ``C = [cos, cos]``, ``S = [-sin, sin]`` and
``R`` the rotation of the lanes by 64 (its own inverse), the operator is ``y =
x C + R(x) S``: ``a + (-b) c`` and ``a - b c`` are the same bits. The
operator's derivative is the operator at the negated angle (``_rotary``): ``x
C - R(x) S``, the same kernel with one sign changed (``back``) over the same
two tables.

What the kernel does that the ``jax.numpy`` form does not: a grid step loads
a (heads, rows, 128) bfloat16 block, converts ``tile`` rows at a time to
float32 in registers, turns the lanes by ``pltpu.roll`` (the XLU: no slice
at lane 64, no concatenation), multiplies and adds against (rows, 128)
float32 blocks of ``C`` and ``S``, rounds once and stores. The heads are the
innermost grid axis and the tables' block depends on the row block alone, so
it stays in VMEM over every head (and batch row) of a row block: the tables
are read once a pass. Where the array does not fit VMEM, XLA makes of the
``jax.numpy`` form a float32 copy of the array, two half-width arrays (64 of a
tile's 128 lanes: each the bytes of the whole), their products and a
concatenation, passes of their own: seven times the bytes' time forward on a
v5e (2.44 ms for the 0.33 that 128 MiB in and out take at 819 GB/s; the
kernel 0.45; PERF.md section 6, PR 59).

``kernel_plan`` is the one rule that says whether the kernel engages and with
which blocks, as ``causal_conv_kernels.kernel_plan`` is the convolution's;
the traced kernel is kept by ``pallas_support._kernel``'s store.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import pallas_support as _ps

_LANES = 128
# Rows a loop step inside a block (a bfloat16 register packs 16), positions
# and heads a grid step: the first of each that divides the array's. On a
# v5e at (2, 32, 8192, 128), ms a pass: heads x rows 1 x 2048 0.466, 2 x 2048
# 0.451, 4 x 2048 0.449, 2 x 1024 0.450, 2 x 4096 0.452, 8 x 512 0.443; tiles
# of 64 rows 0.493, 128 0.453, 256 0.451, 512 0.455: the copy rate, whatever
# the blocks (PERF.md section 6, PR 59).
_TILE = 256
_ROWS = (2048, 1024, 512, 256)
_HEADS = (2, 1)


class Plan(NamedTuple):
    """A grid step takes ``rows`` positions of ``heads`` heads and walks them
    ``tile`` rows at a time."""

    heads: int
    rows: int
    tile: int
    vmem_limit: int


def kernel_plan(dtype, x_shape, rotary_dim, interleaved,
                platform=None) -> Optional[Plan]:
    """The rule: the kernel's blocks for a ``RotaryEmbedding`` of ``data`` of
    ``x_shape`` (..., T, D) and ``dtype`` in a program lowered for
    ``platform`` (the executor's, through ``OpMode.platform``; None: jax's
    default backend), or None: the ``jax.numpy`` form. It engages where the
    program is lowered for the one TPU the process holds (XLA cannot
    partition a Mosaic call over several), ``data`` is bfloat16 (a float32
    trunk keeps the ``jax.numpy`` form), the whole head turns
    (``rotary_dim`` 0 or D) in rotate-half pairs (not ``interleaved``), a
    head is the 128 lanes of a register (D = 64 would need two heads a
    register, D = 192 has no half of whole registers), T is whole tiles, and
    ``data`` is at least half the chip's VMEM: a smaller array XLA can hold
    there between its fusions, where the ``jax.numpy`` form's passes cost
    less than HBM's and fuse with the nodes around them, while a Mosaic call
    reads and writes HBM (``CausalConv1D``'s rule and reason,
    ``causal_conv_kernels.kernel_plan``). On a v5e, 128 MiB of VMEM, a node
    alone, ms forward / forward + backward, form against kernel: 128 MiB
    (the SDAR and Keye-VL-2.0 cells' queries) 2.44 / 4.82 against 0.45 /
    0.88; 64 MiB 1.05 / 2.24 against 0.27 / 0.48; 32 MiB (Trinity's
    queries) behind the per-head norm that feeds it 0.57 forward + backward
    against 0.64; 16 MiB (the keys, Ouro's queries) and 4 MiB the host's
    floor either way, 0.11 against 0.11 chained four deep; every bit equal
    at every size (PERF.md section 6, PR 59). The op and its launch counts
    ask it with the same arguments."""
    vmem = _ps.attached_vmem_bytes()
    if ((platform or jax.default_backend()) != "tpu" or not vmem
            or len(x_shape) < 2):
        return None
    t, d = x_shape[-2:]
    size = int(np.prod(x_shape))
    if (jnp.dtype(dtype) != jnp.bfloat16 or interleaved
            or rotary_dim not in (0, d) or d != _LANES or t % _TILE
            or size * 2 < vmem // 2):
        return None
    rows = next(r for r in _ROWS if t % r == 0)
    heads = next(h for h in _HEADS if (size // (t * d)) % h == 0)
    # x in and out and the two float32 tables, two buffers each, and room
    need = 2 * rows * _LANES * (2 * heads * 2 + 2 * 4) + (8 << 20)
    return Plan(heads, rows, _TILE, min(vmem * 3 // 4, need))


def lane_tables(cos, sin):
    """``C = [cos, cos]`` and ``S = [-sin, sin]`` (T, 128) float32 from the
    operator's host-made (T, 64) tables."""
    cos, sin = np.asarray(cos, np.float32), np.asarray(sin, np.float32)
    return (np.concatenate([cos, cos], axis=-1),
            np.concatenate([-sin, sin], axis=-1))


@functools.partial(jax.jit, static_argnames=(
    "back", "heads", "rows", "tile", "vmem_limit", "interpret"))
def _turn(x, c, s, *, back, heads, rows, tile, vmem_limit, interpret):
    """``x C + R(x) S`` (``back``: ``x C - R(x) S``) in x's shape and dtype:
    x (N, T, 128), c and s (T, 128) float32."""
    pl, pltpu = _ps._pallas()
    N, T, _ = x.shape

    def kernel(x_ref, c_ref, s_ref, y_ref):
        def step(n, carry):
            at = pl.ds(pl.multiple_of(n * tile, tile), tile)
            cf, sf = c_ref[at, :], s_ref[at, :]
            for h in range(heads):
                xf = x_ref[h, at, :].astype(jnp.float32)
                turned = pltpu.roll(xf, _LANES // 2, 1) * sf
                out = xf * cf - turned if back else xf * cf + turned
                y_ref[h, at, :] = out.astype(y_ref.dtype)  # graftlint: allow=trace-purity(a store into a Pallas output ref is the kernel's output, not Python state)
            return carry

        lax.fori_loop(0, rows // tile, step, 0)

    block = pl.BlockSpec((heads, rows, _LANES), lambda i, n: (n, i, 0))
    table = pl.BlockSpec((rows, _LANES), lambda i, n: (i, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(T // rows, N // heads),
        in_specs=[block, table, table],
        out_specs=block,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=3 * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize + 2 * c.size * 4),
        interpret=interpret,
        name="rotary_turn",
    )(x, c, s)


def turn(x, c, s, back, plan, interpret=False):
    """x (..., T, 128) bfloat16 with every head turned by the lane tables
    ``c``, ``s`` (T, 128) float32 (``lane_tables`` of the operator's
    host-made ``cos`` / ``sin``), ``back``: by the negated angles, in x's
    dtype: the kernel at ``plan``'s blocks. Linear in x and kept nowhere:
    whoever differentiates it calls it again with ``back`` flipped.
    ``interpret`` runs it in Pallas's interpreter (tests on the CPU)."""
    flat = x.reshape((-1,) + x.shape[-2:])
    y = _ps._kernel(_turn, (flat, c, s), back=back, interpret=interpret,
                    **plan._asdict())
    return y.reshape(x.shape)
