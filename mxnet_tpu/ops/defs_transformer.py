"""Transformer-block operators: ``RMSNorm``, ``RotaryEmbedding`` and the
sparse-expert layer ``MoE``. (Attention is ``RingAttention`` in
``defs_contrib.py``, whose one-device path is blockwise.)

No reference twin: MXNet 0.x has none of them. The equations are those of
the public OLMoE model (Muennighoff et al. 2024, arXiv:2409.02060; HF
``modeling_olmoe.py``). All three are plain jax lowered by XLA, but for
the grouped matmuls of ``MoE``: Pallas kernels (``grouped_matmul.py``) where
its rule says they engage, ``jax.lax.ragged_dot`` otherwise.

What is float32 whatever the trunk's dtype: the statistics of ``RMSNorm``,
the angles and the rotation of ``RotaryEmbedding``, and in ``MoE`` the
router (logits, softmax, top-k, both regularisers). Outputs come back in
the dtype of ``data``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import parse_float, parse_int
from . import grouped_matmul as _gmm
from .defs_nn import _castp, _prec
from .registry import Param, register


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


# --- RotaryEmbedding -------------------------------------------------------
def _rotary(ins, params, mode):
    """Rotate-half rotary position embedding of ``data`` (..., T, D): the
    pair ``(i, i + D/2)`` of position ``t`` turns by ``t * base^(-2i/D)``.

    The cos/sin tables are made on the host when the op is traced (T and D
    are static) and enter the program as constants: the frequencies in
    float64 rounded to float32, the angle their float32 product with the
    position (the published model's arithmetic), its cosine and sine
    through float64. On the v5e a float32 ``power`` and ``sin`` of an angle
    of some thousand radians were off by 6e-3 at T = 4096 (PERF.md, PR 26).
    """
    (x,) = ins
    t, d = x.shape[-2:]
    half = d // 2
    inv_freq = (params["base"] ** (-np.arange(half, dtype=np.float64) / half)
                ).astype(np.float32)
    angle = (np.arange(t, dtype=np.float32)[:, None] * inv_freq[None, :]
             ).astype(np.float64)
    cos = np.cos(angle).astype(np.float32)
    sin = np.sin(angle).astype(np.float32)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


register(
    "RotaryEmbedding",
    _rotary,
    arg_names=["data"],
    param_schema={"base": Param(parse_float, 10000.0)},
)


# --- MoE -------------------------------------------------------------------
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows, whose gradient is
    the gather ``g[inverse]`` and not the scatter autodiff would write."""

    @jax.custom_vjp
    def f(x):
        return x[perm]

    f.defvjp(lambda x: (x[perm], None), lambda _, g: (g[inverse],))
    return f(x)


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

    def penalty(z):
        lb = e * jnp.sum(routed_share * jnp.mean(jax.nn.softmax(z, -1), 0))
        zl = jnp.mean(jax.nn.logsumexp(z, axis=-1) ** 2)
        return n * (lb_coef * lb + z_coef * zl)

    @jax.custom_vjp
    def f(z):
        return z

    f.defvjp(lambda z: (z, z), lambda z, g: (g + jax.grad(penalty)(z),))
    return f(logits)


def _expert_plans(platform, vmem_bytes, rows_dtype, w_dtype, m, shapes):
    """{(K, N): tiles} of one layer's expert matmuls where
    ``grouped_matmul.plan`` has tiles for every one of ``shapes``, else
    None: a layer's nine matmuls run the kernels or none does."""
    plans = {s: _gmm.plan(platform, vmem_bytes, rows_dtype, w_dtype, m, *s)
             for s in shapes}
    return None if None in plans.values() else plans


def _expert_matmul(counts, rows_dtype, m, weights, vmem_bytes=None,
                   interpret=False):
    """``f(rows, w)`` for one layer: ``rows`` (M, K) sorted by expert times
    ``w`` (E, K, N), one of ``weights``, as the parameter is stored,
    ``counts`` rows an expert. Where a TPU is attached (``vmem_bytes``:
    read from its kind unless given) and ``_expert_plans`` has tiles, ``f``
    is the Pallas kernels in a program lowered for the TPU (they cast a
    weight tile in VMEM) and ``ragged_dot`` in one lowered for anything
    else; with no TPU or no plan there is only ``_castp`` +
    ``ragged_dot``."""
    plans = _expert_plans(
        "tpu", vmem_bytes or _gmm.attached_vmem_bytes(), rows_dtype,
        weights[0].dtype, m, [w.shape[1:] for w in weights])
    if plans is None:
        return lambda rows, w: jax.lax.ragged_dot(
            rows, _castp(w, rows), counts, precision=_prec(rows.dtype))
    groups = _gmm.groups(counts, m, plans[weights[0].shape[1:]])
    return lambda rows, w: _gmm.grouped_matmul(
        rows, w, groups, plans[w.shape[1:]], interpret)


def moe_kernel_matmuls(platform, data_dtype, weight_dtype, rows, hidden,
                       width):
    """How many of one ``MoE`` layer's nine expert matmuls (forward, dgrad
    and wgrad of gate, up and down) a train program lowered for
    ``platform`` runs in the Pallas kernels: the rule ``_expert_matmul``
    follows, asked from outside the trace (``Executor._count_train_launch``).
    All nine or none."""
    return 9 * (_expert_plans(
        platform, _gmm.attached_vmem_bytes(), data_dtype, weight_dtype, rows,
        [(hidden, width), (width, hidden)]) is not None)


def _moe(ins, params, mode):
    """Sparse mixture of SiLU-gated experts, drop-free.

    ``data`` (..., H) is N rows of tokens. ``router_weight`` (E, H);
    ``gate_weight`` and ``up_weight`` (E, H, F) and ``down_weight``
    (E, F, H): expert-major, input features before output features, the
    layout the grouped matmul reads. Each token goes to the ``top_k``
    experts of largest ``p = softmax(router_weight . t)`` and receives
    ``sum p_e * down_e(silu(gate_e t) * up_e t)``, the weights not
    renormalised. The N x top_k assignments are sorted by expert and each
    expert multiplies exactly its own rows (``_expert_matmul``): no
    capacity, no token dropped, none computed for an expert it was not
    routed to.
    """
    x, w_router, w_gate, w_up, w_down = ins
    k = params["top_k"]
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    n, e = x.shape[0], w_router.shape[0]

    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    _, expert = jax.lax.top_k(logits, k)                      # (N, k)
    expert = expert.reshape(-1)
    counts = jnp.bincount(expert, length=e).astype(jnp.int32)
    logits = _attach_router_losses(
        logits, counts.astype(jnp.float32) / n,
        params["lb_coef"], params["z_coef"])
    p = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                            expert.reshape(n, k), axis=1)     # (N, k) f32

    order = jnp.argsort(expert, stable=True)                  # by expert
    inverse = jnp.argsort(order)
    rows = _permute_rows(jnp.repeat(x, k, axis=0), order, inverse)
    matmul = _expert_matmul(counts, x.dtype, n * k, (w_gate, w_up, w_down))
    gate, up = matmul(rows, w_gate), matmul(rows, w_up)
    out = matmul(jax.nn.silu(gate) * up, w_down)
    out = _permute_rows(out, inverse, order).reshape(n, k, -1)
    out = jnp.sum(out.astype(jnp.float32) * p[..., None], axis=1)
    return out.astype(x.dtype).reshape(shape)


def _moe_fill(shapes, params):
    data = shapes[0]
    if data is not None:
        e, f, h = params["num_experts"], params["num_hidden"], data[-1]
        for i, s in enumerate([(e, h), (e, h, f), (e, h, f), (e, f, h)], 1):
            shapes[i] = shapes[i] or s
    return shapes


register(
    "MoE",
    _moe,
    arg_names=["data", "router_weight", "gate_weight", "up_weight",
               "down_weight"],
    param_schema={
        "num_experts": Param(parse_int),
        "num_hidden": Param(parse_int),  # width of one expert
        "top_k": Param(parse_int),
        # router regularisers, attached in backward (forward unchanged)
        "lb_coef": Param(parse_float, 0.0),
        "z_coef": Param(parse_float, 0.0),
    },
    fill_in_shapes=_moe_fill,
)
