"""Qwen3-Next (``config.json`` of Qwen3-Next-80B-A3B and the family's public
``modeling_qwen3_next.py``): a decoder of pre-norm blocks in a period of
``full_attention_interval`` layers, all but the last of which mix tokens by
a Gated DeltaNet (a causal depthwise convolution and SiLU over the q, k and
v projections, length-normalised queries and keys, the gated delta rule
with a decay and a write strength a value head, a per-head RMS norm gated
by ``silu(z)``) and the last by softmax attention over grouped key/value
heads (per-head q/k norm, rotary positions on a part of each head, a
sigmoid gate on the output). Every layer then runs a sparse block: a
softmax router with renormalised top-k weights and a shared expert behind a
sigmoid gate. Defaults are Qwen3-Next-80B-A3B's published sizes."""

import math

import numpy as np

from .. import initializer
from .. import symbol as sym
from .olmoe import (embed_tokens, linear, merge_heads, next_token_head,
                    split_heads)


def log_spaced_parameter(name, shape, axis, low, high):
    """A float32 parameter of ``shape``, by default the log of values spaced
    evenly in the log from ``low`` to ``high`` along ``axis`` and alike
    along the others: decays ``exp(-A dt)`` that remember from one token to
    a thousand. (``kimi_linear.py`` takes it too.)"""
    span = max(shape[axis] - 1, 1)
    logs = np.array([math.log(low) + math.log(high / low) * h / span
                     for h in range(shape[axis])])
    logs = logs.reshape([-1 if a == axis else 1 for a in range(len(shape))])
    return sym.Variable(name, shape=shape, dtype="float32",
                        init=initializer.Constant(
                            np.broadcast_to(logs, shape).tolist()))


def qwen3_next_sym_gen(vocab_size=151936, hidden_size=2048, num_layers=48,
                       full_attention_interval=4, num_heads=16,
                       num_kv_heads=2, head_dim=256,
                       partial_rotary_factor=0.25, linear_key_heads=16,
                       linear_value_heads=32, linear_key_dim=128,
                       linear_value_dim=128, conv_kernel=4,
                       num_experts=512, expert_width=512, top_k=10,
                       shared_expert_width=512, route_norm=True,
                       num_local_experts=0, expert_offset=0, lb_coef=0.001,
                       rms_norm_eps=1e-6, rope_theta=1e7, dtype="float32",
                       ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. Layer i is softmax attention where
    ``(i + 1) % full_attention_interval == 0`` and a Gated DeltaNet
    otherwise. ``num_local_experts`` of the ``num_experts`` the router
    scores live here, from ``expert_offset`` (0: all of them): one chip's
    share under expert parallelism, whose ``vocab_size`` is its slice. The
    rows of ``in_proj_qkvz`` are [q | k | v | z] and of ``in_proj_ba`` [b |
    a], each head-major (the published checkpoint groups them by key head:
    a fixed permutation of rows); ``q`` of the attention layers holds each
    head's query then its gate. Norm gains are stored as they multiply (the
    family stores ``gain - 1``). ``lb_coef`` weighs the router's balance
    term, which ``MoE`` attaches in backward. ``dtype`` is the trunk's;
    parameters stay float32, and so do the decay and the write strength."""
    key_width = linear_key_heads * linear_key_dim
    value_width = linear_value_heads * linear_value_dim
    rotary_dim = int(head_dim * partial_rotary_factor)

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def part(x, first, end):
        return sym.slice_axis(x, axis=-1, begin=first, end=end)

    def swiglu(u, width, name):
        hidden = sym.Activation(linear(u, width, name + "_gate"),
                                act_type="silu") * linear(u, width,
                                                          name + "_up")
        return linear(hidden, hidden_size, name + "_down")

    def per_head(x):
        """(B, T, Hv) in the trunk's dtype -> (B, Hv, T) float32."""
        return sym.transpose(sym.Cast(x, dtype="float32"), axes=(0, 2, 1))

    def per_head_parameter(name, low, high):
        """(Hv, 1) float32, one a value head."""
        return log_spaced_parameter(name, (linear_value_heads, 1), 0, low,
                                    high)

    def delta_net(u, pre):
        qkvz = linear(u, 2 * key_width + 2 * value_width,
                      pre + "in_proj_qkvz")
        ba = linear(u, 2 * linear_value_heads, pre + "in_proj_ba")
        qkv = sym.CausalConv1D(
            part(qkvz, 0, 2 * key_width + value_width), kernel=conv_kernel,
            name=pre + "conv")
        q = split_heads(part(qkv, 0, key_width), linear_key_heads,
                        linear_key_dim)
        k = split_heads(part(qkv, key_width, 2 * key_width),
                        linear_key_heads, linear_key_dim)
        v = split_heads(part(qkv, 2 * key_width, 2 * key_width + value_width),
                        linear_value_heads, linear_value_dim)
        beta = sym.Activation(per_head(part(ba, 0, linear_value_heads)),
                              act_type="sigmoid")
        # g = -exp(A_log) * softplus(a + dt_bias), one a value head
        a_log = per_head_parameter(pre + "A_log", 1.0, 16.0)
        dt_bias = per_head_parameter(pre + "dt_bias", 0.001, 0.1)
        a = per_head(part(ba, linear_value_heads, 2 * linear_value_heads))
        g = sym.broadcast_mul(
            sym.negative(sym.exp(a_log)),
            sym.Activation(sym.broadcast_add(a, dt_bias),
                           act_type="softrelu"))
        o = sym.GatedDeltaRule(q, k, v, g, beta, name=pre + "delta")
        # the gated norm: over the width of each value head, one gain
        o = norm(sym.transpose(o, axes=(0, 2, 1, 3)), pre + "out_norm")
        z = sym.Reshape(
            part(qkvz, 2 * key_width + value_width,
                 2 * key_width + 2 * value_width),
            shape=(0, 0, linear_value_heads, linear_value_dim))
        y = o * sym.Activation(z, act_type="silu")
        return linear(sym.Reshape(y, shape=(0, 0, -1)), hidden_size,
                      pre + "out_proj")

    def attention(u, pre):
        def heads(x, count, name):
            # the norm runs over the head_dim of each head, one gain
            x = sym.Reshape(x, shape=(0, 0, count, head_dim))
            x = sym.transpose(norm(x, name), axes=(0, 2, 1, 3))
            return sym.RotaryEmbedding(x, base=rope_theta,
                                       rotary_dim=rotary_dim)

        qg = sym.Reshape(linear(u, 2 * num_heads * head_dim, pre + "q"),
                         shape=(0, 0, num_heads, 2 * head_dim))
        q = heads(part(qg, 0, head_dim), num_heads, pre + "q_norm")
        gate = sym.Reshape(part(qg, head_dim, 2 * head_dim),
                           shape=(0, 0, -1))
        k = heads(linear(u, num_kv_heads * head_dim, pre + "k"),
                  num_kv_heads, pre + "k_norm")
        v = split_heads(linear(u, num_kv_heads * head_dim, pre + "v"),
                        num_kv_heads, head_dim)
        a = sym.RingAttention(q, k, v, causal=True, name=pre + "attn")
        return linear(merge_heads(a) * sym.Activation(gate,
                                                      act_type="sigmoid"),
                      hidden_size, pre + "o")

    def sparse(u, pre):
        m = sym.MoE(
            u, num_experts=num_experts, num_hidden=expert_width, top_k=top_k,
            route_norm=route_norm, lb_coef=lb_coef,
            num_local_experts=num_local_experts, expert_offset=expert_offset,
            name=pre + "moe")
        if not shared_expert_width:
            return m
        gate = sym.Activation(linear(u, 1, pre + "shared_expert_gate"),
                              act_type="sigmoid")
        return m + sym.broadcast_mul(
            gate, swiglu(u, shared_expert_width, pre + "shared"))

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i in range(num_layers):
            pre = f"l{i}_"
            full = (i + 1) % full_attention_interval == 0
            u = norm(x, pre + "input_norm")
            x = x + (attention(u, pre) if full else delta_net(u, pre))
            x = x + sparse(norm(x, pre + "post_norm"), pre)
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
