"""Ouro (Zhu et al. 2025, *Scaling Latent Reasoning via Looped Language
Models*, arXiv:2510.25741; ByteDance's ``config.json`` and the family's
public ``modeling_ouro.py``): a looped language model. ONE stack of
sandwich-normed decoder layers (bias-free multi-head attention with
rotate-half rotary positions, SwiGLU) and one final norm run
``total_ut_steps`` times over the same weights, each pass reading the normed
stream the pass before wrote; after every pass the one untied head gives an
exit's logits and the one ``early_exit_gate`` a score, and
``ExitSoftmaxOutput`` trains all of them on the expected loss over the exits
less the entropy of the exit distribution (the paper's Stage I). Defaults
are Ouro-2.6B's published sizes."""

from .. import symbol as sym
from .olmoe import embed_tokens, linear, merge_heads, split_heads


def ouro_sym_gen(vocab_size=49152, hidden_size=2048, num_layers=48,
                 num_heads=16, head_dim=128, intermediate_size=5632,
                 total_ut_steps=4, exit_entropy_beta=0.1, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities at the LAST exit (B*T, vocab) out (what the model
    predicts at the published ``early_exit_threshold`` of 1). The passes are
    unrolled in the symbol: one variable a weight (``l<i>_q_weight``, ...,
    ``final_norm_gamma``, ``pred_weight``, ``early_exit_gate_weight`` /
    ``_bias``), read by one node a pass (``u<t>_l<i>_q``, ...), so a
    weight's gradient is the sum over its ``total_ut_steps`` uses. ``dtype``
    is the trunk's; parameters stay float32, and an exit's logits reach the
    loss in the trunk's dtype. Rows whose label is ``ignore_label`` (the
    pad) train nothing."""

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        made = {}

        def var(name):
            """The one variable of a weight, whichever pass asks."""
            if name not in made:
                made[name] = sym.Variable(name)
            return made[name]

        def norm(x, node, weight):
            return sym.RMSNorm(x, var(weight + "_gamma"), eps=rms_norm_eps,
                               name=node)

        def proj(x, width, node, weight):
            return linear(x, width, node, weight=var(weight + "_weight"))

        def heads(x):
            return sym.RotaryEmbedding(
                split_heads(x, num_heads, head_dim), base=rope_theta)

        def layer(x, node, w):
            u = norm(x, node + "input_norm", w + "input_norm")
            width = num_heads * head_dim
            q = heads(proj(u, width, node + "q", w + "q"))
            k = heads(proj(u, width, node + "k", w + "k"))
            v = split_heads(proj(u, width, node + "v", w + "v"), num_heads,
                            head_dim)
            a = sym.RingAttention(q, k, v, causal=True, name=node + "attn")
            x = x + norm(proj(merge_heads(a), hidden_size, node + "o",
                              w + "o"),
                         node + "post_attn_norm", w + "post_attn_norm")
            u = norm(x, node + "pre_mlp_norm", w + "pre_mlp_norm")
            hidden = sym.Activation(
                proj(u, intermediate_size, node + "mlp_gate", w + "mlp_gate"),
                act_type="silu") * proj(u, intermediate_size,
                                        node + "mlp_up", w + "mlp_up")
            m = proj(hidden, hidden_size, node + "mlp_down", w + "mlp_down")
            return x + norm(m, node + "post_mlp_norm", w + "post_mlp_norm")

        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        exits, gates = [], []
        for t in range(1, total_ut_steps + 1):
            for i in range(num_layers):
                x = layer(x, f"u{t}_l{i}_", f"l{i}_")
            # the normed stream is the exit's and the next pass's input
            x = norm(x, f"u{t}_final_norm", "final_norm")
            rows = sym.Reshape(x, shape=(-1, hidden_size))
            exits.append(proj(rows, vocab_size, f"u{t}_pred", "pred"))
            gates.append(sym.FullyConnected(
                rows, var("early_exit_gate_weight"),
                var("early_exit_gate_bias"), num_hidden=1,
                name=f"u{t}_early_exit_gate"))
        pred = sym.ExitSoftmaxOutput(
            *exits, *gates, sym.Reshape(label, shape=(-1,)),
            num_exits=total_ut_steps, beta=exit_entropy_beta,
            use_ignore=True, ignore_label=ignore_label, name="softmax")
        return pred, ("data",), ("softmax_label",)

    return sym_gen
