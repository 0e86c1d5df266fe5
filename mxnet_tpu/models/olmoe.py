"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; HF ``modeling_olmoe``):
a decoder of pre-norm blocks, each causal attention with RMS-normed queries
and keys and rotary positions, then a drop-free mixture of SiLU-gated
experts. Defaults are OLMoE-1B-7B's published sizes."""

from .. import symbol as sym
from .recipe import low_precision_io


# --- pieces the sparse-expert decoders share (``afmoe.py`` too) -------------
def linear(x, width, name, weight=None):
    """A bias-free projection of the last axis (``weight``: its variable,
    where several nodes read one)."""
    return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                              flatten=False, name=name,
                              **_optional(weight=weight))


def split_heads(x, heads, head_dim, norm=None):
    """(B, T, heads * D) -> (B, heads, T, D), the layout of RingAttention.
    ``norm``: applied to (B, T, heads, D) first, so over the D of each
    head with one gain for all of them (the per-head q/k norm of the
    AFMoE and Qwen3-MoE families)."""
    x = sym.Reshape(x, shape=(0, 0, heads, head_dim))
    return sym.transpose(norm(x) if norm else x, axes=(0, 2, 1, 3))


def merge_heads(a):
    """(B, heads, T, D) -> (B, T, heads * D)."""
    return sym.Reshape(sym.transpose(a, axes=(0, 2, 1, 3)), shape=(0, 0, -1))


def _optional(**inputs):
    """The keyword inputs that were given."""
    return {n: v for n, v in inputs.items() if v is not None}


def embed_tokens(data, vocab_size, hidden_size, dtype, weight=None):
    """Rows of the embedding (``weight``: its variable, where the head
    reads it too) in the trunk's ``dtype``."""
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=hidden_size,
                      name="embed", **_optional(weight=weight))
    return low_precision_io(x, dtype)


def next_token_head(x, label, vocab_size, hidden_size, dtype, ignore_label,
                    weight=None):
    """The head over the normed stream ``x`` and its float32 softmax: the
    rows' probabilities (B*T, vocab); rows whose label is ``ignore_label``
    train nothing. Untied, its own ``pred_weight``; tied
    (``tie_word_embeddings``), ``weight`` is the embedding's variable,
    whose gradient is then the sum of the two uses."""
    pred = sym.FullyConnected(sym.Reshape(x, shape=(-1, hidden_size)),
                              num_hidden=vocab_size, no_bias=True,
                              name="pred", **_optional(weight=weight))
    pred = low_precision_io(pred, dtype, out=True)
    return sym.SoftmaxOutput(
        pred, sym.Reshape(label, shape=(-1,)), use_ignore=True,
        ignore_label=ignore_label, name="softmax")


def olmoe_sym_gen(vocab_size=50304, hidden_size=2048, num_layers=16,
                  num_heads=16, num_experts=64, expert_width=1024, top_k=8,
                  rms_norm_eps=1e-5, rope_theta=10000.0, lb_coef=0.01,
                  z_coef=0.001, dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and next-token ids ``softmax_label`` (B, T) in, the
    rows' probabilities (B*T, vocab) out. ``dtype`` is the trunk's: the
    embedding is cast to it and the scores back to float32 for the loss;
    parameters stay float32. ``lb_coef`` and ``z_coef`` weigh the router's
    load-balancing and z terms, which ``MoE`` attaches in backward. Rows
    whose label is ``ignore_label`` (the pad) train nothing."""
    head_dim = hidden_size // num_heads

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_norm_eps, name=name)

    def proj(x, name):
        return linear(x, hidden_size, name)

    def heads(x, rotate):
        x = split_heads(x, num_heads, head_dim)
        return sym.RotaryEmbedding(x, base=rope_theta) if rotate else x

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        x = embed_tokens(data, vocab_size, hidden_size, dtype)
        for i in range(num_layers):
            pre = f"l{i}_"
            u = norm(x, pre + "input_norm")
            q = heads(norm(proj(u, pre + "q"), pre + "q_norm"), True)
            k = heads(norm(proj(u, pre + "k"), pre + "k_norm"), True)
            v = heads(proj(u, pre + "v"), False)
            a = sym.RingAttention(q, k, v, causal=True, name=pre + "attn")
            x = x + proj(merge_heads(a), pre + "o")
            x = x + sym.MoE(
                norm(x, pre + "post_norm"), num_experts=num_experts,
                num_hidden=expert_width, top_k=top_k, lb_coef=lb_coef,
                z_coef=z_coef, name=pre + "moe")
        pred = next_token_head(norm(x, "final_norm"), label, vocab_size,
                               hidden_size, dtype, ignore_label)
        return pred, ("data",), ("softmax_label",)

    return sym_gen
