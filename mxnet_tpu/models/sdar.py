"""SDAR (Cheng et al. 2025, arXiv:2510.06303; JetLM's ``config.json``,
``model_type`` sdar_moe): a Qwen3-MoE decoder (``keye_vl2.qwen3_moe_block``)
trained as a block-diffusion model (Arriola et al. 2025, arXiv:2503.09573,
over the masked diffusion of Sahoo et al. 2024, arXiv:2406.07524).

A training step reads every row twice. ``BlockDiffusionNoise`` draws, from
the step's rng, one noise level t a block of ``block_length`` positions and
masks each position with probability its block's t; the noised copy and the
clean one go through the trunk as the two halves of the batch axis, (2B, T),
noised first, both at positions 0..T-1, so every operator but attention acts
on a trunk row alone and unchanged. ``RingAttention(diffusion_block=)`` is
bidirectional inside a block and causal across blocks, the noised copy
reading its own block and the clean copy's earlier ones: the clean rows are
what generation keeps in its cache for finished blocks, the noised block's
rows what a denoising step computes. The head sees the noised copy alone,
and the loss is the cross-entropy of a MASKED position's own clean token
(no shift), weighed by 1/t of its block (``SoftmaxOutput(sample_weight=)``);
an unmasked position and a pad train nothing.

Defaults are SDAR-30B-A3B-Chat's published sizes."""

from .. import symbol as sym
from .keye_vl2 import qwen3_moe_block
from .olmoe import _optional, embed_tokens
from .recipe import low_precision_io


def sdar_sym_gen(vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, num_kv_heads=4, head_dim=128, num_experts=128,
                 expert_width=768, top_k=8, route_norm=True,
                 num_local_experts=0, expert_offset=0, block_length=4,
                 mask_id=None, noise_eps=1e-3, noise_seed=None,
                 rms_norm_eps=1e-6, rope_theta=1e6, lb_coef=0.001,
                 dtype="float32", ignore_label=0):
    """Return a ``sym_gen(seq_len)`` for ``BucketingModule``: token ids
    ``data`` (B, T) and ``softmax_label`` (B, T) in, the NOISED copy's
    probabilities (B*T, vocab) out, row i the distribution of position i's
    own token. The targets are ``data`` itself, pads (``ignore_label``)
    excepted; ``softmax_label``, the next-token ids every iterator and metric
    of this repo feeds a decoder, is not read by the objective: it is kept
    an input of the graph (its shape alone is read) so that the module binds
    the same iterators, ``fit`` the same metric and the benchmark's driver
    the same probe as for every other decoder here.

    ``mask_id`` (None: the last id of the vocabulary) stands where a
    position is masked. ``noise_seed`` None is what ``fit`` runs: fresh
    noise every step from the executor's stream; a number holds the noise to
    that seed (``BlockDiffusionNoise``), for a comparison with a reference
    that draws the same. ``lb_coef`` weighs the router's balance term, taken
    over all 2 B T trunk rows, against the loss a token (the mean over the B
    T positions). ``num_local_experts`` of the ``num_experts`` the
    router scores live here, from ``expert_offset`` (0: all of them), and
    ``vocab_size`` is this chip's slice. ``dtype`` is the trunk's;
    parameters stay float32."""
    mask_id = vocab_size - 1 if mask_id is None else mask_id
    block = dict(
        hidden_size=hidden_size, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        num_experts=num_experts, expert_width=expert_width, top_k=top_k,
        route_norm=route_norm, num_local_experts=num_local_experts,
        expert_offset=expert_offset, rms_norm_eps=rms_norm_eps,
        # ``MoE`` puts its balance term on the scale of ITS rows, two a
        # token here; the objective's is a token's
        rotary=dict(base=rope_theta), lb_coef=lb_coef / 2)

    def attention(pre):
        return lambda q, k, v, u: sym.RingAttention(
            q, k, v, causal=True, diffusion_block=block_length,
            name=pre + "attn")

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        clean = data + sym.zeros_like(label)     # the label's shape alone
        noise = sym.BlockDiffusionNoise(
            clean, block=block_length, mask_id=mask_id, eps=noise_eps,
            pad_id=ignore_label, name="noise", **_optional(seed=noise_seed))
        x = embed_tokens(sym.Concat(noise[0], clean, dim=0), vocab_size,
                         hidden_size, dtype)
        for i in range(num_layers):
            x = qwen3_moe_block(x, f"l{i}_", attention(f"l{i}_"), **block)
        # the noised copy alone: the first half of the batch axis
        x = sym.slice_axis(
            sym.Reshape(x, shape=(2, -1, seq_len, hidden_size)), axis=0,
            begin=0, end=1)
        x = sym.RMSNorm(x, eps=rms_norm_eps, name="final_norm")
        pred = sym.FullyConnected(sym.Reshape(x, shape=(-1, hidden_size)),
                                  num_hidden=vocab_size, no_bias=True,
                                  name="pred")
        pred = low_precision_io(pred, dtype, out=True)
        prob = sym.SoftmaxOutput(
            pred, sym.Reshape(clean, shape=(-1,)),
            sym.Reshape(noise[2], shape=(-1,)), use_ignore=True,
            ignore_label=ignore_label, sample_weight=True, name="softmax")
        return prob, ("data",), ("softmax_label",)

    return sym_gen
