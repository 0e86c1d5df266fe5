"""Builder of ``phi-4-mini-flash``: the program's ``sym_gen``, the seeded
weights (:func:`init_rule`: Mamba's own ranges for the scan, normal(0, 0.02)
elsewhere, gains and biases that move the answer where they are left out),
the model FLOPs of the configuration as it is run (one chip's share of the
deployment), the least work of its ``RingAttention``, ``SelectiveScan``
and ``CausalConv1D`` operators for their roofline shares, and what the
graph wires across layers, counted from the symbol (:func:`graph_counts`)."""

from __future__ import annotations

import collections
import json
import math

INIT_STD, GAIN_STD, LAMBDA_STD = 0.02, 0.1, 0.1
TAP_STD = 0.5                # 1 / sqrt(4 taps), as 0.02 is 1 / sqrt(2560)
A_RANGE = (1.0, 16.0)        # A = exp(A_log): Mamba's 1 .. d_state
DT_RANGE = (0.001, 0.1)      # exp(dt_bias), about softplus(dt_bias)
SCAN_KINDS = ("mamba", "mamba_memory")
ATTENTION_KINDS = ("window", "full_shared", "cross")


def sym_gen(cfg, mx, dropout=None):
    """(sym_gen, state_names) for ``BucketingModule``. The model has no
    dropout and no recurrent state across rows; ``dropout`` is the driver's
    signature."""
    from mxnet_tpu import models

    return models.phi4flash_sym_gen(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_kinds=tuple(cfg["layer_kinds"]),
        layer_ids=tuple(cfg["layer_ids"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        sliding_window=cfg["sliding_window"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        layer_norm_eps=cfg["layer_norm_eps"], subln_eps=cfg["subln_eps"],
        attention_bias=cfg["attention_bias"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"]), []


def graph_counts(cfg, mx):
    """What the program's graph wires across layers, counted from the symbol
    the cell binds (its JSON) by structure, whatever its nodes are named. A
    node's rows descend from the ``LayerNorm`` its first input leads back
    to. ``differential_layers``: pairs of ``RingAttention`` nodes whose
    queries descend from one norm and which weigh the SAME values (two
    softmaxes the graph subtracts). ``shared_kv_layers``: those of them
    whose keys descend from ANOTHER norm than their queries: a layer that
    projects a query only and reads the keys and values a layer below made.
    ``memory_gate_layers``: products of a ``SelectiveScan``'s output with
    rows that descend from another norm than the scan's own channels (a
    Gated Memory Unit; the scan layer's own gate ``y * silu(z)`` descends
    from the scan's norm). A builder that gave a cross layer keys of its
    own, or a unit a gate without the memory, reads one fewer."""
    symbol = sym_gen(cfg, mx)[0](max(cfg["buckets"]))[0]
    nodes = json.loads(symbol.tojson())["nodes"]

    def norm_of(i):
        while nodes[i]["op"] not in ("LayerNorm", "null"):
            i = nodes[i]["inputs"][0][0]
        return i

    softmaxes = collections.defaultdict(list)
    for node in nodes:
        if node["op"] == "RingAttention":
            (q, *_), (k, *_), (v, *_) = node["inputs"]
            softmaxes[norm_of(q), v].append(norm_of(k))
    pairs = {at: keys for at, keys in softmaxes.items() if len(keys) == 2}
    gates = 0
    for node in nodes:
        if node["op"] == "_mul":
            a, b = (i for i, *_ in node["inputs"])
            for scan, other in ((a, b), (b, a)):
                gates += nodes[scan]["op"] == "SelectiveScan" \
                    and norm_of(other) != norm_of(scan)
    return {"differential_layers": len(pairs),
            "shared_kv_layers": sum(
                any(key != query for key in keys)
                for (query, _), keys in pairs.items()),
            "memory_gate_layers": gates}


def input_shapes(cfg, batch, seq_len):
    return {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}


def init_rule(name, shape):
    """(kind, scale, offset) of a seeded leaf. The scan's ``A_log`` is
    uniform over [log 1, log 16] and its ``dt_bias`` over [log 0.001, log
    0.1] (a step ``softplus(dt_bias)`` within 5% of ``exp(dt_bias)``):
    Mamba's own ranges, so that a channel's state remembers from one token
    to a thousand; the harness draws a leaf as ``draw * scale + offset``, so
    the 16 states of a channel are spread over Mamba's 1 .. 16 by the seed
    and not in order. A normal(0, 0.02) ``A_log`` would make every state
    forget at one rate. ``D`` is 1 + normal(0, 0.1). The convolution's taps
    are normal(0, 0.5): a depthwise convolution sums 4 numbers, not 2560,
    and taps of 0.02 shrink the scan's channels 25-fold, its ``B`` and ``C``
    with them: the recurrence, cubic in them, was then 4e-4 of the scan's
    output beside the ``D x`` skip and no comparison could see it; with
    these taps it is 0.3 of it (one layer at the published widths, T 1024:
    PERF.md section 6, PR 65). The embedding is
    normal(0, 0.02) like every projection: it is the head too (tied), and
    the final norm hands the head rows of length sqrt(2560), so a unit
    embedding would make logits of standard deviation 50. Gains are
    normal(1, 0.1), norm and projection biases normal(0, 0.02), the lambda
    vectors normal(0, 0.1): each moves the answer where it is left out."""
    if name.endswith("_A_log"):
        low, high = (math.log(a) for a in A_RANGE)
        return "uniform01", high - low, low
    if name.endswith("_dt_bias"):
        low, high = (math.log(a) for a in DT_RANGE)
        return "uniform01", high - low, low
    if name.endswith(("_gamma", "_scan_D")):
        return "normal", GAIN_STD, 1.0
    if name.endswith("_conv_weight"):
        return "normal", TAP_STD, 0.0
    if "_lambda_" in name:
        return "normal", LAMBDA_STD, 0.0
    return "normal", INIT_STD, 0.0


def sizes(cfg):
    """(head_dim, d_inner, states, dt_rank, taps)."""
    return (cfg["hidden_size"] // cfg["num_attention_heads"],
            cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"])


def window_of(cfg, kind):
    """Keys a query of a layer of ``kind`` reads; 0: every key before it."""
    return cfg["sliding_window"] if kind == "window" else 0


def mixer_macs_per_token(cfg, kind, t):
    """Multiply-adds of one token through a mixer of ``kind`` at rows of
    ``t`` positions. A scan: in_proj, the convolution's taps, x_proj,
    dt_proj, the recurrence (a decay's product, the write and the read of a
    channel's N states: 3 N a channel) and out_proj. A differential
    attention: its projections and, over the pairs its mask keeps, the two
    softmaxes' ``q.k`` over 64 and ``p.v`` over 128, 20 query pairs each. A
    GMU: its two projections."""
    from benchmark.lib import flops

    h = cfg["hidden_size"]
    d, inner, n, rank, taps = sizes(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    if kind in SCAN_KINDS:
        return 2 * h * inner + taps * inner + inner * (rank + 2 * n) \
            + rank * inner + 3 * n * inner + inner * h
    if kind == "gmu":
        return 2 * h * inner
    projections = 2 * h * heads * d + (
        0 if kind == "cross" else 2 * h * kv * d)
    pairs = flops.causal_pairs(t, window_of(cfg, kind)) / t
    return projections + 2 * (heads // 2) * pairs * (d + 2 * d)


def forward_macs_per_token(cfg):
    """Multiply-adds of one token position through what this chip computes:
    every layer's mixer and its feed-forward (fc1 to twice the intermediate
    width, fc2 back), and the sliced tied head."""
    h, t = cfg["hidden_size"], max(cfg["buckets"])
    mlp = 3 * h * cfg["intermediate_size"]
    return sum(mixer_macs_per_token(cfg, kind, t) + mlp
               for kind in cfg["layer_kinds"]) + h * cfg["vocab_size"]


def train_flops_per_unit(cfg):
    """Model FLOPs of one training token position (for ``kernels.mfu_pct``):
    3 x forward, 2 FLOPs a multiply-add, no recomputation."""
    from benchmark.lib import flops

    return flops.train_flops(forward_macs_per_token(cfg))


def scan_work(cfg, tokens, row_bytes=2, weight_bytes=4):
    """The least work of one ``SelectiveScan`` a training step: a token and
    state element's step product ``delta A``, its ``exp``, the state's
    multiply-add, the write ``delta x B`` and the read's multiply-add, 9
    FLOPs a (channel, state) forward and twice that backward; x, dt and y (C
    each) and B, C (N each) across HBM once forward and, with their
    gradients, twice backward; ``A_log``, ``D`` and ``dt_bias`` read once and
    their gradients written once. The softplus, the skip and the state's
    own traffic (it need never leave the chip's VMEM) are left out."""
    _, inner, n, _, _ = sizes(cfg)
    return {"flops": 3 * 9 * tokens * inner * n,
            "bytes": 3 * row_bytes * tokens * (3 * inner + 2 * n)
            + 2 * weight_bytes * inner * (n + 2)}


def conv_work(cfg, tokens, row_bytes=2, weight_bytes=4):
    """The least work of one scan layer's ``CausalConv1D`` a training step
    (as ``kimi-linear-48b-a3b.py:conv_work``): every tap's product forward
    once and backward twice; the channels in and out across HBM once each
    way, the taps and the bias read once and their gradients written once.
    The pad and the SiLU are left out."""
    _, inner, _, _, taps = sizes(cfg)
    return {"flops": 3 * 2 * tokens * inner * taps,
            "bytes": 2 * row_bytes * tokens * 2 * inner
            + 2 * weight_bytes * inner * (taps + 1)}


def operator_work(cfg, traffic):
    """{operator: {"flops", "bytes"}} of one training step, the least the
    mathematics needs (``lib/flops.py``: the rule and what each term leaves
    out), for the roofline metrics: ``RingAttention`` on the two nodes of
    every differential layer, 20 query heads of 64 over 10 key heads of 64
    and 10 value heads of 128, the band's pairs exactly on the window layer
    and the full triangle on the other two; ``SelectiveScan`` and
    ``CausalConv1D`` on the two scan layers."""
    from benchmark.lib import flops

    rows, t = traffic["batch_size"], max(cfg["buckets"])
    d = sizes(cfg)[0]
    kinds = cfg["layer_kinds"]
    attention = [flops.attention_work(
        rows, t, cfg["num_attention_heads"] // 2,
        cfg["num_key_value_heads"] // 2, d, 2 * d,
        window=window_of(cfg, kind))
        for kind in kinds if kind in ATTENTION_KINDS for _ in (1, 2)]
    scans = sum(kind in SCAN_KINDS for kind in kinds)
    return {"RingAttention": flops.add_work(*attention),
            "SelectiveScan": flops.add_work(
                *[scan_work(cfg, rows * t)] * scans),
            "CausalConv1D": flops.add_work(
                *[conv_work(cfg, rows * t)] * scans)}
