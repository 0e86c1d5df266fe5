"""The fused attention kernels (``mxnet_tpu/ops/flash_attention.py``) in
Pallas's interpreter on the CPU, at small shapes: forward and the three
gradients against the whole score matrix in float32, for full causal
attention, bands whose edges cut a block, a window wider than T, and 8:1 /
1:1 grouped heads; the float32 fall-back; the visit list; the rule that says
where the kernels engage; and the executor's counters with a v5e described.
(The kernels compiled for a described v5e are in ``test_grouped_matmul.py``,
beside the other compile tests: one file, one worker, one libtpu.)
"""

import functools
import importlib

import numpy as np
import pytest

from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import pallas_support as ps

ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")

V5E_VMEM = 128 << 20
T, D = 512, 128
# (causal, window): blocks of 128 queries and keys
MASKS = {
    "causal": (True, 0),
    "band_edges_on_block_boundaries": (True, 128),
    "band_edges_cut_blocks": (True, 200),
    "band_narrower_than_a_block": (True, 40),
    "window_wider_than_T": (True, 1000),
    "not_causal": (False, 0),
}
HEADS = {"grouped_8_to_1": (8, 1), "grouped_4_to_2": (4, 2),
         "heads_1_to_1": (2, 2)}
# 3:1 runs under two masks only (test_kernels_with_a_group_no_power_of_two),
# 16 over 2 at a head of 256 (test_kernels_at_a_head_of_256)
ALL_HEADS = dict(HEADS, grouped_3_to_1=(3, 1), grouped_16_to_2=(16, 2))
TENSORS = ["output", "dq", "dk", "dv"]


def _whole_matrix(q, k, v, causal, window, scale):
    """softmax over the whole (T, T) score matrix in float32: the oracle,
    with the band and the grouped heads written out."""
    import jax
    import jax.numpy as jnp

    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    apart = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    if causal:
        ok = apart >= 0
        if window:
            ok = jnp.logical_and(ok, apart < window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _three_ways(mask, heads, dtype="bfloat16", bq=128, bk=128, d=D):
    """{tensor: (kernels', jax.numpy blocks', oracle's)} as float32 arrays,
    and the kernels' raw dtypes."""
    import jax
    import jax.numpy as jnp

    causal, window = MASKS[mask]
    H, kv = ALL_HEADS[heads]
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, g = (jax.random.normal(key, (1, h, T, d)).astype(dtype)
                  for key, h in zip(keys, (H, kv, kv, H)))
    scale = d ** -0.5
    plan = fa.Plan(bq, bk, 32 << 20)

    def kernels(q, k, v):
        return ra.blockwise_attention(q, k, v, causal, scale, 128, window,
                                      plan, True)

    def blocks(q, k, v):
        return ra.blockwise_attention(q, k, v, causal, scale, 128, window)

    def oracle(q, k, v):
        return _whole_matrix(q, k, v, causal, window, scale)

    sides = []
    for f in (kernels, blocks, oracle):
        out, vjp = jax.vjp(f, q, k, v)
        sides.append((out,) + vjp(g.astype(out.dtype)))
    dtypes = [str(a.dtype) for a in sides[0]]
    return {t: tuple(np.asarray(side[i], np.float32) for side in sides)
            for i, t in enumerate(TENSORS)}, dtypes


def _rel(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("mask", sorted(MASKS))
def test_kernels_match_the_whole_score_matrix(mask, heads, tensor):
    """bfloat16 operands, float32 softmax: the kernels are as far from the
    float32 oracle as the ``jax.numpy`` blocks are (both round p and ds to
    bfloat16 for their matmuls), and a last place of a bfloat16 from them."""
    got, dtypes = _three_ways(mask, heads)
    kernel, blocks, want = got[tensor]
    assert dtypes == ["bfloat16"] * 4
    assert kernel.shape == want.shape and np.isfinite(kernel).all()
    assert _rel(kernel, blocks) < 2.0 ** -6
    assert _rel(kernel, want) < 1.5 * _rel(blocks, want) + 2.0 ** -8
    assert _rel(kernel, want) < 2e-2


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("tiles", [(256, 128), (128, 256), (64, 512)])
def test_kernels_at_other_tiles(tiles, tensor):
    """Query blocks wider and narrower than key blocks: the visit list and
    the masks follow; a band of 200 keys cuts every kind of block."""
    got, _ = _three_ways("band_edges_cut_blocks", "grouped_4_to_2", bq=tiles[0],
                         bk=tiles[1])
    kernel, blocks, want = got[tensor]
    assert _rel(kernel, blocks) < 2.0 ** -6
    assert _rel(kernel, want) < 2e-2


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("mask", ["causal", "band_edges_cut_blocks"])
def test_kernels_with_a_group_no_power_of_two(mask, tensor):
    """3 query heads a key/value head: 3 x 128 rows a tile, a row's
    position still ``row & (bq - 1)``."""
    got, _ = _three_ways(mask, "grouped_3_to_1")
    kernel, blocks, want = got[tensor]
    assert _rel(kernel, blocks) < 2.0 ** -6
    assert _rel(kernel, want) < 2e-2


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("tiles", [(128, 512), (256, 512)])
def test_kernels_at_a_head_of_256(tiles, tensor):
    """16 query heads over 2 key/value heads of 256 (8 x 128 or 8 x 256
    rows a tile, two lane tiles a head), at the tiles the rule gives that
    shape at T 8192 and 4096."""
    got, dtypes = _three_ways("causal", "grouped_16_to_2", bq=tiles[0],
                              bk=tiles[1], d=256)
    kernel, blocks, want = got[tensor]
    assert dtypes == ["bfloat16"] * 4
    assert kernel.shape == want.shape and np.isfinite(kernel).all()
    assert _rel(kernel, blocks) < 2.0 ** -6
    assert _rel(kernel, want) < 1.5 * _rel(blocks, want) + 2.0 ** -8
    assert _rel(kernel, want) < 2e-2


def test_kernels_refuse_a_query_block_no_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        _three_ways("causal", "grouped_4_to_2", bq=96, bk=128)


@pytest.mark.parametrize("tensor", TENSORS)
@pytest.mark.parametrize("mask", ["causal", "band_edges_cut_blocks"])
def test_float32_falls_back_to_the_blocks(mask, tensor):
    """A float32 trunk has no plan: ``blockwise_attention`` runs the
    ``jax.numpy`` blocks at ``precision=HIGHEST``, to float32 accuracy."""
    assert ra.kernel_plan("float32", (1, 8, T, D), 1, *MASKS[mask]) is None
    got, dtypes = _three_ways(mask, "grouped_8_to_1", "float32")
    _, blocks, want = got[tensor]
    assert dtypes == ["float32"] * 4
    assert _rel(blocks, want) < 1e-5


# (T, bq, bk, causal, window) -> (first, end) key block of each query block
VISITS = {
    "full_triangle": ((512, 128, 128, True, 0),
                      ([0, 0, 0, 0], [1, 2, 3, 4])),
    "not_causal": ((512, 128, 128, False, 0),
                   ([0, 0, 0, 0], [4, 4, 4, 4])),
    "band_of_a_block": ((512, 128, 128, True, 128),
                        ([0, 0, 1, 2], [1, 2, 3, 4])),
    "band_that_cuts_blocks": ((512, 128, 128, True, 200),
                              ([0, 0, 0, 1], [1, 2, 3, 4])),
    "wide_key_blocks": ((1024, 128, 512, True, 256),
                        ([0, 0, 0, 0, 0, 0, 1, 1], [1, 1, 1, 1, 2, 2, 2, 2])),
    "wide_query_blocks": ((1024, 512, 128, True, 256),
                          ([0, 2], [4, 8])),
}


@pytest.mark.parametrize("case", sorted(VISITS))
def test_visit_list_skips_what_no_query_sees(case):
    """Every visible pair is in a visited block, and a block is visited
    only if it holds one."""
    args, (first, end) = VISITS[case]
    got_first, got_end = fa.visits(*args)
    assert got_first.tolist() == first and got_end.tolist() == end
    t, bq, bk, causal, window = args
    apart = np.arange(t)[:, None] - np.arange(t)[None, :]
    ok = np.ones((t, t), bool)
    if causal:
        ok = apart >= 0
        if window:
            ok &= apart < window
    seen = ok.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    visited = np.zeros_like(seen)
    for i, (a, b) in enumerate(zip(got_first, got_end)):
        visited[i, a:b] = True
    assert (visited == seen).all()
    assert fa.scored_pairs(*args) == visited.sum() * bq * bk


# (platform, VMEM, dtype, heads, kv heads, T, D, causal, window) -> tiles
RULE_CASES = {
    "trinity_window_layer_on_a_v5e": (
        ("tpu", V5E_VMEM, "bfloat16", 32, 4, 4096, 128, True, 2048),
        (256, 256)),
    "trinity_full_layer_on_a_v5e": (
        ("tpu", V5E_VMEM, "bfloat16", 32, 4, 4096, 128, True, 0), (256, 512)),
    "olmoe_layer_on_a_v5e": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 16, 4096, 128, True, 0),
        (512, 512)),
    "not_causal": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 16, 4096, 128, False, 0),
        (512, 512)),
    "head_of_256": (
        ("tpu", V5E_VMEM, "bfloat16", 8, 2, 2048, 256, True, 0), (512, 512)),
    "qwen3_next_full_layer_at_T_4096": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 2, 4096, 256, True, 0), (256, 512)),
    # 8 x 256 rows beside 50 MB of keys, values and their gradients are
    # 84 MB of the 67 allowed: the next narrower query block, at the limit
    "qwen3_next_full_layer_at_T_8192": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 2, 8192, 256, True, 0), (128, 512)),
    "head_of_256_too_long_at_any_tile": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 2, 16384, 256, True, 0), None),
    "T_only_128_divides": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 16, 128 * 7, 128, True, 0),
        (128, 128)),
    "group_of_7_28_heads_over_4": (
        ("tpu", V5E_VMEM, "bfloat16", 28, 4, 4096, 128, True, 0), (256, 512)),
    "group_of_5_40_heads_over_8": (
        ("tpu", V5E_VMEM, "bfloat16", 40, 8, 4096, 128, True, 0), (256, 512)),
    "group_of_6_48_heads_over_8": (
        ("tpu", V5E_VMEM, "bfloat16", 48, 8, 4096, 128, True, 2048),
        (256, 256)),
    "group_of_6_T_only_128_divides": (
        ("tpu", V5E_VMEM, "bfloat16", 48, 8, 2688, 128, True, 0), (128, 128)),
    "group_of_16_takes_the_narrowest": (
        ("tpu", V5E_VMEM, "bfloat16", 32, 2, 4096, 128, True, 0), (128, 512)),
    "group_of_64_too_tall_for_the_vmem": (
        ("tpu", V5E_VMEM, "bfloat16", 64, 1, 4096, 128, True, 0), None),
    "lowered_for_the_cpu": (
        ("cpu", V5E_VMEM, "bfloat16", 32, 4, 4096, 128, True, 2048), None),
    "no_tpu_or_several_chips_attached": (
        ("tpu", None, "bfloat16", 32, 4, 4096, 128, True, 2048), None),
    "float32_trunk_keeps_the_blocks": (
        ("tpu", V5E_VMEM, "float32", 32, 4, 4096, 128, True, 2048), None),
    "head_of_64": (
        ("tpu", V5E_VMEM, "bfloat16", 32, 4, 4096, 64, True, 0), None),
    "T_no_block_divides": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 16, 4096 + 64, 128, True, 0), None),
    "heads_the_key_value_heads_do_not_divide": (
        ("tpu", V5E_VMEM, "bfloat16", 6, 4, 4096, 128, True, 0), None),
    "window_without_causal": (
        ("tpu", V5E_VMEM, "bfloat16", 16, 16, 4096, 128, False, 512), None),
    "a_head_too_long_for_the_vmem": (
        ("tpu", 16 << 20, "bfloat16", 16, 16, 8192, 128, True, 0), None),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_says_where_the_kernels_engage(case):
    args, tiles = RULE_CASES[case]
    plan = fa.plan(*args)
    if tiles is None:
        assert plan is None
        return
    assert (plan.bq, plan.bk) == tiles
    _, vmem, _, _, _, t, _, _, _ = args
    assert t % plan.bq == 0 and t % plan.bk == 0
    assert plan.bq in (128, 256, 512)    # Mosaic's tiles; ``& (bq - 1)``
    assert plan.vmem_limit <= vmem * 3 // 4


@pytest.mark.parametrize("layer,plan", [
    ("trinity_window_layer_on_a_v5e", fa.Plan(256, 256, 46137344)),
    ("trinity_full_layer_on_a_v5e", fa.Plan(256, 512, 58720256)),
    ("olmoe_layer_on_a_v5e", fa.Plan(512, 512, 36700160))])
def test_accepted_cells_plans_are_what_they_were(layer, plan):
    """The tiles and the VMEM limit PR 33 measured the two accepted cells
    at: a rule that finds tiles for a new shape leaves these alone."""
    assert fa.plan(*RULE_CASES[layer][0]) == plan


@pytest.mark.parametrize("group", range(1, 17))
def test_rule_query_blocks_are_powers_of_two_for_any_group(group):
    for t in (1024, 2688, 4096):
        plan = fa.plan("tpu", V5E_VMEM, "bfloat16", 2 * group, 2, t, 128,
                       True, 0)
        assert plan.bq in (128, 256, 512) and t % plan.bq == 0
        assert group * plan.bq <= max(2048, group * 128)


def test_on_the_cpu_the_op_takes_the_blocks():
    """What the op asks where it is traced: no TPU here, so no plan: the
    blocks, chosen in Python."""
    assert ps.attached_vmem_bytes() is None
    assert ra.kernel_plan("bfloat16", (1, 32, 4096, 128), 4, True,
                          2048) is None


@pytest.mark.parametrize("chips,engages", [(1, True), (4, False)])
def test_rule_with_chips_attached(monkeypatch, chips, engages):
    """Several chips attached: XLA cannot partition a Mosaic call, so the
    blocks (``attached_vmem_bytes`` reads the device list)."""
    import jax
    from types import SimpleNamespace

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [SimpleNamespace(device_kind="TPU v5 lite")] * chips)
    plan = ra.kernel_plan("bfloat16", (1, 32, 4096, 128), 4, True, 2048)
    assert (plan is not None) == engages


# --- what the op declares a launch counts -------------------------------------

def _declared(q, k, v, dtype, platform, **attrs):
    """``RingAttention``'s launch counts for one node over operands of
    these shapes, in a program lowered for ``platform``."""
    import jax

    from mxnet_tpu.ops import registry

    op = registry.get("RingAttention")
    ins = [jax.ShapeDtypeStruct(s, dtype) for s in (q, k, v)]
    return op.launch_counts(
        ins, [jax.ShapeDtypeStruct(q[:3] + v[3:], dtype)],
        op.parse_params(dict(causal=True, **attrs)), platform)


# the attention layers of a tiny decoder of the benchmark's two
# architectures at a head of 128 and T 512: (query heads, key/value heads,
# the layers' windows)
DECODERS = {"trinity": (8, 1, (256, 0, 256, 256, 256)),
            "olmoe": (2, 2, (0,))}


@pytest.mark.parametrize("model,dtype,platform,kernel_layers", [
    ("trinity", "bfloat16", "tpu", 5),
    ("olmoe", "bfloat16", "tpu", 1),
    ("trinity", "bfloat16", "cpu", 0),
    ("trinity", "float32", "tpu", 0),
    ("olmoe", "float32", "tpu", 0),
])
def test_counter_rule_with_a_v5e_described(monkeypatch, model, dtype,
                                           platform, kernel_layers):
    """``executor.attention_kernel_layers``: the layers of a train program
    that run the kernels, from the rule the op follows asked with the
    platform the op is given; ``attention_scored_pairs`` follows the plan
    that runs."""
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    heads, kv, windows = DECODERS[model]
    held = {}
    for window in windows:
        counts = _declared((1, heads, 512, 128), (1, kv, 512, 128),
                           (1, kv, 512, 128), dtype, platform, window=window)
        for name, n in counts.items():
            held[name] = held.get(name, 0) + n
    assert held["executor.attention_layers"] == len(windows)
    assert held["executor.attention_kernel_layers"] == kernel_layers
    assert held["executor.attention_window_layers"] == sum(
        w > 0 for w in windows)
    if kernel_layers:
        def pairs(window):
            tiles = fa.plan("tpu", V5E_VMEM, dtype, heads, kv, 512, 128, True,
                            window)
            return fa.scored_pairs(512, tiles.bq, tiles.bk, True, window)

        want = {"trinity": 4 * pairs(256) + pairs(0),
                "olmoe": pairs(0)}[model]
    else:
        block = ra.block_q_of(1, heads, 512)
        want = {"trinity": 4 * ra.scored_pairs(512, True, 256, block)
                + ra.scored_pairs(512, True, 0, block),
                "olmoe": ra.scored_pairs(512, True, 0, block)}[model]
    assert held["executor.attention_scored_pairs"] == heads * want


@pytest.mark.parametrize("fused_kv", [False, True])
def test_counter_rule_reads_the_key_whatever_feeds_it(monkeypatch, fused_kv):
    """The key/value heads are the key operand's as the node is lowered: a
    variable, or entry 0 of a split (a fused kv projection). Asked over
    the shapes the graph infers for the node's entries."""
    import jax
    import mxnet_tpu as mx

    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    q = mx.sym.Variable("q")
    if fused_kv:
        kv = mx.sym.SliceChannel(mx.sym.Variable("kv"), num_outputs=2, axis=1,
                                 name="kv_split")
        k, v, shapes = kv[0], kv[1], {"kv": (1, 4, T, D)}
    else:
        k, v = mx.sym.Variable("k"), mx.sym.Variable("v")
        shapes = {"k": (1, 2, T, D), "v": (1, 2, T, D)}
    net = mx.sym.RingAttention(q, k, v, causal=True, name="attn")
    shapes["q"] = (1, 8, T, D)
    node, = [n for n in net._topo() if not n.is_variable
             and n.op.name == "RingAttention"]
    internals = net.get_internals()
    _, inferred, _ = internals.infer_shape(**shapes)
    entry = dict(zip(internals._outputs, inferred))
    ins = [jax.ShapeDtypeStruct(entry[e], "bfloat16") for e in node.inputs]
    held = node.op.launch_counts(ins, ins[:1], node.params(), "tpu")
    assert held["executor.attention_layers"] \
        == held["executor.attention_kernel_layers"] == 1
    tiles = fa.plan("tpu", V5E_VMEM, "bfloat16", 8, 2, T, D, True, 0)
    assert held["executor.attention_scored_pairs"] == 8 * fa.scored_pairs(
        T, tiles.bq, tiles.bk, True, 0)
