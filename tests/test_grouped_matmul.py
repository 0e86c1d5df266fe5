"""The grouped-matmul kernels of ``MoE`` (``mxnet_tpu/ops/grouped_matmul.py``)
in Pallas's interpreter on the CPU, at small shapes: forward, dgrad (the
weights read transposed) and wgrad against ``jax.lax.ragged_dot`` and
``jax.vjp`` of it; the in-kernel cast; the rule that says where the kernels
engage; ``MoE`` through the kernel path against the plain reference; and
the kernels compiled for a described v5e at the OLMoE cell's widths (no
chip: a compile that passes is not a chip run).
"""

import functools
import importlib.util
import os
import re

import numpy as np
import pytest

from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import grouped_matmul as gm
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_VMEM = 128 << 20

# rows an expert; M = 512 in every pattern: two row tiles of 256 (four of
# 128 for wgrad), each of 128-row chunks
PATTERNS = {
    "uniform": [128, 128, 128, 128],
    "an_empty_expert": [200, 0, 184, 128],
    "one_expert_takes_every_row": [0, 0, 512, 0],
    "boundaries_inside_a_tile": [100, 156, 3, 253],
    "last_group_short": [255, 129, 127, 1],
    # a dead tail: rows past counts.sum() that are no group's (``ROWS`` has
    # M), which the kernels own: zeros forward and dgrad, nothing of them
    # in a live row or in wgrad
    "dead_tail_half_the_rows": [100, 56, 3, 97],
    "dead_tail_all_but_one_row": [0, 1, 0, 0],
    "dead_tail_of_whole_tiles": [60, 40, 20, 8],
    "dead_tail_after_an_empty_last_group": [130, 126, 44, 0],
    "dead_tail_and_no_live_row": [0, 0, 0, 0],
}
# M where it is more than the counts' sum
ROWS = {"dead_tail_half_the_rows": 512, "dead_tail_all_but_one_row": 512,
        "dead_tail_of_whole_tiles": 1024,
        "dead_tail_after_an_empty_last_group": 768,
        "dead_tail_and_no_live_row": 512}
DTYPES = {"bf16_rows_f32_weights": "float32",
          "bf16_rows_bf16_weights": "bfloat16"}
K, N = 256, 128


@functools.lru_cache(maxsize=None)
def _kernels_and_ragged_dot(pattern, weight_dtype):
    """{kind: (kernel's, ragged_dot's, the kernel's as it came, its twin)}
    for one pattern and weight dtype; panels of 128 so that the forward and
    dgrad kernels walk two weight panels a group and the prefetch wraps
    from one panel to the next. With a dead tail the kernels are handed
    large finite rows and a NaN cotangent there and ``ragged_dot`` is masked
    on both sides, the form ``MoE`` wrapped around it; the twin is the same
    kernel where the call is what it was before the tail was the kernels':
    forward and dgrad with the tail given to the last group that has rows
    (counts that fill M), wgrad with zeros in the tail."""
    import jax
    import jax.numpy as jnp

    counts = jnp.asarray(PATTERNS[pattern], jnp.int32)
    live, e = int(counts.sum()), counts.shape[0]
    m = ROWS.get(pattern, live)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(keys[0], (m, K)).astype(jnp.bfloat16)
    w = (0.1 * jax.random.normal(keys[1], (e, K, N))).astype(weight_dtype)
    g = jax.random.normal(keys[2], (m, N)).astype(jnp.bfloat16)
    mask = (jnp.arange(m) < live)[:, None]
    plan = gm.Plan(tm=256, tmw=128, tn=128, tk=128, tw=128,
                   vmem_limit=32 << 20)

    def kernel(r, w, counts=counts):
        return gm.grouped_matmul(r, w, gm.groups(counts, m, plan), plan, True)

    def ragged(r, w):
        return jnp.where(mask, jax.lax.ragged_dot(
            jnp.where(mask, r, 0), w.astype(jnp.bfloat16), counts), 0)

    def both(f, r, g):
        out, vjp = jax.vjp(f, r, w)
        return (out,) + vjp(g)

    got = both(kernel, jnp.where(mask, rows, 3e38).astype(rows.dtype),
               jnp.where(mask, g, jnp.nan))
    want = both(ragged, rows, g)
    twin = (None,) * 3
    if live < m:
        last = max(np.flatnonzero(PATTERNS[pattern]), default=0)
        filled = both(functools.partial(
            kernel, counts=counts.at[last].add(m - live)), rows, g)
        zeros = both(kernel, jnp.where(mask, rows, 0), jnp.where(mask, g, 0))
        twin = tuple(np.asarray(a, np.float32)
                     for a in filled[:2] + zeros[2:])
    return {kind: (np.asarray(a, np.float32), np.asarray(b, np.float32), a, t)
            for kind, a, b, t in zip(("forward", "dgrad", "wgrad"), got,
                                     want, twin)}


@pytest.mark.parametrize("kind", ["forward", "dgrad", "wgrad"])
@pytest.mark.parametrize("weights", sorted(DTYPES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_kernel_matches_ragged_dot(pattern, weights, kind):
    """Both round float32 sums of the same bfloat16 products to bfloat16
    once; they may order the sums differently, so an element is at most a
    last place of a bfloat16 (2^-8 of its size) apart."""
    got, want, raw, twin = _kernels_and_ragged_dot(
        pattern, DTYPES[weights])[kind]
    assert got.shape == want.shape
    assert str(raw.dtype) == {"wgrad": DTYPES[weights]}.get(kind, "bfloat16")
    assert np.isfinite(got).all()
    scale = np.maximum(np.abs(want), max(np.abs(want).max() * 2.0 ** -7,
                                         1e-30))  # no live row: all zeros
    assert np.max(np.abs(got - want) / scale) <= 2.0 ** -7
    if kind == "wgrad":     # an expert with no rows has a zero gradient
        for e, c in enumerate(PATTERNS[pattern]):
            assert c or not got[e].any()
    if twin is None:
        return
    # a dead tail: the live rows' bits are those of the call that had no
    # tail, and nothing the tail held reached them or wgrad
    live = sum(PATTERNS[pattern])
    if kind == "wgrad":
        assert np.array_equal(got, twin)
    else:
        assert got.shape[0] == ROWS[pattern] > live
        assert not got[live:].any()
        assert np.array_equal(got[:live], twin[:live])


def test_in_kernel_cast_is_astype_bfloat16_bit_for_bit():
    """Identity rows times one float32 tile: every output element is one
    product 1 x bfloat16(w), so the output IS the kernel's cast of the
    tile."""
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 128)) * 3.0
    w = w.at[0, 0, :4].set(jnp.asarray(
        [1.00390625, 1.01171875, -1.00390625, 3.3895e38]))  # ties, near max
    eye = jnp.eye(128, dtype=jnp.bfloat16)
    plan = gm.Plan(128, 128, 128, 128, 128, 32 << 20)
    one_group = gm.groups(jnp.asarray([128], jnp.int32), 128, plan)
    out = gm.grouped_matmul(eye, w, one_group, plan, True)
    want = w[0].astype(jnp.bfloat16)
    assert out.dtype == want.dtype
    assert np.array_equal(np.asarray(out).view(np.uint16),
                          np.asarray(want).view(np.uint16))


VISIT_CASES = {
    "uniform_aligned": ([256, 256, 256, 256], 256),
    "boundaries_inside_tiles": ([100, 156, 3, 253, 512], 128),
    "empty_groups_first_last_and_between": ([0, 300, 0, 0, 212, 0], 128),
    "one_group_has_every_row": ([0, 0, 1024, 0], 256),
    "many_groups_in_one_tile": ([5, 7, 1, 3, 112, 128], 128),
    # (counts, row tile, M): a dead tail past the counts' sum
    "dead_tail_of_whole_tiles": ([100, 56, 0, 100], 256, 1024),
    "dead_tail_all_but_one_row": ([0, 0, 1, 0], 128, 1024),
    "dead_tail_from_inside_a_tile": ([0, 300, 0, 0, 212, 0], 128, 1024),
    "dead_tail_after_an_empty_last_group": ([130, 126, 44, 0], 256, 768),
    "dead_tail_and_no_live_row": ([0, 0, 0], 128, 256),
}


@pytest.mark.parametrize("visit_empty", [False, True])
@pytest.mark.parametrize("case", sorted(VISIT_CASES))
def test_visit_lists_are_megabloxs(case, visit_empty):
    """The dense-compare visit lists against the metadata of jax's own
    megablox kernels, which they replace: same visits in the same order
    (the row tile of an empty group's visit is free: nothing is read). The
    lists as ``groups`` asks for them: wgrad's visit the empty groups, the
    forward's the dead tiles, one visit each after megablox's, in order,
    under the last group that has rows, within the static length."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    counts, tm = VISIT_CASES[case][:2]
    m = (VISIT_CASES[case] + (sum(counts),))[2]
    (offsets, group_ids, m_tile_ids), visits = make_group_metadata(
        group_sizes=jnp.asarray(counts, jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=len(counts),
        visit_empty_groups=visit_empty)
    got = gm._visit_lists(jnp.asarray(counts, jnp.int32), m, tm, visit_empty)
    v = int(visits)
    live_tiles = -(-sum(counts) // tm)
    dead = 0 if visit_empty else m // tm - live_tiles
    assert int(got[3]) == v + dead <= len(got[1]) == m // tm + len(counts) - 1
    assert np.array_equal(got[0], offsets)
    assert np.array_equal(got[1][:v], group_ids[:v])
    held = np.asarray(counts)[np.asarray(group_ids[:v])] > 0
    assert np.array_equal(np.asarray(got[2][:v])[held],
                          np.asarray(m_tile_ids[:v])[held])
    assert dead == 0 or len(VISIT_CASES[case]) == 3
    assert np.array_equal(got[2][v:v + dead], live_tiles + np.arange(dead))
    assert np.array_equal(got[1][v:v + dead], [max(
        np.flatnonzero(counts), default=0)] * dead)


def test_kernels_come_back_from_the_cache_directory(tmp_path, monkeypatch):
    """A second process (here: an emptied memo) reads the exported kernels
    and traces none: the same program text, no call into the kernels'
    Python."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(ps, "_kernel_cache_dir", lambda: str(tmp_path))
    monkeypatch.setattr(ps, "_EXPORTED", {})
    plan = gm.plan("tpu", V5E_VMEM, "bfloat16", "float32", 512, 256, 128)
    counts = jnp.asarray([100, 156, 0, 256], jnp.int32)

    def step(rows, w, g):
        gr = gm.groups(counts, 512, plan)
        out, vjp = jax.vjp(
            lambda r, w: gm.grouped_matmul(r, w, gr, plan), rows, w)
        return (out,) + vjp(g)

    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((512, 256), jnp.bfloat16), ((4, 256, 128), jnp.float32),
        ((512, 128), jnp.bfloat16))]
    first = str(jax.make_jaxpr(step)(*args))
    assert "call_exported" in first and len(list(tmp_path.iterdir())) == 3

    def _gmm(*a, **k):
        raise AssertionError("traced again")

    def _tgmm(*a, **k):
        raise AssertionError("traced again")

    monkeypatch.setattr(ps, "_EXPORTED", {})
    monkeypatch.setattr(gm, "_gmm", _gmm)
    monkeypatch.setattr(gm, "_tgmm", _tgmm)
    again = str(jax.make_jaxpr(step)(*args))
    assert "call_exported" in again and "pallas_call" not in again


def test_no_cache_directory_where_jaxs_cache_is_off():
    """The suite runs with jax's persistent cache off (conftest): kernels
    are traced in place and nothing is written."""
    assert ps._kernel_cache_dir() is None


# (platform, VMEM bytes, rows dtype, weight dtype, M, K, N) -> engages?
RULE_CASES = {
    "olmoe_gate_on_a_v5e": (("tpu", V5E_VMEM, "bfloat16", "float32",
                             32768, 2048, 1024), True),
    "olmoe_down_on_a_v5e": (("tpu", V5E_VMEM, "bfloat16", "float32",
                             32768, 1024, 2048), True),
    "moonlight_64_experts_top6_width_1408": (
        ("tpu", V5E_VMEM, "bfloat16", "float32", 8192 * 6, 2048, 1408), True),
    "granite_h_small_72_experts_top10_width_768": (
        ("tpu", V5E_VMEM, "bfloat16", "float32", 4096 * 10, 4096, 768), True),
    "bfloat16_weights": (("tpu", V5E_VMEM, "bfloat16", "bfloat16",
                          1024, 256, 384), True),
    "lowered_for_the_cpu": (("cpu", V5E_VMEM, "bfloat16", "float32",
                             32768, 2048, 1024), False),
    "no_tpu_attached": (("tpu", None, "bfloat16", "float32",
                         32768, 2048, 1024), False),
    "float32_trunk_keeps_ragged_dot": (("tpu", V5E_VMEM, "float32", "float32",
                                        32768, 2048, 1024), False),
    "float16_weights": (("tpu", V5E_VMEM, "bfloat16", "float16",
                         32768, 2048, 1024), False),
    "width_the_tiles_do_not_divide": (("tpu", V5E_VMEM, "bfloat16", "float32",
                                       32768, 2048, 1000), False),
    "depth_the_tiles_do_not_divide": (("tpu", V5E_VMEM, "bfloat16", "float32",
                                       32768, 32, 128), False),
    "rows_no_tile_divides": (("tpu", V5E_VMEM, "bfloat16", "float32",
                              32768 + 64, 2048, 1024), False),
    "rows_only_the_smallest_tile_divides": (
        ("tpu", V5E_VMEM, "bfloat16", "float32", 128 * 7, 256, 256), True),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_says_where_the_kernels_engage(case):
    args, engages = RULE_CASES[case]
    plan = gm.plan(*args)
    assert (plan is not None) == engages
    if plan is None:
        return
    _, vmem, _, _, m, k, n = args
    assert m % plan.tm == 0 and plan.tm in gm._ROW_TILES
    assert m % plan.tmw == 0 and plan.tmw in gm._WGRAD_ROW_TILES
    for panel, width in ((plan.tn, n), (plan.tk, k), (plan.tw, n)):
        assert width % panel == 0 and panel % 128 == 0
    assert plan.vmem_limit <= vmem * 3 // 4


def test_rule_narrows_the_weight_panel_to_a_small_vmem():
    """One expert's float32 matrix of the OLMoE cell is 8 MiB: whole in a
    v5e's 128 MiB, in panels where a core has 16 MiB."""
    args = ("bfloat16", "float32", 32768, 2048, 1024)
    whole, small = gm.plan("tpu", V5E_VMEM, *args), gm.plan(
        "tpu", 16 << 20, *args)
    assert (whole.tn, whole.tk, whole.tw) == (1024, 2048, 1024)
    assert small is not None and small.tn < 1024 and small.tw < 1024


def _moe_kernel_matmuls(platform, data_dtype, weight_dtype, rows, hidden,
                         width, experts=64, top_k=8):
    """``executor.moe_kernel_matmuls`` as ``MoE`` declares it for one layer
    that holds every expert: ``rows`` assignments of ``hidden`` features
    through experts of ``width``."""
    import jax

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    ins = [struct((rows // top_k, hidden), data_dtype),
           struct((experts, hidden), weight_dtype),
           struct((experts, hidden, width), weight_dtype),
           struct((experts, hidden, width), weight_dtype),
           struct((experts, width, hidden), weight_dtype)]
    op = registry.get("MoE")
    params = op.parse_params(dict(num_experts=experts, num_hidden=width,
                                  top_k=top_k))
    counts = op.launch_counts(ins, ins[:1], params, platform)
    assert counts["executor.moe_assignments"] == rows
    return counts["executor.moe_kernel_matmuls"]


def test_counter_rule_counts_nine_or_none():
    """What ``MoE`` declares a launch counts: on the CPU no kernel
    (``attached_vmem_bytes`` is None here), and the op takes ragged_dot,
    chosen in Python."""
    assert ps.attached_vmem_bytes() is None
    assert _moe_kernel_matmuls("cpu", "bfloat16", "float32",
                               32768, 2048, 1024) == 0
    assert _moe_kernel_matmuls("tpu", "bfloat16", "float32",
                               32768, 2048, 1024) == 0


@pytest.mark.parametrize("chips,kind,vmem", [
    (1, "TPU v5 lite", V5E_VMEM), (4, "TPU v5 lite", None),
    (1, "TPU v9 not listed", None)])
def test_attached_vmem_is_of_the_one_listed_chip(monkeypatch, chips, kind,
                                                 vmem):
    """Several chips (XLA cannot partition a Mosaic call) or a kind whose
    VMEM is not listed: no figure, so no kernel."""
    import jax
    from types import SimpleNamespace

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(device_kind=kind)] * chips)
    assert ps.attached_vmem_bytes() == vmem


def test_counter_rule_with_a_v5e_attached(monkeypatch):
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    nine = ("bfloat16", "float32", 32768, 2048, 1024)
    assert _moe_kernel_matmuls("tpu", *nine) == 9
    assert _moe_kernel_matmuls("cpu", *nine) == 0
    assert _moe_kernel_matmuls("tpu", "float32", "float32",
                               32768, 2048, 1024) == 0
    # a hidden size no tile divides: all nine take ragged_dot
    assert _moe_kernel_matmuls("tpu", "bfloat16", "float32",
                               32768, 2000, 1024) == 0


# --- MoE through the kernel path against the plain reference ----------------

MOE_INPUTS = ["data", "router_weight", "gate_weight", "up_weight",
              "down_weight"]


@functools.lru_cache(maxsize=None)
def _moe_three_ways():
    """(kernel path, ragged_dot path, float32 reference), each the output
    and the gradient of every input of one ``MoE`` layer: 64 bfloat16
    tokens of 128 features, 4 experts of width 128, top-2, float32
    masters. The kernel path is forced through the rule's inputs: a plan
    for a v5e, run in the interpreter."""
    import jax
    import jax.numpy as jnp

    spec = importlib.util.spec_from_file_location(
        "olmoe_reference",
        os.path.join(ROOT, "benchmark", "reference", "olmoe-1b-7b.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    rs = np.random.RandomState(11)
    tok = jnp.asarray(rs.randn(64, 128), jnp.bfloat16)
    ws = [jnp.asarray(rs.randn(*s) * 0.1, jnp.float32)
          for s in ((4, 128), (4, 128, 128), (4, 128, 128), (4, 128, 128))]
    head = jnp.asarray(rs.randn(64, 128), jnp.float32)
    params = registry.get("MoE").parse_params(dict(
        num_experts=4, num_hidden=128, top_k=2, lb_coef=0.01, z_coef=0.001))
    def run(matmul):
        def scalar(*ins):
            out = dt._moe(list(ins), params, registry.OpMode())
            return jnp.sum(out.astype(jnp.float32) * head), out

        old, dt._expert_matmul = dt._expert_matmul, matmul
        try:
            grads, out = jax.grad(scalar, argnums=tuple(range(5)),
                                  has_aux=True)(tok, *ws)
        finally:
            dt._expert_matmul = old
        return [out] + list(grads)

    def reference(*ins):
        out, pen = ref.moe(*ins, 2, 0.01, 0.001)
        return jnp.sum(out * head) + ins[0].shape[0] * pen, out

    expert_matmul = dt._expert_matmul

    def on_a_v5e(counts, rows_dtype, m, weights, platform=None):
        return expert_matmul(counts, rows_dtype, m, weights, "tpu", V5E_VMEM,
                             interpret=True)

    kernel = run(on_a_v5e)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *ins: on_a_v5e(
            jnp.asarray([32] * 4, jnp.int32), tok.dtype, 128, ws[1:])[0](
                *ins))(jnp.repeat(tok, 2, 0), ws[1]))
    # lowered for the CPU: ragged_dot alone, whatever is attached
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *ins: expert_matmul(
            jnp.asarray([32] * 4, jnp.int32), tok.dtype, 128, ws[1:], "cpu",
            V5E_VMEM)[0](*ins))(jnp.repeat(tok, 2, 0), ws[1]))
    ragged = run(expert_matmul)     # no TPU here: ragged_dot alone
    with jax.default_matmul_precision("highest"):
        grads, out = jax.grad(reference, argnums=tuple(range(5)),
                              has_aux=True)(tok.astype(jnp.float32), *ws)
    return [[np.asarray(a, np.float64) for a in side]
            for side in (kernel, ragged, [out] + list(grads))]


def _rel(a, b):
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.mark.parametrize("tensor", ["output"] + MOE_INPUTS)
def test_moe_through_the_kernels_matches_the_reference(tensor):
    """The kernel path is the ragged_dot path with other orders of float32
    sums: a bfloat16 rounding apart at most, and no further from the
    float32 reference than the ragged_dot path is (a bfloat16 trunk: the
    tolerance is that path's own distance, with a half on top)."""
    i = (["output"] + MOE_INPUTS).index(tensor)
    kernel, ragged, want = (side[i] for side in _moe_three_ways())
    assert kernel.shape == want.shape
    assert _rel(kernel, ragged) < 2.0 ** -7
    assert _rel(kernel, want) < 1.5 * _rel(ragged, want) + 1e-6
    assert _rel(kernel, want) < 3e-2


# --- compiled for a described v5e at the cell's widths ----------------------

@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("matmul", ["gate_and_up", "down"])
def test_kernels_compile_for_a_v5e_at_the_cell_widths(one_chip, matmul):
    """Mosaic accepts forward, dgrad and wgrad at 32 768 rows, 64 experts,
    2048 x 1024 (what interpret mode cannot show: tiling, VMEM)."""
    import jax
    import jax.numpy as jnp

    m, e = 32768, 64
    k, n = (2048, 1024) if matmul == "gate_and_up" else (1024, 2048)
    plan = gm.plan("tpu", V5E_VMEM, jnp.bfloat16, jnp.float32, m, k, n)

    def step(rows, w, counts, g):
        gr = gm.groups(counts, m, plan)
        out, vjp = jax.vjp(
            lambda r, w: gm.grouped_matmul(r, w, gr, plan), rows, w)
        return (out,) + vjp(g)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((m, k), jnp.bfloat16), arg((e, k, n), jnp.float32),
        arg((e,), jnp.int32), arg((m, n), jnp.bfloat16)).compile()
    text = compiled.as_text()
    for name in ("moe_gmm", "moe_gmm_dgrad", "moe_gmm_wgrad"):
        assert name in text
    # no bfloat16 copy of the weights: the largest temporary is the
    # bfloat16 wgrad before its float32 convert
    assert compiled.memory_analysis().temp_size_in_bytes <= e * k * n * 2 \
        + (8 << 20)


@pytest.mark.parametrize("layer", ["trinity_window", "trinity_full",
                                   "olmoe_full", "group_of_7", "group_of_5",
                                   "group_of_6_window", "qwen3_next_4k",
                                   "qwen3_next_8k", "mellum2_window",
                                   "mellum2_full"])
def test_attention_kernels_compile_for_a_v5e_at_the_cell_widths(one_chip,
                                                                layer):
    """Mosaic accepts the fused attention kernels
    (``ops/flash_attention.py``; their other tests are in
    ``test_flash_attention.py``) at T 4096, head 128: 32 query heads over 4
    key/value heads with a band of 2048 and without, and 16 over 16; groups
    that are no power of two (28 over 4, 40 and 48 over 8: the rule's tiles
    are G x 256 rows); 16 over 2 heads of 256 at T 4096 and 8192 (the
    Qwen3-Next cell's full layer: 8 x 128 rows a tile at 8192, where the
    rule's count is exactly the half of the VMEM it allows); 32 over 4 at T
    16 384 under a band of 1024 keys, where the rule takes its narrowest key
    block (256 x 128), and without one, where a tile of 256 positions no
    longer fits beside the keys and the rule gives 128 x 512 (the Mellum2
    cell's window and full layers); no score tile among the temporaries."""
    import importlib

    import jax
    import jax.numpy as jnp

    ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")
    heads, kv, window, t, d = {
        "trinity_window": (32, 4, 2048, 4096, 128),
        "trinity_full": (32, 4, 0, 4096, 128),
        "olmoe_full": (16, 16, 0, 4096, 128),
        "group_of_7": (28, 4, 0, 4096, 128),
        "group_of_5": (40, 8, 0, 4096, 128),
        "group_of_6_window": (48, 8, 2048, 4096, 128),
        "qwen3_next_4k": (16, 2, 0, 4096, 256),
        "qwen3_next_8k": (16, 2, 0, 8192, 256),
        "mellum2_window": (32, 4, 1024, 16384, 128),
        "mellum2_full": (32, 4, 0, 16384, 128)}[layer]
    from mxnet_tpu.ops import flash_attention as fa

    plan = fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, kv, t, d, True,
                   window)
    if layer.startswith("mellum2"):
        assert (plan.bq, plan.bk) == ((256, 128) if window else (128, 512))

    def step(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: ra.blockwise_attention(
                q, k, v, True, d ** -0.5, 256, window, plan), q, k, v)
        return (out,) + vjp(g)

    def arg(h):
        return jax.ShapeDtypeStruct((1, h, t, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(arg(heads), arg(kv), arg(kv),
                                   arg(heads)).compile()
    text = compiled.as_text()
    assert "attention_fwd" in text and "attention_bwd" in text
    # the largest temporary is the rows' float32 delta, not a score tile
    # (heads x 256 queries x up to 4096 keys x 4 bytes = 32-128 MiB)
    assert plan is not None
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 << 20


def test_diffusion_attention_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip):
    """Mosaic accepts the fused attention kernels under the block-diffusion
    mask at the SDAR cell's layer (two copies of a row of 8192, 32 heads
    over 4 of 128, blocks of 4: tiles of 256 positions x 512 keys), the
    strict walk's with the noised copy's own keys and values beside each
    query block and their ``dk`` / ``dv`` as two more results; the rule's
    VMEM count did not move for them; no ``jax.numpy`` square is left in
    the program."""
    import importlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")
    heads, kv, t, d, block = 32, 4, 8192, 128, 4
    plan = fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, kv, t, d, True,
                   diffusion_block=block)
    assert plan == fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, kv, t, d,
                           True)
    assert tuple(plan)[:2] == (256, 512)

    def step(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: ra.diffusion_attention(
                q, k, v, d ** -0.5, block, 512, plan), q, k, v)
        return (out,) + vjp(g)

    def arg(h):
        return jax.ShapeDtypeStruct((2, h, t, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(arg(heads), arg(kv), arg(kv),
                                   arg(heads)).compile()
    text = compiled.as_text()
    assert text.count("attention_fwd") >= 2 and "attention_bwd" in text
    assert "own_block" not in text
    # the halves cut and joined (bfloat16 copies of q, out and d_out) and the
    # rows' float32 delta: no float32 (B, H, T, Dv) temporary of the join
    assert compiled.memory_analysis().temp_size_in_bytes <= 400 << 20


def test_latent_attention_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip):
    """Mosaic accepts the fused attention kernels at the kanana2-30b cell's
    layer: 32 heads, T 8192, queries and keys of 192 = 128 + 64 rotated (the
    one rotated key broadcast into every head's key, as the model does),
    values of 128; the rule gives tiles of 512 x 512; no float32 score tile
    among the temporaries, which are q, k, dq and dk as the concatenations
    write and read them (a minor dimension of 192 is stored in 256 lanes)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")
    heads, t, nope, rope, dv = 32, 8192, 128, 64, 128
    plan = fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, heads, t,
                   nope + rope, True, 0, dv)
    assert (plan.bq, plan.bk) == (512, 512)

    def attend(q_nope, q_rope, k_nope, k_rope, v):
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (1, heads, t, rope))], -1)
        return ra.blockwise_attention(q, k, v, True, (nope + rope) ** -0.5,
                                      256, 0, plan)

    def step(*args):
        out, vjp = jax.vjp(attend, *args[:-1])
        return (out,) + vjp(args[-1])

    def arg(h, d):
        return jax.ShapeDtypeStruct((1, h, t, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg(heads, nope), arg(heads, rope), arg(heads, nope), arg(1, rope),
        arg(heads, dv), arg(heads, dv)).compile()
    text = compiled.as_text()
    assert "attention_fwd" in text and "attention_bwd" in text
    # the widest array of the program is a q or a k: a block of 256
    # queries' scores (the ``jax.numpy`` blocks') would be 4/3 of that
    widest = max(int(np.prod([int(d) for d in dims.split(",")]))
                 for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert widest == heads * t * (nope + rope)
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        4 * heads * t * 256 * 2 + (8 << 20)


@pytest.mark.parametrize("top_k", [2048, 16384])
def test_selected_attention_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip, top_k):
    """Mosaic accepts the four kernels of a selection at the Keye cell's
    layer (32 over 4 heads of 128, 16 index heads of 64, T 16 384, keep
    2048; the rule's tiles 128 x 256) and, where nothing is selected, the
    dense kernels at theirs with the indexer's two. No (queries x keys)
    array in float32 is among the program's arrays, forward or backward:
    the widest is the kept pairs in int8 (T x T, forward's then
    backward's), the temporaries are those and the rows' float32 delta."""
    import importlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")
    heads, kv, t, d, j, di = 32, 4, 16384, 128, 16, 64
    plan = fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, kv, t, d, True, 0,
                   d, top_k, (jnp.bfloat16, j, di))
    assert (plan.bq, plan.bk) == ((128, 256) if top_k < t else (128, 512))

    def step(*args):
        out, vjp = jax.vjp(lambda *a: ra.selected_kernels(
            *a, d ** -0.5, top_k, 1.0, plan), *args[:-1])
        return (out,) + vjp(args[-1])

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg(1, heads, t, d), arg(1, kv, t, d), arg(1, kv, t, d),
        arg(1, j, t, di), arg(1, 1, t, di), arg(1, j, t),
        arg(1, heads, t, d)).compile()
    text = compiled.as_text()
    for name in ("attention_select", "attention_fwd", "attention_index_bwd",
                 "attention_bwd"):
        assert name in text
    floats = max(int(np.prod([int(n) for n in dims.split(",")]))
                 for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert floats == heads * t * d
    assert (f"s8[1,{t},{t}]" in text) == (top_k < t)
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        t * t * (top_k < t) + (96 << 20)


def test_gated_delta_kernels_compile_for_a_v5e_at_the_cell_widths(one_chip):
    """Mosaic accepts the kernels of the gated delta rule's chunk-local
    algebra (``ops/gated_delta_kernels.py``; their other tests are in
    ``test_gated_delta_kernels.py``) at the Qwen3-Next cell's widths: T
    8192 in chunks of 64, 16 key over 32 value heads of 128, at the rule's
    block; between forward and backward nothing is kept but the chunks'
    inverses, pairs of chunks side by side with no lane padded (T x 64
    float32 a value head)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta_kernels as gk

    b, hk, g, t, d, c = 1, 16, 2, 8192, 128, 64
    n = t // c
    plan = gk.plan("tpu", V5E_VMEM, jnp.bfloat16, d, d, g, c, t)
    assert plan is not None and n % plan.chunks == 0

    def step(k, v, cum, beta, du, dw):
        out, vjp = jax.vjp(lambda *a: gk.within_chunks(*a, plan), k, v, cum,
                           beta)
        return out + vjp((du, dw))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((b, hk, n, c, d), jnp.bfloat16),
        arg((b, hk, g, n, c, d), jnp.bfloat16),
        arg((b, hk, g, n, c), jnp.float32), arg((b, hk, g, n, c), jnp.float32),
        arg((b, hk, g, n, c, d), jnp.float32),
        arg((b, hk, g, n, c, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "gated_delta_chunks_fwd" in text
    assert "gated_delta_chunks_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= b * hk * g * t * c * 4 + (1 << 20)


def test_gated_delta_scan_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip):
    """Mosaic accepts the two kernels of the gated delta rule's scan over
    chunks at the Qwen3-Next cell's widths (T 8192 in chunks of 64, 16 key
    over 32 value heads of 128) at the rule's block and VMEM limit; between
    forward and backward nothing is kept but the state every chunk started
    from ((T / 64) x 128 x 128 float32 a value head)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta_kernels as gk

    b, hk, g, t, d, c = 1, 16, 2, 8192, 128, 64
    n = t // c
    plan = gk.plan("tpu", V5E_VMEM, jnp.bfloat16, d, d, g, c, t)
    assert plan is not None and n % plan.chunks == 0

    def step(q, k, u, w, cum, do):
        out, vjp = jax.vjp(lambda *a: gk.across_chunks(*a, plan), q, k, u, w,
                           cum)
        return (out,) + vjp(do)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((b, hk, n, c, d), jnp.bfloat16),
        arg((b, hk, n, c, d), jnp.bfloat16),
        arg((b, hk, g, n, c, d), jnp.float32),
        arg((b, hk, g, n, c, d), jnp.bfloat16),
        arg((b, hk, g, n, c), jnp.float32),
        arg((b, hk, g, n, c, d), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "gated_delta_scan_fwd" in text
    assert "gated_delta_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= b * hk * g * n * d * d * 4 + (4 << 20)


def test_channel_gated_delta_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip):
    """Mosaic accepts the six kernels of the gated delta rule with a gate a
    key channel (the Gram matrices' two, and the four above taking ``c`` a
    channel) at the Kimi-Linear cell's widths, the whole operator forward
    and backward: T 4096 in chunks of 64, 32 heads of 128, at the rule's
    block and VMEM limit; the temporaries are of the order of the kept
    residuals (``U``, ``W``, three (T x 64) float32 a head and a state a
    chunk: 0.35 GiB) and not the ``jax.numpy`` form's 1.5 GiB (their other
    tests are in ``test_gated_delta_channel_kernels.py``). Between the
    kernels XLA only reshapes: no running sum and no sum of cotangents."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import gated_delta as gd
    from mxnet_tpu.ops import gated_delta_kernels as gk

    b, h, t, d, c = 1, 32, 4096, 128, 64
    plan = gk.plan("tpu", V5E_VMEM, jnp.bfloat16, d, d, 1, c, t, True)
    assert plan is not None and (t // c) % plan.chunks == 0

    def loss(*a):
        return jnp.sum(gd.chunk_gated_delta_rule(
            *a, chunk=c, kernels=plan).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = arg((b, h, t, d), jnp.bfloat16)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, arg((b, h, t, d), jnp.float32),
        arg((b, h, t), jnp.float32))
    # one rule over the six: the gate's running sum, its transpose and the
    # sums of the kernels' dc, dq and dk are taken on the kernels' tiles,
    # so the program around them holds no reduce-window and adds nothing
    # of a chunked operand's shape
    joins = lowered.as_text()
    assert "reduce_window" not in joins and "cumsum" not in joins
    assert not re.findall(
        rf"stablehlo\.add.*tensor<{b}x{h}(x1)?x{t // c}x{c}x{d}x", joins)
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in ("grams", "chunks", "scan"):
        for way in ("fwd", "bwd"):
            assert f"gated_delta_{kernel}_{way}" in text
    assert "while" not in text and "reduce-window" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 640 << 20


@pytest.mark.parametrize("cell", ["qwen3_next", "zaya1"])
def test_causal_conv_kernels_compile_for_a_v5e_at_the_cell_widths(
        monkeypatch, one_chip, cell):
    """Mosaic accepts the two kernels of ``CausalConv1D``'s depthwise form
    (``ops/causal_conv_kernels.py``; their other tests are in
    ``test_causal_conv_kernels.py``) at the rule's blocks for the Qwen3-Next
    cell's convolution, T 8192 over 8192 channels, 4 taps and SiLU, and for
    ZAYA1's first, 1280 channels, 2 taps, a bias and no activation, at a
    batch of four rows (one row is under half the VMEM: the rule keeps the
    ``jax.numpy`` form). Between forward and backward nothing is kept: the
    program's only temporaries are the taps turned a row a tap."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import causal_conv_kernels as ck

    b, t, c, taps, act, bias = {
        "qwen3_next": (1, 8192, 8192, 4, "silu", False),
        "zaya1": (4, 8192, 1280, 2, "none", True)}[cell]
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    plan = ck.kernel_plan(jnp.bfloat16, (b, t, c), taps, "tpu")
    assert plan is not None and t % plan.time == 0 and c % plan.channels == 0

    def step(x, w, b, dy):
        out, vjp = jax.vjp(lambda *a: ck.causal_conv(*a, act, plan), x, w, b)
        return (out,) + vjp(dy)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((b, t, c), jnp.bfloat16), arg((c, taps), jnp.float32),
        arg((c,), jnp.float32) if bias else None,
        arg((b, t, c), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "causal_conv_fwd" in text and "causal_conv_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 1 << 20


def test_selective_scan_kernels_compile_for_a_v5e_at_the_cell_widths(
        monkeypatch, one_chip):
    """Mosaic accepts the two kernels of ``SelectiveScan``
    (``ops/selective_scan.py``; their other tests are in
    ``test_selective_scan.py``) at the rule's blocks for the phi4-mini-flash
    cell's scan, T 4096 over 5120 channels of 16 states. Between forward and
    backward the program holds ``B`` and ``C`` over lanes (bfloat16, 16 MiB
    each), their gradients' sums (float32, 32 MiB each) and the start
    states: no (T, C, N) array (1.25 GiB in float32)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import selective_scan as ss

    b, t, c, n = 1, 4096, 5120, 16
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    plan = ss.kernel_plan(jnp.bfloat16, (b, t, c), n, "tpu")
    assert plan is not None and t % plan.time == 0 and c % plan.lanes == 0

    def step(*a):
        out, vjp = jax.vjp(lambda *z: ss.selective_scan(*z, plan), *a[:-1])
        return (out,) + vjp(a[-1])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((b, t, c)), arg((b, t, c)), arg((c, n), jnp.float32),
        arg((b, t, n)), arg((b, t, n)), arg((c,), jnp.float32),
        arg((c,), jnp.float32), arg((b, t, c))).compile()
    text = compiled.as_text()
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 128 << 20


@pytest.mark.parametrize("window", [512, 0], ids=["band_512", "full"])
def test_differential_attention_kernels_compile_for_a_v5e_at_the_cell_widths(
        one_chip, window):
    """Mosaic accepts the fused attention kernels at keys of 64 under values
    of 128 (a differential pair's node in the phi4-mini-flash cell: 20
    query heads over 10 key/value heads, T 4096), under the band of 512 keys
    and without one; no score tile among the temporaries."""
    import importlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa

    ra = importlib.import_module("mxnet_tpu.parallel.ring_attention")
    heads, kv, t, d, dv = 20, 10, 4096, 64, 128
    plan = fa.plan("tpu", V5E_VMEM, jnp.bfloat16, heads, kv, t, d, True,
                   window, dv)
    assert (plan.bq, plan.bk) == (512, 128 if window else 512)

    def step(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: ra.blockwise_attention(
                q, k, v, True, d ** -0.5, 256, window, plan), q, k, v)
        return (out,) + vjp(g)

    def arg(h, w):
        return jax.ShapeDtypeStruct((1, h, t, w), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(step).lower(arg(heads, d), arg(kv, d), arg(kv, dv),
                                   arg(heads, dv)).compile()
    text = compiled.as_text()
    assert "attention_fwd" in text and "attention_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 << 20


@pytest.mark.parametrize("cell", ["sdar", "keye_vl2", "mellum2_full"])
def test_rotary_kernel_compiles_for_a_v5e_at_the_cell_widths(
        monkeypatch, one_chip, cell):
    """Mosaic accepts ``RotaryEmbedding``'s kernel (``ops/rotary_kernels.py``;
    its other tests are in ``test_rotary_kernels.py``) at the rule's blocks
    for the queries of the SDAR cell, two trunk rows of 8192 over 32 heads
    of 128, and of the Keye-VL-2.0 cell, one row of 16 384 (the Mellum2
    cell's too: its full layer's, turned by the YaRN schedule, which is
    other numbers in the same tables): the operator lowered for the chip, forward and its derivative, which is the same
    kernel twice. Between the two nothing is kept: the program has no
    temporary."""
    import jax
    import jax.numpy as jnp

    shape = {"sdar": (2, 32, 8192, 128), "keye_vl2": (1, 32, 16384, 128),
             "mellum2_full": (1, 32, 16384, 128)}[cell]
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    schedule = dict(
        base=5e5, scaling="yarn", factor=16.0, original_max_position=8192,
        attention_factor=1.2772588722239782) if cell == "mellum2_full" \
        else dict(base=1e6)
    params = registry.get("RotaryEmbedding").parse_params(schedule)
    mode = registry.OpMode(is_train=True, platform="tpu")

    def step(x, dy):
        out, vjp = jax.vjp(lambda x: dt._rotary([x], params, mode), x)
        return (out,) + vjp(dy)

    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(step).lower(arg, arg).compile()
    assert compiled.as_text().count("rotary_turn") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes <= 1 << 20


@pytest.mark.parametrize("cell", ["mellum2", "keye_vl2", "sdar"])
def test_row_sum_kernel_compiles_for_a_v5e_at_the_cell_widths(
        monkeypatch, one_chip, cell):
    """Mosaic accepts ``MoE``'s row sum kernel (``ops/row_sum_kernels.py``;
    its other tests are in ``test_row_sum_kernels.py``) at the rule's blocks
    for a layer of the Mellum2 cell (16 384 tokens of 2304, 8 of 64 experts
    at top-8: rounds of 32 768 rows), of the Keye-VL-2.0 cell (2048, 8 of
    128: 16 384 rows) and of the SDAR cell (2048, 16 of 128: the widest
    slots, and the most VMEM the rule asks for): the operator lowered for the
    chip, forward and gradient, holds the weighted sum, the unweighted one,
    both again under the branch of the further rounds, no scatter of rows
    (the one left is of scalars: the backward of the round's window of
    weights) and, since the grouped matmuls own a round's dead rows, no
    select over a round's rows outside the loop of the further rounds'
    backward, whose select of ``y`` stays (``_held_round``). One program
    returns the output beside the gradients: without that select libtpu
    0.0.34 falls over compiling it at the Keye-VL-2.0 and SDAR shapes
    (PERF.md section 7)."""
    import jax
    import jax.numpy as jnp

    n, h, e, f = {"mellum2": (16384, 2304, 64, 896),
                  "keye_vl2": (16384, 2048, 128, 768),
                  "sdar": (16384, 2048, 128, 768)}[cell]
    held = 16 if cell == "sdar" else 8   # the widest slots: 1536 rows
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    params = registry.get("MoE").parse_params(dict(
        num_experts=e, num_hidden=f, top_k=8, num_local_experts=held,
        route_norm=True))
    mode = registry.OpMode(is_train=True, platform="tpu")

    def step(x, router, gate, up, down, dy):
        out, vjp = jax.vjp(
            lambda *ins: dt._moe(list(ins), params, mode), x, router, gate,
            up, down)
        return (out,) + vjp(dy)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((n, h), jnp.bfloat16), arg((e, h), jnp.float32),
        arg((held, h, f), jnp.float32), arg((held, h, f), jnp.float32),
        arg((held, f, h), jnp.float32), arg((n, h), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("moe_row_sum") >= 4
    assert not re.search(r"= \w+\[\d+,\d+\]\S* scatter\(", text)
    assert re.search(r"= \w+\[\d+\]\S* scatter\(", text)
    rows = dt.held_round_rows(n * 8, held, e)
    assert re.search(rf"= f32\[{rows},{f}\]\S* multiply\(", text)   # is read
    # the select of ``y`` and its transpose, in the further rounds' backward
    assert len(re.findall(rf"= bf16\[{rows},(?:{h}|{f})\]\S* select\(",
                          text)) == 2
