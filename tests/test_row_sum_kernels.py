"""The row sum kernel of ``MoE``'s held rounds
(``mxnet_tpu/ops/row_sum_kernels.py``) in Pallas's interpreter on the CPU
against the ``jax.numpy`` forms it stands for (``zeros.at[tok].add``, the
backward of ``x[tok]``), forward and under ``jax.vjp``; the weighted sum
against a float64 oracle; the runs ``block_runs`` gives against a search of
the sorted list; the rule, and what ``MoE`` declares a launch counts on either
side of it. That Mosaic takes the kernels for a described v5e at the Mellum2
and Keye-VL-2.0 cells' shapes sits with the other compiles in
``tests/test_grouped_matmul.py`` (one file, one libtpu)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import row_sum_kernels as rs

V5E_VMEM = 128 << 20
BLOCK = rs._BLOCK   # every case runs the blocks the rule ships

# name: (tokens, H, experts, held, the first held, top_k, what the routing is)
CASES = {
    # 256 rows an expert: every run crosses chunks of 16 and both blocks
    "runs-cross-chunks-and-blocks": (256, 128, 8, 4, 0, 8, "all"),
    "an-expert-with-no-rows": (256, 128, 8, 4, 2, 2, "skip-one"),
    "every-row-on-one-expert": (256, 128, 8, 4, 0, 1, "one"),
    "a-token-held-by-8-experts": (128, 128, 16, 8, 4, 8, "held-8"),
    # 4 of 16 experts at top-3: 1152 assignments padded to two rounds of
    # 1024, 288 live
    "a-dead-tail-and-padding": (384, 128, 16, 4, 5, 3, "random"),
    # 3 of 10 tokens choose the four held experts alone: their runs are
    # whole blocks long (128 rows that start off a multiple of 16: 129-143
    # rows of chunks, one long copy and a short one) and the rounds cut them
    "a-partly-collapsed-router": (1024, 128, 32, 4, 3, 4, "partly"),
    "h-2048": (128, 2048, 8, 4, 0, 2, "random"),
    "h-2304": (128, 2304, 8, 4, 0, 2, "random"),
}
# the same lists cut into rounds: (case, the round)
LATER_ROUNDS = {
    "a-round-at-first-512": ("runs-cross-chunks-and-blocks", 512, 1),
    "the-last-live-round": ("runs-cross-chunks-and-blocks", 512, 3),
    "a-round-of-dead-rows": ("an-expert-with-no-rows", 128, 3),
    "a-partly-dead-second-round": ("a-partly-collapsed-router", 0, 1),
}


def _routing(kind, n, e, held, first, k, rng):
    """(n, k) int32: each token's k distinct experts."""
    if kind == "all":            # every token to the first k experts
        return np.tile(np.arange(k, dtype=np.int32), (n, 1))
    if kind == "one":            # routing collapsed onto one held expert
        return np.full((n, 1), first + 1, np.int32)
    if kind == "held-8":         # every token to all eight held experts
        return np.tile(np.arange(first, first + k, dtype=np.int32), (n, 1))
    expert = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    if kind == "partly":
        expert[:n * 3 // 10] = np.arange(first, first + k)
    if kind == "skip-one":       # nobody chooses the second held expert
        expert = np.where(expert == first + 1, first + 3, expert)
        expert[:, 1] = np.where(expert[:, 1] == expert[:, 0],
                                (expert[:, 0] + 4) % e, expert[:, 1])
    return expert.astype(np.int32)


def _round(name, weighted, seed=0):
    """One round as ``_moe`` and ``_held_round`` make it: (rows bfloat16
    with zeros where dead, tok, weight or None, the round's runs, n)."""
    rows, at = 0, 0
    if name in LATER_ROUNDS:
        name, rows, at = LATER_ROUNDS[name]
    n, h, e, held, first, k, kind = CASES[name]
    rng = np.random.RandomState(seed)
    expert = _routing(kind, n, e, held, first, k, rng)
    assert all(len(set(r)) == k for r in expert)
    rows = rows or dt.held_round_rows(n * k, held, e)
    local = expert - first
    key = np.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = np.argsort(key, kind="stable")
    order = np.pad(order, (0, -(-n * k // rows) * rows - n * k))
    counts = np.bincount(key, minlength=held + 1)[:held]
    member = (local[:, :, None] == np.arange(held)).any(1)
    runs = rs.block_runs(jnp.asarray(member),
                         jnp.asarray(np.cumsum(counts) - counts), BLOCK)
    start = at * rows
    tok = order[start:start + rows] // k
    live = np.arange(start, start + rows) < counts.sum()
    a = np.where(live[:, None], rng.randn(rows, h), 0).astype(np.float32)
    w = (rng.rand(rows) + 0.25).astype(np.float32) if weighted else None
    return (jnp.asarray(a, jnp.bfloat16), jnp.asarray(tok, jnp.int32),
            None if w is None else jnp.asarray(w),
            jnp.clip(runs - start, 0, rows), n, int(live.sum()))


def _plan(rows, n, h, held, k):
    plan = rs.kernel_plan("tpu", V5E_VMEM, jnp.bfloat16, rows, n, h, held, k)
    assert plan is not None
    return plan


def _kernel(a, tok, w, runs, n, dtype):
    held = runs.shape[1]   # a token's rows at most: what sizes the slots
    return rs.sum_rows(a, tok, w, runs, n, dtype,
                       _plan(a.shape[0], n, a.shape[1], held, held),
                       interpret=True)


def _form(a, tok, w, n, dtype):
    """The ``jax.numpy`` forms of ``_held_round``: the combine in float32,
    the backward of ``x[tok]`` in the rows' dtype."""
    if w is None:
        return jnp.zeros((n, a.shape[1]), dtype).at[tok].add(a.astype(dtype))
    return jnp.zeros((n, a.shape[1]), jnp.float32).at[tok].add(
        a.astype(jnp.float32) * w[:, None])


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("case", list(CASES) + list(LATER_ROUNDS))
def test_kernel_is_the_scatter_add(case, weighted):
    """Every live row summed into its token, the rows no run holds ignored,
    a block no run touches written as zeros. Weighted: float32, the form's
    sum up to the order of its additions. Unweighted into float32: the
    same; the rows are bfloat16 and their float32 sums exact."""
    a, tok, w, runs, n, live = _round(case, weighted)
    got = np.asarray(_kernel(a, tok, w, runs, n, jnp.float32))
    want = np.asarray(_form(a, tok, w, n, jnp.float32))
    assert (np.abs(want).max() > 0) == (live > 0)
    if weighted:
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=8 * 2.0 ** -24 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2.0 ** -22 * np.abs(want).max())


@pytest.mark.parametrize("case", ["runs-cross-chunks-and-blocks",
                                  "a-token-held-by-8-experts",
                                  "a-dead-tail-and-padding"])
def test_the_unweighted_sum_is_rounded_once(case):
    """Into bfloat16 (the backward of the dispatch): the float32 sum of a
    token's rows rounded once, where the scatter-add rounds after every
    row."""
    a, tok, _, runs, n, _ = _round(case, False)
    got = _kernel(a, tok, None, runs, n, jnp.bfloat16)
    exact = np.zeros((n, a.shape[1]), np.float64)
    np.add.at(exact, np.asarray(tok), np.asarray(a, np.float64))
    want = jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("case", ["every-row-on-one-expert",
                                  "a-token-held-by-8-experts",
                                  "runs-cross-chunks-and-blocks"])
def test_the_weighted_sum_is_float32_exact(case):
    """Against a float64 oracle: a token with one row gets ``float32(w *
    a)`` to the bit (the three bfloat16 terms add up to the weight), and
    any token is off by at most one float32 rounding a term."""
    a, tok, w, runs, n, _ = _round(case, True, seed=3)
    got = np.asarray(_kernel(a, tok, w, runs, n, jnp.float32), np.float64)
    terms = np.asarray(a, np.float64) * np.asarray(w, np.float64)[:, None]
    want = np.zeros_like(got)
    size = np.zeros_like(got)
    np.add.at(want, np.asarray(tok), terms)
    np.add.at(size, np.asarray(tok), np.abs(terms))
    held = np.bincount(np.asarray(tok)[np.abs(terms).max(1) > 0],
                       minlength=n)
    assert held.max() == {"every-row-on-one-expert": 1,
                          "a-token-held-by-8-experts": 8}.get(case, 4)
    if held.max() == 1:
        np.testing.assert_array_equal(got.astype(np.float32),
                                      want.astype(np.float32))
    assert np.all(np.abs(got - want)
                  <= held[:, None] * 2.0 ** -24 * size)


@pytest.mark.parametrize("case", ["runs-cross-chunks-and-blocks",
                                  "an-expert-with-no-rows",
                                  "a-dead-tail-and-padding",
                                  "a-round-at-first-512"])
def test_the_derivatives_are_the_forms(monkeypatch, case):
    """``jax.vjp`` of ``_take_rows`` and ``_sum_weighted_rows`` against
    autodiff of ``x[tok]`` and of the combine: the rows' gradient (a
    float32 sum here, rounded after every row there), the combine's two
    cotangents to the bit."""
    monkeypatch.setattr(rs, "sum_rows",
                        functools.partial(rs.sum_rows, interpret=True))
    y, tok, w, runs, n, _ = _round(case, True)
    plan = _plan(y.shape[0], n, y.shape[1], runs.shape[1], runs.shape[1])
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(n, y.shape[1]), jnp.bfloat16)
    g_rows = jnp.where(y != 0, jnp.asarray(rng.randn(*y.shape), jnp.bfloat16),
                       0)   # a dead row's cotangent is masked to zero
    g_out = jnp.asarray(rng.randn(n, y.shape[1]), jnp.float32)

    rows, back = jax.vjp(lambda x: dt._take_rows(n, plan, x, tok, runs), x)
    want_rows, want_back = jax.vjp(lambda x: x[tok], x)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(want_rows, np.float32))
    got = np.asarray(back(g_rows)[0], np.float32)
    want = np.asarray(want_back(g_rows)[0], np.float32)
    exact = np.asarray(_form(g_rows, tok, None, n, jnp.float32))
    assert np.abs(got - exact).max() <= np.abs(want - exact).max()
    np.testing.assert_array_equal(
        got, np.asarray(jnp.asarray(exact).astype(jnp.bfloat16), np.float32))

    out, back = jax.vjp(
        lambda y, w: dt._sum_weighted_rows(n, plan, y, w, tok, runs), y, w)
    want_out, want_back = jax.vjp(
        lambda y, w: _form(y, tok, w, n, jnp.float32), y, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out), rtol=0,
                               atol=8 * 2.0 ** -24 * np.abs(want_out).max())
    for a, b in zip(back(g_out), want_back(g_out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("case", list(CASES))
def test_block_runs_are_where_a_search_finds_them(case):
    """``runs[b, e]``: the first row of held expert e's run whose token is
    at least ``b * block``, by counting; against a search of the sorted
    list."""
    n, _, e, held, first, k, kind = CASES[case]
    expert = _routing(kind, n, e, held, first, k, np.random.RandomState(0))
    local = expert - first
    key = np.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=held + 1)[:held]
    starts = np.cumsum(counts) - counts
    member = (local[:, :, None] == np.arange(held)).any(1)
    runs = np.asarray(rs.block_runs(jnp.asarray(member), jnp.asarray(starts),
                                    BLOCK))
    assert runs.shape == (n // BLOCK + 1, held) and runs.dtype == np.int32
    for x in range(held):
        toks = order[starts[x]:starts[x] + counts[x]] // k
        assert np.all(np.diff(toks) > 0)    # strictly ascending in a run
        want = starts[x] + np.searchsorted(
            toks, np.arange(n // BLOCK + 1) * BLOCK)
        np.testing.assert_array_equal(runs[:, x], want)


# (platform, vmem, dtype, rows, tokens, H, held, top_k): does it engage
RULE_CASES = {
    "the-mellum2-round": (("tpu", V5E_VMEM, "bfloat16", 32768, 16384, 2304,
                           8, 8), True),
    "the-sdar-round": (("tpu", V5E_VMEM, "bfloat16", 32768, 16384, 2048, 16,
                        8), True),
    "the-keye-vl2-round": (("tpu", V5E_VMEM, "bfloat16", 16384, 16384, 2048,
                            8, 8), True),
    "the-cpu": (("cpu", V5E_VMEM, "bfloat16", 32768, 16384, 2304, 8, 8),
                False),
    "no-vmem-known": (("tpu", None, "bfloat16", 32768, 16384, 2304, 8, 8),
                      False),
    "float32-rows": (("tpu", V5E_VMEM, "float32", 32768, 16384, 2304, 8, 8),
                     False),
    "a-width-128-does-not-divide": (("tpu", V5E_VMEM, "bfloat16", 32768,
                                     16384, 2300, 8, 8), False),
    "tokens-that-are-no-whole-blocks": (("tpu", V5E_VMEM, "bfloat16", 32768,
                                         16400, 2304, 8, 8), False),
    "a-round-that-is-no-whole-long-copies": (
        ("tpu", V5E_VMEM, "bfloat16", 288, 4096, 2048, 8, 8), False),
    "the-kimi-linear-round": (("tpu", V5E_VMEM, "bfloat16", 2048, 4096, 2304,
                               8, 8), True),
    "slots-over-half-a-small-vmem": (("tpu", 16 << 20, "bfloat16", 32768,
                                      16384, 2304, 8, 8), False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_rule_says_where_the_kernel_engages(case):
    args, engages = RULE_CASES[case]
    plan = rs.kernel_plan(*args)
    assert (plan is not None) == engages
    if engages:
        rows, n, h, held, k = args[3:]
        assert n % plan.block == 0 and rows % plan.chunk == 0
        assert plan.cap % plan.slab == 0
        assert plan.cap >= plan.block * min(k, held) + held * plan.chunk
        assert plan.vmem_limit <= V5E_VMEM * 3 // 4


def _row_sums(platform, dtype, tokens, hidden, experts, held, top_k):
    """``executor.moe_kernel_row_sums`` as ``MoE`` declares it for one
    layer."""
    op = registry.get("MoE")
    params = op.parse_params(dict(num_experts=experts, num_hidden=512,
                                  top_k=top_k, num_local_experts=held))
    local = held or experts
    ins = [jax.ShapeDtypeStruct((1, tokens, hidden), jnp.dtype(dtype)),
           jax.ShapeDtypeStruct((experts, hidden), jnp.float32)] + [
        jax.ShapeDtypeStruct(s, jnp.float32)
        for s in ((local, hidden, 512), (local, hidden, 512),
                  (local, 512, hidden))]
    assert "executor.moe_kernel_row_sums" in op.launch_instruments
    return op.launch_counts(ins, [ins[0]], params, platform)[
        "executor.moe_kernel_row_sums"]


@pytest.mark.parametrize("side,want", [
    (("tpu", "bfloat16", 16384, 2304, 64, 8, 8), 2),    # the Mellum2 layer
    (("tpu", "bfloat16", 16384, 2048, 128, 16, 8), 2),  # the SDAR layer
    (("tpu", "bfloat16", 192, 2048, 128, 8, 8), 0),     # no whole blocks
    (("tpu", "bfloat16", 16384, 2048, 64, 0, 8), 0),    # every expert held
    (("tpu", "float32", 16384, 2304, 64, 8, 8), 0),     # ragged_dot's rows
    (("cpu", "bfloat16", 16384, 2304, 64, 8, 8), 0),
], ids=["mellum2", "sdar", "tokens-no-whole-blocks", "all-held", "float32", "cpu"])
def test_launch_counts_read_two_or_none(monkeypatch, side, want):
    """2 a layer whose round engages the kernel (the combine and the
    dispatch's backward, both or none), 0 on the other side of the rule:
    the op's own ask, with a v5e attached."""
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: V5E_VMEM)
    assert _row_sums(*side) == want


def test_no_kernel_without_an_attached_chip():
    assert _row_sums("tpu", "bfloat16", 16384, 2304, 64, 8, 8) == 0


# (tokens, experts, held, top_k, the share of the tokens that choose the
# held experts alone): a cell's layer under a router that collapses part of
# the way, at the blocks the rule ships
REPLAYS = {
    "mellum2-three-tenths": (16384, 64, 8, 8, 0.3),
    "mellum2-collapsed": (16384, 64, 8, 8, 1.0),
    "sdar-three-tenths": (16384, 128, 16, 8, 0.3),
    "trinity-three-tenths": (4096, 128, 8, 8, 0.3),
    "zaya1-every-row-live": (8192, 16, 8, 1, 1.0),
}


@pytest.mark.parametrize("case", sorted(REPLAYS))
def test_the_copies_stay_inside_the_round_and_the_slots(case):
    """``block_chunks`` (the kernel's own bookkeeping, on the host) over
    every block of every live round of a whole layer's routing: each copy
    reads rows of the round, lands inside a slot of ``cap`` rows on one of
    the ``_SEMS`` semaphores, chunks follow each other in the slot, and the
    rows marked as a run's are each live row of the round exactly once."""
    n, e, held, k, collapse = REPLAYS[case]
    rows = dt.held_round_rows(n * k, held, e)
    plan = rs.kernel_plan("tpu", V5E_VMEM, jnp.bfloat16, rows, n, 2048, held,
                          k)
    rng = np.random.RandomState(0)
    logits = rng.randn(n, e).astype(np.float32)
    logits[:int(collapse * n), :held] += 20.0
    expert = np.argsort(-logits, 1)[:, :k]
    member = (expert[:, :, None] == np.arange(held)).any(1)
    counts = member.sum(0)
    runs = rs.block_runs(jnp.asarray(member),
                         jnp.asarray(np.cumsum(counts) - counts), plan.block)
    rounds = -(-int(counts.sum()) // rows)
    assert rounds > 1 or collapse == 0.0 or k == 1

    def piece(carry, at, size, first, lo, hi, nth):
        ok, end, marked = carry
        ok = (ok & (first >= 0) & (first + size <= rows) & (at == end)
              & (at + size <= plan.cap) & (first % 16 == 0) & (at % 16 == 0)
              & (nth >= 0) & (nth < rs._SEMS) & (lo >= first)
              & (jnp.minimum(hi, first + size) >= lo))
        return ok, at + size, marked + jnp.minimum(hi, first + size) - lo

    @jax.jit
    def replay(flat):
        def block(b, carry):
            ok, marked = carry
            packed, (ok, end, marked) = rs.block_chunks(
                flat, b, held, rows, plan.chunk, piece, (ok, 0, marked))
            return ok & (packed == end) & (packed <= plan.cap), marked
        return jax.lax.fori_loop(0, n // plan.block, block, (True, 0))

    for r in range(rounds):
        ok, marked = replay(jnp.clip(runs - r * rows, 0, rows).reshape(-1))
        assert bool(ok), r
        assert int(marked) == min(int(counts.sum()) - r * rows, rows), r


@pytest.mark.parametrize("collapse", [0.3, 1.0],
                         ids=["three-tenths", "every-token"])
def test_a_collapsed_layer_is_the_scatter_adds(monkeypatch, collapse):
    """``MoE`` itself under a router that collapses onto the held experts
    (1024 tokens, 4 of 32 experts at top-4: rounds of 1024 rows, two or
    four of them live, runs whole blocks long), every round's two row sums
    in the kernel at the plan the rule ships for the layer's own sizes:
    output and all five gradients against the scatter-adds. (A slot sized
    for ``rows // tokens`` rows a token where it meant ``top_k`` held a
    balanced block and overflowed under this routing: on the chip a halt,
    in the interpreter rows in the wrong place.)"""
    n, h, e, held, k, f = 1024, 128, 32, 4, 4, 128
    rows = dt.held_round_rows(n * k, held, e)
    asked = []

    def shipped(platform, dtype, m, tokens, hidden, weights, top_k,
                vmem=None):
        asked.append((m, tokens, hidden, weights[0].shape[0], top_k))
        return rs.kernel_plan("tpu", V5E_VMEM, dtype, m, tokens, hidden,
                              weights[0].shape[0], top_k)

    rng = np.random.RandomState(2)
    logits = rng.randn(n, e).astype(np.float32)
    logits[:int(collapse * n), :held] += 20.0
    ins = [jnp.asarray(rng.randn(n, h), jnp.bfloat16), jnp.asarray(logits)]
    ins += [jnp.asarray(rng.randn(*s) * 0.05, jnp.float32)
            for s in ((held, h, f), (held, h, f), (held, f, h))]
    head = jnp.asarray(rng.randn(n, h), jnp.float32)
    params = registry.get("MoE").parse_params(dict(
        num_experts=e, num_hidden=f, top_k=k, num_local_experts=held,
        router="graph", route_norm=True))

    def scalar(*ins):
        out = dt._moe(list(ins), params, registry.OpMode(is_train=True))
        return jnp.sum(out.astype(jnp.float32) * head), out

    sides = []
    for kernel in (True, False):
        with monkeypatch.context() as steer:
            if kernel:
                steer.setattr(dt, "_row_sum_plan", shipped)
                steer.setattr(rs, "sum_rows", functools.partial(
                    rs.sum_rows, interpret=True))
            grads, out = jax.jit(jax.grad(
                scalar, argnums=tuple(range(5)), has_aux=True))(*ins)
            sides.append([np.asarray(a, np.float32)
                          for a in (out,) + tuple(grads)])
    assert set(asked) == {(rows, n, h, held, k)}
    for name, a, b in zip(("out", "x", "logits", "gate", "up", "down"),
                          *sides):
        assert np.abs(b).max() > 0, name
        tol = 2.0 ** -7 if name in ("out", "x") else 2.0 ** -18
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max(),
                                   err_msg=name)
