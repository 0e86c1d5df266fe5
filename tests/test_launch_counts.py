"""The seam between an operator and the executor's per-launch counters
(``OpDef.launch_counts``): an operator declares, beside its ``fn``, what one
launch of a train program that holds it counts, and lists the instruments
it may name; the executor sums what the nodes say while it lowers them and
knows no operator by name. No Pallas interpreter and no Mosaic compile
here: the kernel families' rules (six since PR 65's ``SelectiveScan``)
have their own files (the other two
declaring operators, ``ExitSoftmaxOutput`` and ``BlockDiffusionNoise``, have
no kernel and no rule)."""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import defs_transformer as dt
from mxnet_tpu.ops import pallas_support as ps
from mxnet_tpu.ops import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECLARING = ("BlockDiffusionNoise", "CausalConv1D", "ExitSoftmaxOutput",
             "GatedDeltaRule", "MoE", "RingAttention", "RotaryEmbedding",
             "SelectiveScan")


def test_the_four_kernel_families_declare_and_nobody_else():
    """And, since PR 55, the loss layer of a looped model: its exits and
    their rows; since PR 57 the noise of a block-diffusion step: its rows
    (and three more names of ``RingAttention``'s under that mode); since PR
    59 ``RotaryEmbedding``, a fifth family of one kernel: its nodes, and
    those of them in the kernel; since PR 61 ``RingAttention``'s layers whose
    own block (of the block-diffusion mask) is a tile of the kernels; since
    PR 62 ``RotaryEmbedding``'s nodes of a scaled schedule and the kept and
    scored pairs of ``RingAttention``'s window layers; since PR 63 the row
    sums of ``MoE``'s held rounds that run their kernel; since PR 64 the
    expert matmuls of a held round that no row select is traced around;
    since PR 65 ``SelectiveScan``, a sixth family (its nodes, those in the
    kernels, the state elements they update)."""
    declaring = {name for name, op in registry.canonical_ops().items()
                 if op.launch_instruments}
    assert declaring == set(DECLARING)
    assert sum(len(registry.get(n).launch_instruments)
               for n in DECLARING) == 39


@pytest.mark.parametrize("op", DECLARING)
def test_every_declared_instrument_is_catalogued(op):
    """``docs/observability.md`` is the catalogue: the ``telemetry-catalog``
    lint reads literal names at a call site, and the executor's one call
    site forwards these."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        doc = f.read()
    names = registry.get(op).launch_instruments
    assert names and len(set(names)) == len(names)
    for name in names:
        assert name.startswith("executor.") and f"`{name}`" in doc, name


def test_an_op_without_a_declaration_counts_nothing():
    op = registry.get("FullyConnected")
    assert op.launch_instruments == ()
    assert op.launch_counts([], [], {}, "cpu") == {}


# an operator of the test's own, declared as the four are
_SEAM = "_test_launch_counts_seam"


@pytest.fixture
def seam_op():
    """The test's operator, registered for the test alone."""
    registry.register(
        _SEAM, lambda ins, params, mode: ins[0] * 2.0, arg_names=["data"],
        launch_counts=lambda ins, outs, params, platform: {
            "executor.moe_layers": 1,
            "executor.moe_assignments": int(np.prod(outs[0].shape)),
            "executor.moe_kernel_matmuls": 9 * (platform == "tpu")},
        launch_instruments=("executor.moe_layers", "executor.moe_assignments",
                            "executor.moe_kernel_matmuls"))
    yield registry.get(_SEAM)
    del registry._OPS[_SEAM]


def _train_launches(sym, launches, **shapes):
    exe = sym.simple_bind(mx.cpu(), grad_req="write", **shapes)
    for _ in range(launches):
        exe.forward(is_train=True)
        exe.backward()
        exe.grad_dict["data"].wait_to_read()
    return exe


def _seam_graph():
    """Two nodes of the test's operator under a loss head."""
    x = mx.sym.Variable("data")
    for i in range(2):
        x = mx.sym._create(_SEAM, [x], {}, name=f"seam{i}")
    return mx.sym.MakeLoss(mx.sym.sum(x))


def test_a_declaring_op_is_counted_by_a_train_launch_and_only_by_one(seam_op):
    """The seam's own test: nothing in ``executor.py`` names this operator,
    and every launch of its train program bumps what it declared, summed
    over its nodes; a counter it declared 0 for is not moved, and a
    forward alone counts nothing."""
    before = tm.snapshot().get("executor", {})
    exe = _train_launches(_seam_graph(), 3, data=(4, 5))
    exe.forward(is_train=False)
    exe.outputs[0].wait_to_read()
    after = tm.snapshot()["executor"]

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)

    assert delta("moe_layers") == 3 * 2
    assert delta("moe_assignments") == 3 * 2 * 20
    assert delta("moe_kernel_matmuls") == 0   # bound on the CPU
    assert delta("attention_layers") == 0
    assert exe.graph.launch_counts == {
        "executor.moe_layers": 2, "executor.moe_assignments": 40,
        "executor.moe_kernel_matmuls": 0}


def test_a_program_not_traced_here_counts_from_inferred_shapes(seam_op):
    """An executable read from the ``MXNET_AOT_CACHE`` store is launched
    without a trace: the same declarations, asked over the shapes and types
    the graph infers from the bound arguments, give the same sums."""
    exe = _seam_graph().simple_bind(mx.cpu(), grad_req="write", data=(4, 5))
    assert exe._launch_counts is None
    assert exe._declared_from_shapes() == {
        "executor.moe_layers": 2, "executor.moe_assignments": 40,
        "executor.moe_kernel_matmuls": 0}
    traced = _train_launches(_seam_graph(), 1, data=(4, 5))
    assert traced._launch_counts == exe._declared_from_shapes()


def test_a_count_outside_the_listed_instruments_is_refused():
    op = registry.OpDef(
        "unlisted", lambda ins, params, mode: ins[0], ["data"],
        launch_counts=lambda *a: {"executor.attention_layers": 1},
        launch_instruments=("executor.moe_layers",))
    with pytest.raises(MXNetError, match="launch_instruments"):
        op.launch_counts([], [], {}, "cpu")


def test_an_imperative_moe_is_told_its_operands_platform(monkeypatch):
    """A bare ``mx.nd.MoE`` on CPU arrays in a process that holds a TPU
    (``jax.default_backend`` says so, its VMEM is known): the imperative
    path fills ``OpMode.platform`` from its concrete operands' device, the
    rule is asked for the CPU and says None, and ``ragged_dot`` runs."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: 128 << 20)
    asked = []
    rule = dt._expert_plans

    def spy(platform, *args, **kwargs):
        asked.append((platform, rule(platform, *args, **kwargs)))
        return asked[-1][1]

    monkeypatch.setattr(dt, "_expert_plans", spy)
    rs = np.random.RandomState(0)
    tokens = mx.nd.array(rs.randn(256, 128), dtype="bfloat16")
    weights = [mx.nd.array(rs.randn(*s) * 0.1) for s in (
        (4, 128), (4, 128, 128), (4, 128, 128), (4, 128, 128))]
    out = mx.nd.MoE(tokens, *weights, num_experts=4, num_hidden=128, top_k=2)
    assert out.shape == (256, 128)
    assert np.isfinite(out.asnumpy().astype(np.float32)).all()
    assert asked == [("cpu", None)]
    # the same operands with no platform said: the default backend's, a plan
    assert rule(None, "bfloat16", 512, [w._data for w in weights[1:]]) \
        is not None


@pytest.mark.parametrize("held,trunk,kernels,unmasked", [
    (8, "bfloat16", 9, 9),    # the kernels own a held round's dead rows
    (8, "float32", 0, 0),     # ragged_dot keeps its masks
    (0, "bfloat16", 9, 0),    # every expert held: no dead row to mask
], ids=["held-range-bfloat16", "held-range-float32", "every-expert-held"])
def test_moe_counts_the_matmuls_no_select_is_traced_around(
        monkeypatch, held, trunk, kernels, unmasked):
    """``executor.moe_unmasked_matmuls`` with a v5e attached, a layer of
    the Mellum2 cell's shapes: nine where the grouped matmuls' kernels run
    a held round, from the same ask of the rule as
    ``executor.moe_kernel_matmuls``."""
    import jax

    monkeypatch.setattr(ps, "attached_vmem_bytes", lambda: 128 << 20)
    op = registry.get("MoE")
    params = op.parse_params(dict(num_experts=64, num_hidden=896, top_k=8,
                                  num_local_experts=held))
    local = held or 64
    ins = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((16384, 2304), trunk), ((64, 2304), "float32"),
        ((local, 2304, 896), "float32"), ((local, 2304, 896), "float32"),
        ((local, 896, 2304), "float32"))]
    counts = op.launch_counts(ins, ins[:1], params, "tpu")
    assert counts["executor.moe_kernel_matmuls"] == kernels
    assert counts["executor.moe_unmasked_matmuls"] == unmasked
    assert op.launch_counts(ins, ins[:1], params, "cpu")[
        "executor.moe_unmasked_matmuls"] == 0
