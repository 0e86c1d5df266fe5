"""The graph's names on the device's work, and the reader of a trace by
them (``mxnet_tpu/profiler.py``): the scopes in a train program's HLO, the
scope parser over strings recorded on the v5e, the pure reduce over
hand-made events, and one real (XLA:CPU) trace through the loader, the
reader and ``tools/trace_table.py``."""

import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import executor as ex  # noqa: E402
from mxnet_tpu import profiler as prof  # noqa: E402
from mxnet_tpu import telemetry as tm  # noqa: E402

# --- the parser over what the chip's trace carries -------------------------
# tf_op strings of a traced trinity-mini-train-1c slice (my chip run, PR 36;
# MXNET_BACKWARD_DO_MIRROR=1, so every node without auxiliary state runs
# under jax.checkpoint), and of a qwen3-next-train-1c slice for the scopes an
# operator opens itself.
CHIP = {
    "forward": (
        "jit(_step)/jvp(FullyConnected[l0_q])/dot_general:",
        ("FullyConnected", "l0_q", "forward")),
    "forward_nested_jit": (
        "jit(_step)/jvp(Embedding[embed])/jit(_take)/jit(_where)/select_n:",
        ("Embedding", "embed", "forward")),
    "backward": (
        "jit(_step)/transpose(jvp(FullyConnected[pred]))/"
        "jvp(FullyConnected[pred])/checkpoint/dot_general:",
        ("FullyConnected", "pred", "backward")),
    "backward_remat2": (
        "jit(_step)/transpose(jvp(Reshape[reshape79]))/"
        "jvp(Reshape[reshape79])/remat2:",
        ("Reshape", "reshape79", "backward")),
    "recompute": (
        "jit(_step)/transpose(jvp(RMSNorm[final_norm]))/"
        "jvp(RMSNorm[final_norm])/checkpoint/rematted_computation/"
        "reduce_sum:",
        ("RMSNorm", "final_norm", "recompute")),
    "update": (
        "jit(_step)/executor.update/param[l2_k_norm_gamma]/slice:",
        ("update", "l2_k_norm_gamma", "update")),
    "phase": (
        "jit(_step)/executor.repack/concatenate:",
        ("repack", None, "other")),
    "pallas_forward": (
        "jit(_step)/jvp(RingAttention[l0_attn])/call_exported/"
        "jit(<lambda>)/jit(_fwd)/attention_fwd/pallas_call:",
        ("RingAttention", "l0_attn", "forward")),
    "pallas_recompute": (
        "jit(_step)/transpose(jvp(RingAttention[l4_attn]))/"
        "jvp(RingAttention[l4_attn])/checkpoint/rematted_computation/"
        "call_exported/jit(<lambda>)/jit(_fwd)/attention_fwd/pallas_call:",
        ("RingAttention", "l4_attn", "recompute")),
    "custom_vjp_backward": (
        "jit(_step)/transpose(jvp(MoE[l4_moe]))/jvp(MoE[l4_moe])/"
        "checkpoint/transpose(jvp())/cond/branch_0_fun/call_exported/"
        "jit(<lambda>)/jit(_tgmm)/moe_gmm_wgrad/pallas_call:",
        ("MoE", "l4_moe", "backward")),
    "no_scope": ("jit(_step)/scatter-add:", None),
    "argument": ("upd_vals[0]", None),
    "other_program": ("jit(take_along_axis)/gather:", None),
}


@pytest.mark.parametrize("case", sorted(CHIP))
def test_parse_scope_reads_the_chips_strings(case):
    tf_op, want = CHIP[case]
    assert prof.parse_scope(tf_op) == want


INNER = {
    "forward": "jit(take_along_axis)/gather",
    "recompute": "reduce_sum",
    "pallas_recompute":
        "call_exported/jit(<lambda>)/jit(_fwd)/attention_fwd/pallas_call",
    "custom_vjp_backward":
        "transpose(jvp())/cond/branch_0_fun/call_exported/jit(<lambda>)/"
        "jit(_tgmm)/moe_gmm_wgrad/pallas_call",
    "update": "slice",
    "phase": "", "no_scope": "", "argument": "", "other_program": "",
}


@pytest.mark.parametrize("case", sorted(INNER))
def test_inner_scope_reads_what_follows_the_node(case):
    """After the LAST part that names a node, less jax's ``checkpoint`` /
    ``rematted_computation``; nothing where no part names one."""
    tf_op = ("jit(_step)/jvp(MoE[l1_moe])/jit(take_along_axis)/gather:"
             if case == "forward" else CHIP[case][0])
    assert prof.inner_scope(tf_op) == INNER[case]


def test_parse_scope_reads_its_own_grammar():
    """What ``node_scope`` / ``phase_scope`` / ``param_scope`` write, the
    parser reads back, a window's ``while`` body and a batched group
    included; a name's unsafe characters never reach the scope."""
    scope = prof.node_scope("FullyConnected", "t0/h2h:x", group=35)
    assert scope == "FullyConnected[t0_h2h_x]x35"
    stack = f"jit(_step_k)/while/body/closed_call/transpose(jvp({scope}))/dot"
    assert prof.parse_scope(stack) == (
        "FullyConnected", "t0_h2h_x (x35)", "backward")
    stack = "/".join(["jit(_step_k)/while/body", prof.phase_scope("update"),
                      prof.param_scope("fc1_weight"), "sub"])
    assert prof.parse_scope(stack) == ("update", "fc1_weight", "update")
    assert prof.parse_scope(
        "jit(f)/" + prof.phase_scope("window_data") + "/dynamic_slice") == (
        "window_data", None, "other")
    # an operator's own scopes nest below the node's and change nothing
    assert prof.parse_scope(
        "jit(_step)/jvp(GatedDeltaRule[l0_gdr])/gated_delta_rule/"
        "within_chunks/dot_general:") == ("GatedDeltaRule", "l0_gdr",
                                          "forward")


# --- the scopes in a train program's HLO -----------------------------------

def _small_net():
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, num_filter=4, kernel=(3, 3), pad=(1, 1),
                           name="conv0")
    b = mx.sym.BatchNorm(c, name="bn0")
    a = mx.sym.Activation(b, act_type="relu", name="relu0")
    f = mx.sym.FullyConnected(mx.sym.Flatten(a, name="flat"), num_hidden=5,
                              name="fc1")
    return mx.sym.SoftmaxOutput(f, name="softmax")


def _fit_small(batches=2):
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(
        rng.rand(8 * batches, 3, 8, 8).astype("float32"),
        rng.randint(0, 5, (8 * batches,)).astype("float32"), batch_size=8)
    mod = mx.mod.Module(_small_net(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    return mod


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.fixture(scope="module", params=["plain", "mirror"])
def train_hlo(request):
    """(remat, op_names of the compiled fused train program, op nodes)."""
    mirror = request.param == "mirror"
    old = os.environ.get("MXNET_BACKWARD_DO_MIRROR")
    if mirror:
        os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        mod = _fit_small()
        names = _op_names(ex.fused_window_hlo()["compiled"])
    finally:
        if mirror:
            if old is None:
                del os.environ["MXNET_BACKWARD_DO_MIRROR"]
            else:
                os.environ["MXNET_BACKWARD_DO_MIRROR"] = old
    nodes = [n for n in mod._exec_group.execs[0].graph.topo
             if not n.is_variable]
    return mirror, names, nodes


def _passes(names, node):
    return {prof.parse_scope(n)[2] for n in names
            if (prof.parse_scope(n) or ("", ""))[:2] == (node.op.name,
                                                        node.name)}


def test_every_node_lowers_forward_under_its_scope(train_hlo):
    _mirror, names, nodes = train_hlo
    assert len(nodes) == 6
    for node in nodes:
        if node.op.name == "Flatten":
            continue  # a reshape: XLA keeps no instruction of it
        assert "forward" in _passes(names, node), node.name


def test_backward_carries_the_scope_in_transpose_form(train_hlo):
    _mirror, names, nodes = train_hlo
    for node in nodes:
        if node.op.name == "Flatten":
            continue
        scope = prof.node_scope(node.op.name, node.name)
        assert any(f"transpose(jvp({scope}))" in n for n in names), scope
        assert "backward" in _passes(names, node), node.name


def test_recompute_only_under_the_mirror_switch(train_hlo):
    """The forward that runs again carries jax's remat mark below the
    node's scope; BatchNorm holds auxiliary state and is not rematted."""
    mirror, names, nodes = train_hlo
    again = {n.name for n in nodes if "recompute" in _passes(names, n)}
    if mirror:
        assert {"relu0", "softmax"} <= again
        assert "bn0" not in again
    else:
        assert again == set()


def test_one_update_scope_a_parameter(train_hlo):
    _mirror, names, _nodes = train_hlo
    updated = {prof.parse_scope(n)[1] for n in names
               if (prof.parse_scope(n) or ("",))[0] == "update"}
    assert updated == {"conv0_weight", "conv0_bias", "bn0_gamma", "bn0_beta",
                       "fc1_weight", "fc1_bias"}


def test_a_batched_group_lowers_under_its_group_scope():
    import stacked_wgrad_cases as swc

    sym, shapes, _loss, n_groups = swc.recurrent("lstm", layers=1, steps=5)
    exe = swc.bound(sym, shapes, swc.values(sym, shapes))
    exe.forward(is_train=True)
    exe.backward()
    exe.grad_dict["l0_i2h_weight"].asnumpy()
    text = exe._get_jit("train_step").executable.as_text()
    groups = {m for n in _op_names(text)
              for m in re.findall(r"FullyConnected\[[^\]]*\]x\d+", n)}
    assert n_groups >= 1 and len(groups) == n_groups
    assert all(g.endswith("x5") for g in groups)
    assert any(prof.parse_scope(n) and prof.parse_scope(n)[1].endswith(
        "(x5)") and prof.parse_scope(n)[2] == "backward"
        for n in _op_names(text))


def test_scoped_nodes_counts_the_train_programs_op_nodes():
    tm.reset()
    mod = _fit_small(batches=3)
    assert tm.counter("executor.scoped_nodes").value == 3 * 6
    # a forward-only program launches no train program
    before = tm.counter("executor.scoped_nodes").value
    exe = mod._exec_group.execs[0]
    exe.forward(is_train=False)
    exe.outputs[0].asnumpy()
    assert tm.counter("executor.scoped_nodes").value == before
    assert exe.graph.scoped_nodes == 6  # and it too lowers under names


# --- the pure reduce --------------------------------------------------------

FWD = "jit(_step)/jvp(Convolution[conv0])/conv_general_dilated:"
BWD = ("jit(_step)/transpose(jvp(Convolution[conv0]))/"
       "conv_general_dilated:")
UPD = "jit(_step)/executor.update/param[conv0_weight]/sub:"


def op(name, tf_op, start, dur, flops=0.0, nbytes=0.0, program="p",
       operands=""):
    return (f"%{name} = f32[8]{{0}} fusion({operands})", tf_op, start, dur,
            flops, nbytes, program)


def test_reduce_takes_nested_events_out_of_their_holder():
    ops = [op("while.1", FWD, 0, 100, flops=999.0),
           op("fusion.1", FWD, 10, 30, flops=5.0, nbytes=64.0),
           op("fusion.2", BWD, 50, 40, flops=7.0),
           op("fusion.3", UPD, 120, 10)]
    t = prof.reduce_trace(ops)
    rows = {(r["operator"], r["pass"]): r for r in t["by_operator"]}
    assert rows["Convolution", "forward"]["ms"] == pytest.approx(60e-6)
    assert rows["Convolution", "backward"]["ms"] == pytest.approx(40e-6)
    assert rows["update", "update"]["ms"] == pytest.approx(10e-6)
    assert t["busy_ms"] == pytest.approx(110e-6)
    assert sum(r["share"] for r in t["by_operator"]) == pytest.approx(1.0)
    # the holder's own statistics would count what it holds again
    assert rows["Convolution", "forward"]["flops"] == 5.0
    assert rows["Convolution", "forward"]["bytes"] == 64.0
    node = {(r["node"], r["pass"]) for r in t["by_node"]}
    assert ("conv0", "forward") in node and ("conv0_weight", "update") in node


def test_reduce_splits_one_operator_by_what_follows_its_node():
    """``inner="Convolution"``: its rows by name stack inside the node and
    pass, every node of it together, the instructions named serial and
    all; an operation booked through a neighbour has no stack of its own;
    the other operators' rows stay out, and without ``inner`` no table."""
    top_k = "jit(_step)/jvp(Convolution[conv0])/top_k:"
    gather = "jit(_step)/jvp(Convolution[conv1])/jit(take_along_axis)/gather:"
    back = ("jit(_step)/transpose(jvp(Convolution[conv0]))/"
            "jvp(Convolution[conv0])/checkpoint/jit(take_along_axis)/"
            "scatter-add:")
    ops = [op("sort.1", top_k, 0, 30), op("fusion.59", gather, 40, 50),
           op("fusion.60", gather, 100, 30),
           op("fusion.61", gather.replace("conv1", "conv0"), 140, 20),
           op("fusion.50", back, 200, 70),
           op("copy.3", "", 300, 5, operands="%fusion.50"),
           op("fusion.9", UPD, 400, 10)]
    t = prof.reduce_trace(ops, inner="Convolution")
    rows = {(r["inner"], r["pass"]): r for r in t["by_inner"]}
    assert set(rows) == {
        ("top_k", "forward"), ("jit(take_along_axis)/gather", "forward"),
        ("jit(take_along_axis)/scatter-add", "backward"), ("", "backward")}
    row = rows["jit(take_along_axis)/gather", "forward"]
    assert row["ms"] == pytest.approx(100e-6) and row["calls"] == 3
    assert row["xla"][0] == ["fusion.59", pytest.approx(50e-6), 1]
    assert rows["", "backward"]["xla"][0][0] == "copy.3"
    assert sum(r["ms"] for r in t["by_inner"]) == pytest.approx(
        sum(r["ms"] for r in t["by_operator"]
            if r["operator"] == "Convolution"))
    assert t["by_inner"][0]["inner"] == "jit(take_along_axis)/gather"
    assert "by_inner" not in prof.reduce_trace(ops)
    assert prof.reduce_trace(ops, inner="MoE")["by_inner"] == []


def test_reduce_divides_by_the_windows_step_roots():
    spans = [("bench.traced_slice", 0, 1000), ("fit.step", 10, 400),
             ("fit.dispatch", 20, 50), ("fit.step", 500, 400),
             ("fit.dispatch", 510, 50), ("fit.dispatch", 950, 40),
             ("fit.step", 2000, 400)]
    ops = [op("fusion.1", FWD, 100, 300), op("fusion.1", FWD, 600, 300),
           op("fusion.1", FWD, 2100, 300)]
    t = prof.reduce_trace(ops, spans=spans, window="bench.traced_slice")
    # two whole roots and a third dispatch whose root the trace cut off
    assert t["steps"] == 3
    row, = t["by_operator"]
    assert row["calls"] == 2 and row["ms_per_step"] == pytest.approx(2e-4)
    with pytest.raises(ValueError, match="no span"):
        prof.reduce_trace(ops, spans=spans, window="nowhere")


def test_reduce_names_a_gap_by_the_innermost_program_span():
    spans = [("bench.traced_slice", 0, 1000), ("bench.fit", 0, 1000),
             ("fit.step", 0, 1000), ("fit.metric", 300, 250),
             ("PjitFunction(loss)", 350, 50), ("executor.launch", 700, 20)]
    ops = [op("fusion.1", FWD, 0, 300), op("fusion.2", FWD, 500, 205),
           op("fusion.3", FWD, 715, 85), op("fusion.4", FWD, 900, 100)]
    idle = prof.reduce_trace(ops, spans=spans,
                             window="bench.traced_slice")["idle"]
    assert idle["total_ms"] == pytest.approx(310e-6)
    assert idle["longest"][0][0] == "fit.metric"       # 300-500, not jax's
    assert idle["longest"][0][1:] == [pytest.approx(200e-6),
                                      pytest.approx(300e-6)]
    assert idle["by_span"]["executor.launch"] == pytest.approx(10e-6)
    assert idle["by_span"]["fit.step"] == pytest.approx(100e-6)
    assert not any(name.startswith("bench.") for name in idle["by_span"])
    # no program span at all: unattributed, never the caller's annotation
    idle = prof.reduce_trace(ops, spans=spans[:2],
                             window="bench.traced_slice")["idle"]
    assert set(idle["by_span"]) == {"unattributed"}


def test_reduce_says_when_a_stale_cache_took_the_names():
    stale = [op("fusion.1", "jit(_step)/conv_general_dilated:", 0, 90),
             op("fusion.2", FWD, 100, 10)]
    t = prof.reduce_trace(stale)
    assert t["unscoped_share"] == pytest.approx(0.9)
    assert "cache" in t["hint"]
    assert t["unscoped"][0]["name"] == "fusion"
    fresh = prof.reduce_trace([op("fusion.1", FWD, 0, 96),
                               op("fusion.2", "jit(_step)/add:", 100, 4)])
    assert fresh["unscoped_share"] == pytest.approx(0.04)
    assert "hint" not in fresh


def test_reduce_books_a_nameless_copy_to_its_neighbour():
    """Up the operands first, then down the readers; through the HLO graph
    where the link is no device event (a get-tuple-element)."""
    ops = [op("fusion.9", UPD, 0, 50),
           op("copy.1", "", 60, 20, operands="f32[8]{0} %fusion.9"),
           op("copy.2", "", 90, 10, operands="f32[8]{0} %param.3"),
           op("fusion.10", FWD, 100, 40, operands="f32[8]{0} %copy.2"),
           op("copy.3", "", 150, 5, operands="f32[8]{0} %gte.4")]
    t = prof.reduce_trace(ops)
    rows = {(r["operator"], r["pass"]): r["ms"] for r in t["by_operator"]}
    assert rows["update", "update"] == pytest.approx(70e-6)
    assert rows["Convolution", "forward"] == pytest.approx(50e-6)
    assert rows["unscoped", "other"] == pytest.approx(5e-6)
    assert t["inherited_share"] == pytest.approx(30 / 125)
    graph = {("p", "gte.4"): ("", ["fusion.9"])}
    t = prof.reduce_trace(ops, graph=graph)
    assert t["unscoped_share"] == 0.0
    assert t["by_operator"][0]["ms"] == pytest.approx(75e-6)


# the event lists of the three kernel_table tests this reader replaces
def _xla(name, dur, start, nbytes=0.0):
    return (name, "", start, dur, 0.0, nbytes, "p")


def test_reader_aggregates_and_ranks_by_xla_kind():
    ops = [_xla("convolution.1", 100.0, 0), _xla("convolution.1", 50.0, 200),
           _xla("fusion.7", 200.0, 300, nbytes=1024.0),
           _xla("reduce.2", 25.0, 600)]
    t = prof.reduce_trace(ops, spans=[("fit.dispatch", 0, 999)])
    assert [r["name"] for r in t["unscoped"]] == ["fusion", "convolution",
                                                  "reduce"]
    conv = t["unscoped"][1]
    assert conv["ms"] == pytest.approx(150e-6) and conv["calls"] == 2
    assert t["unscoped"][0]["bytes"] == 1024.0
    assert t["unscoped"][0]["share"] == pytest.approx(200.0 / 375.0)
    assert sum(r["share"] for r in t["unscoped"]) == pytest.approx(1.0)


def test_reader_cuts_to_the_top_rows():
    ops = [_xla(f"op{i}.0", float(i + 1), 100 * i) for i in range(15)]
    t = prof.reduce_trace(ops, top=3)
    assert [r["name"] for r in t["unscoped"]] == ["op14", "op13", "op12"]
    assert len(prof.reduce_trace(ops)["unscoped"]) == 10


def test_reader_on_an_empty_trace():
    t = prof.reduce_trace([])
    assert t["by_operator"] == [] and t["by_program"] == []
    assert t["busy_ms"] == 0.0 and t["unscoped_share"] == 0.0
    assert t["idle"] == {"total_ms": 0.0, "by_span": {}, "longest": []}


# --- one real trace through the loader --------------------------------------

@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """The profile directory of three traced steps of the small net."""
    where = tmp_path_factory.mktemp("profile")
    mod = _fit_small()
    rng = np.random.RandomState(1)
    it = mx.io.NDArrayIter(rng.rand(24, 3, 8, 8).astype("float32"),
                           rng.randint(0, 5, (24,)).astype("float32"),
                           batch_size=8)
    mx.profiler.profiler_set_config(filename=str(where / "p.json"))
    mx.profiler.profiler_set_state("run")
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    mx.profiler.dump_profile()
    return mx.profiler._state["logdir"]


def test_device_table_reads_a_trace_by_operator(cpu_trace):
    t = mx.profiler.device_table()  # this process's last trace
    assert t["steps"] == 3
    rows = {(r["operator"], r["pass"]): r for r in t["by_operator"]}
    for key in (("Convolution", "forward"), ("FullyConnected", "backward"),
                ("update", "update")):
        assert rows[key]["ms"] > 0 and rows[key]["calls"] >= 3, key
    assert t["unscoped_share"] < 0.25
    assert sum(r["share"] for r in t["by_operator"]) == pytest.approx(1.0)
    # fit's step returns no gradients: the program is named for the
    # wrapper that leaves them out (executor._build_train_plan)
    assert any(r["program"].startswith("jit_step_fn") and r["calls"] == 3
               for r in t["by_program"])
    assert set(t["idle"]["by_span"]) <= {
        "fit.step", "fit.dispatch", "fit.metric", "fit.data_wait",
        "fit.callback", "executor.launch", "executor.stage_args",
        "unattributed"}
    # XLA:CPU runs a step's operations after the host has left its span
    # when the machine is loaded: the window may hold few of them, or none
    one = mx.profiler.device_table(cpu_trace, window="fit.step")
    assert one["steps"] == 1 and 0 <= one["busy_ms"] < t["busy_ms"]


def test_trace_table_prints_the_tables(cpu_trace, capsys):
    import trace_table

    assert trace_table.main([cpu_trace]) == 0
    out = capsys.readouterr().out
    assert "Convolution" in out and "jit_step_fn" in out and "idle" in out
    assert trace_table.main([cpu_trace, "--by", "node", "--json"]) == 0
    assert '"by_node"' in capsys.readouterr().out


def test_trace_table_splits_an_operator_by_its_inner_scopes(cpu_trace,
                                                            capsys):
    """On XLA:CPU the name stacks come from the trace's HLO."""
    import trace_table

    assert trace_table.main([cpu_trace, "--by", "inner", "--operator",
                             "Convolution"]) == 0
    out = capsys.readouterr().out
    assert "inside the operator" in out and "conv_general_dilated" in out
    assert "backward" in out and "jit_step_fn" not in out
    table = mx.profiler.device_table(cpu_trace, inner="Convolution")
    assert sum(r["ms"] for r in table["by_inner"]) == pytest.approx(
        sum(r["ms"] for r in table["by_operator"]
            if r["operator"] == "Convolution"))
    with pytest.raises(SystemExit):
        trace_table.main([cpu_trace, "--by", "inner"])


def test_device_table_without_a_trace_says_so(tmp_path):
    with pytest.raises(ValueError, match="no .xplane.pb"):
        mx.profiler.device_table(str(tmp_path))


# --- tools/lowered_hashes.py -------------------------------------------------

def test_lowered_hashes_do_not_see_the_scopes(monkeypatch):
    """The same train program under other names lowers to the same text
    without debug info; another program does not."""
    import lowered_hashes

    def shas():
        seen = lowered_hashes.lowered_programs(
            lambda: _fit_small(batches=2), launches=1)
        return [sha for counter, sha, _size in seen
                if counter == lowered_hashes.FUSED]

    first = shas()
    assert len(first) == 1
    monkeypatch.setattr(prof, "node_scope",
                        lambda op, node, group=0: f"renamed[{node}]")
    monkeypatch.setattr(prof, "phase_scope", lambda phase: "phase." + phase)
    assert shas() == first
    monkeypatch.setattr(
        sys.modules[__name__], "_small_net",
        lambda: mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            mx.sym.Flatten(mx.sym.Variable("data")), num_hidden=5,
            name="fc1"), name="softmax"))
    assert shas() != first
