"""``step.scoped_nodes_per_step.{fit,seq}`` and
``compile.window_compiles.{fit,seq}``: each layer file loads and agrees with
its entry in ``BENCHMARK.json``, reads a hand-made pair of snapshots, reads
nothing alarming from a program from before the counter, and the two
drivers here on the CPU print both of their family on a traced run."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import (canned_trace, tiny_lstm,  # noqa: F401
                                          tiny_resnet)
from benchmark.tests.util import run_driver

FIT = ["resnet50-train-1c", "resnet50-train-4c"]
SEQ = ["lstm-ptb-train-1c", "olmoe-1b7b-train-1c", "trinity-mini-train-1c",
       "qwen3-next-train-1c"]
ENTRIES = {
    "step.scoped_nodes_per_step.fit": (
        "1/step", "fused step", "train_samples_per_s", "higher",
        "program_counter", FIT),
    "step.scoped_nodes_per_step.seq": (
        "1/step", "fused step", "train_tokens_per_s", "higher",
        "program_counter", SEQ),
    "compile.window_compiles.fit": (
        "1", "compile and cache", "train_samples_per_s", "lower",
        "program_span", FIT),
    "compile.window_compiles.seq": (
        "1", "compile and cache", "train_tokens_per_s", "lower",
        "program_span", SEQ),
}


def bench_file():
    return hx.load_json(hx.ROOT, "BENCHMARK.json")


def made_run(scoped=(0, 0), lowered=(6, 6), steps=48):
    def snap(nodes, count):
        executor = {"trace_lower": {"count": count, "sum": 9e6,
                                    "self_sum": 9e6}}
        if nodes is not None:
            executor["scoped_nodes"] = nodes
        return {"executor": executor}
    return {"obs": {"tm0": snap(scoped[0], lowered[0]),
                    "tm1": snap(scoped[1], lowered[1]), "steps": steps}}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_layer_file_agrees_with_its_entry(name):
    bench = bench_file()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = hx.layer_readers()[name]
    unit, layer, moves, better, source, cells = ENTRIES[name]
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
        unit, layer, moves, better, source)
    assert (entry["unit"], entry["layer"], entry["moves"], entry["better"],
            entry["source"], entry["workloads"]) == (
        unit, layer, moves, better, source, cells)
    # the new entries stand at the end of the list
    assert entry in bench["per_layer"][-4:]
    for cell in FIT + SEQ:
        assert (name in hx.metrics_of(bench, cell, "per_layer")) \
            == (cell in cells)


@pytest.mark.parametrize("family", ["fit", "seq"])
def test_scoped_nodes_reads_the_graphs_op_nodes_per_step(family):
    read = hx.layer_readers()["step.scoped_nodes_per_step." + family].read
    # warm-up launched 24 steps of a 175-node graph, the window 48 more
    assert read(made_run(scoped=(24 * 175, 72 * 175))) == 175.0
    # six buckets of other sizes: the mean over the steps of the window
    assert read(made_run(scoped=(0, 20 * 100 + 28 * 200))) == pytest.approx(
        (20 * 100 + 28 * 200) / 48)
    # a path that lost the names, and a program from before the counter
    assert read(made_run(scoped=(4200, 4200))) == 0.0
    assert read(made_run(scoped=(None, None))) == 0.0


@pytest.mark.parametrize("family", ["fit", "seq"])
def test_window_compiles_counts_the_spans_inside_the_window(family):
    read = hx.layer_readers()["compile.window_compiles." + family].read
    assert read(made_run(lowered=(6, 6))) == 0
    assert read(made_run(lowered=(6, 8))) == 2
    # a program without the span: nothing to count, and no raise
    run = made_run()
    run["obs"].update(tm0={}, tm1={})
    assert read(run) == 0


def check_traced_run(run, family, nodes):
    readers = hx.layer_readers()
    scoped = readers["step.scoped_nodes_per_step." + family].read(run)
    compiles = readers["compile.window_compiles." + family].read(run)
    print("step.scoped_nodes_per_step." + family, scoped)
    print("compile.window_compiles." + family, compiles)
    assert compiles == 0
    lo, hi = nodes
    assert lo <= scoped <= hi


def op_nodes(sym):
    return sum(1 for n in sym._topo() if not n.is_variable)


def test_fit_driver_traced_run_prints_both(canned_trace):  # noqa: F811
    cfg, traffic = tiny_resnet()
    run = run_driver(cfg, traffic, builder_of="resnet50-v2", seconds=0.3,
                     trace=1, bench=bench_file())
    assert run["correct"]
    n = op_nodes(run["builder"].symbol(cfg, run["mx"]))
    check_traced_run(run, "fit", (n, n))


def test_bucketing_driver_traced_run_prints_both(canned_trace):  # noqa: F811
    cfg, traffic = tiny_lstm()
    run = run_driver(cfg, traffic, builder_of="lstm-ptb-large", seconds=0.3,
                     trace=1, bench=bench_file())
    assert run["correct"]
    # the mean over the buckets the window visited: between the shortest
    # and the longest unrolled graph
    gen, _state_names = run["builder"].sym_gen(cfg, run["mx"])
    sizes = [op_nodes(gen(k)[0]) for k in cfg["buckets"]]
    check_traced_run(run, "seq", (min(sizes), max(sizes)))
