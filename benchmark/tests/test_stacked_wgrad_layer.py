"""``step.stacked_wgrad_per_step.seq``: the layer file loads, agrees with its
entry in ``BENCHMARK.json`` and reads the program's counter from a canned
pair of snapshots; a program that has no such counter (the parent of the PR
that brought it) reads 0 and does not raise."""

from benchmark.lib import harness as hx

NAME = "step.stacked_wgrad_per_step.seq"


def made_run(before, after, steps=48):
    tm0 = {"executor": {"fused_plan_hit": 6, **before}}
    tm1 = {"executor": {"fused_plan_hit": 54, **after}}
    return {"obs": {"tm0": tm0, "tm1": tm1, "steps": steps}}


def test_layer_file_agrees_with_its_entry():
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = hx.layer_readers()[NAME]
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
        "1/step", "fused step", "train_tokens_per_s", "higher",
        "program_counter")
    assert entry["workloads"] == ["lstm-ptb-train-1c"]
    assert NAME in hx.metrics_of(bench, "lstm-ptb-train-1c", "per_layer")
    assert NAME not in hx.metrics_of(bench, "resnet50-train-1c", "per_layer")


def test_reads_groups_per_step_from_the_counter():
    read = hx.layer_readers()[NAME].read
    # warm-up launched 24 steps of four groups each, the window 48 more
    run = made_run({"stacked_wgrad": 96}, {"stacked_wgrad": 96 + 4 * 48})
    assert read(run) == 4.0


def test_a_program_without_the_counter_reads_zero():
    read = hx.layer_readers()[NAME].read
    assert read(made_run({}, {})) == 0.0
