"""``setup.infer_evals``: the layer file loads, agrees with its entry in
``BENCHMARK.json`` and reads the program's counter from the snapshot taken
at the end of warm-up; a program that has no such counter (the parent of the
PR that brought it) reads None, so its line leaves the metric out, and does
not raise."""

from benchmark.lib import harness as hx

NAME = "setup.infer_evals"
CELLS = ["resnet50-train-1c", "lstm-ptb-train-1c", "resnet50-train-4c",
         "olmoe-1b7b-train-1c"]


def made_run(symbol_at_fence, symbol_at_end=None):
    tm0 = {"executor": {"fused_plan_hit": 6}, **symbol_at_fence}
    tm1 = {"executor": {"fused_plan_hit": 54},
           **(symbol_at_fence if symbol_at_end is None else symbol_at_end)}
    return {"obs": {"tm0": tm0, "tm1": tm1, "steps": 48}}


def test_layer_file_agrees_with_its_entry():
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    mod = hx.layer_readers()[NAME]
    assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
        "1", "module set-up", "setup_s", "lower", "program_counter")
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE}
    # a later PR may append cells and metrics: nothing here pins the lists
    assert set(CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert NAME in hx.metrics_of(bench, cell, "per_layer")
        assert "setup_s" in hx.metrics_of(bench, cell, "end_to_end")


def test_reads_the_counter_at_the_end_of_warm_up():
    read = hx.layer_readers()[NAME].read
    # six binds asked 12 700 times and evaluated 97; the window's later
    # reference check does not count
    run = made_run({"symbol": {"infer_eval": 97, "infer_memo_hit": 12603}},
                   {"symbol": {"infer_eval": 140, "infer_memo_hit": 14000}})
    assert read(run) == 97
    assert read(made_run({"symbol": {"infer_eval": 0}})) == 0


def test_a_program_without_the_counter_reads_none():
    read = hx.layer_readers()[NAME].read
    assert read(made_run({})) is None
    assert read(made_run({"symbol": {"other": 3}})) is None
