"""The ``memory.*`` layer files (PR 53): each loads, agrees with its entry in
``BENCHMARK.json`` (checked by membership of name and cells, never by a
list's end or whole), reads the program's gauge out of the window's last
snapshot in GiB, and returns None, without raising, for a program that has
no such gauge (the parent of the PR that brought them). Arguments, kept
outputs, scratch and the unattributed rest sum to the run's peak."""

import pytest

from benchmark.lib import harness as hx
from benchmark.lib.readers import GIB
from benchmark.tests.test_drivers import tiny_lstm, tiny_resnet
from benchmark.tests.util import run_driver

BASES = ("memory.step_arguments_gib", "memory.step_kept_outputs_gib",
         "memory.step_scratch_gib", "memory.published_grads_gib",
         "memory.unattributed_gib")
FAMILIES = {
    "fit": ("train_samples_per_s", ["resnet50-train-1c",
                                    "resnet50-train-4c"]),
    "seq": ("train_tokens_per_s", [
        "lstm-ptb-train-1c", "olmoe-1b7b-train-1c", "trinity-mini-train-1c",
        "qwen3-next-train-1c", "kanana2-30b-train-1c", "zaya1-8b-train-1c",
        "kimi-linear-train-1c", "keye-vl2-30b-train-1c"]),
}
NAMES = [f"{base}.{family}" for base in BASES for family in FAMILIES]

# the Kimi-Linear step as its builder's rehearsal read it (PERF.md section 4)
GAUGES = {"program_argument_bytes": int(6.73 * GIB),
          "program_kept_output_bytes": int(0.31 * GIB),
          "program_temp_bytes": int(5.37 * GIB),
          "program_code_bytes": int(0.24 * GIB),
          "published_grad_bytes": 0,
          "train_state_bytes": int(6.70 * GIB)}
PEAK = int(12.82 * GIB)


def made_run(gauges, peak=PEAK):
    executor = {name: {"value": v, "max": v} for name, v in gauges.items()}
    return {"obs": {"tm0": {"executor": {"fused_plan_hit": 6}},
                    "tm1": {"executor": dict(fused_plan_hit=54, **executor)},
                    "steps": 48, "memory_peak_bytes": peak}}


def read(base, run, family="seq"):
    return hx.layer_readers()[f"{base}.{family}"].read(run)


@pytest.mark.parametrize("name", NAMES)
def test_layer_file_agrees_with_its_entry(name):
    family = name.rsplit(".", 1)[1]
    moves, cells = FAMILIES[family]
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = hx.layer_readers()[name]
    assert {k: entry[k] for k in ("unit", "layer", "moves", "better",
                                  "source")} == {
        "unit": mod.UNIT, "layer": mod.LAYER, "moves": mod.MOVES,
        "better": mod.BETTER, "source": mod.SOURCE} == {
        "unit": "GiB", "layer": "fused step", "moves": moves,
        "better": "lower", "source": "program_counter"}
    # a later PR may append cells: nothing here pins the list
    assert set(cells) <= set(entry["workloads"])
    peak = next(m for m in bench["per_layer"]
                if m["name"] == "device.peak_hbm_gib." + family)
    assert set(peak["workloads"]) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert name in hx.metrics_of(bench, cell, "per_layer")
        assert moves in hx.metrics_of(bench, cell, "end_to_end")


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("base, want", [
    ("memory.step_arguments_gib", GAUGES["program_argument_bytes"]),
    ("memory.step_kept_outputs_gib", GAUGES["program_kept_output_bytes"]),
    ("memory.step_scratch_gib",
     GAUGES["program_temp_bytes"] + GAUGES["program_code_bytes"]),
    ("memory.published_grads_gib", 0),
    ("memory.unattributed_gib", PEAK - sum(
        GAUGES["program_" + part + "_bytes"]
        for part in ("argument", "kept_output", "temp", "code"))),
])
def test_reads_the_gauges_value_in_gib(base, want, family):
    assert read(base, made_run(GAUGES), family) == want / GIB


def test_reads_value_and_not_the_high_water_mark():
    run = made_run(GAUGES)
    run["obs"]["tm1"]["executor"]["program_argument_bytes"]["max"] *= 2
    assert read("memory.step_arguments_gib", run) == \
        GAUGES["program_argument_bytes"] / GIB


@pytest.mark.parametrize("peak", [PEAK, int(15.39 * GIB), 0])
def test_the_three_and_the_rest_sum_to_the_peak(peak):
    run = made_run(GAUGES, peak)
    parts = [read(base, run) for base in (
        "memory.step_arguments_gib", "memory.step_kept_outputs_gib",
        "memory.step_scratch_gib", "memory.unattributed_gib")]
    assert sum(parts) == pytest.approx(peak / GIB, abs=1e-9)
    assert sum(parts) == pytest.approx(
        hx.layer_readers()["device.peak_hbm_gib.seq"].read(run), abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_gauges_reads_none(name):
    assert hx.layer_readers()[name].read(made_run({})) is None


@pytest.mark.parametrize("missing", ["program_temp_bytes",
                                     "program_code_bytes"])
def test_half_a_split_is_no_split(missing):
    run = made_run({k: v for k, v in GAUGES.items() if k != missing})
    assert read("memory.step_scratch_gib", run) is None
    assert read("memory.unattributed_gib", run) is None
    assert read("memory.step_arguments_gib", run) is not None


def check_driver_run(run, family):
    """A CPU run of a driver: XLA:CPU's executables answer
    ``memory_analysis()`` too, so every ``memory.*`` metric of the family
    reads; the CPU keeps no allocator statistics, so the peak is 0 and the
    rest is what the three leave of it."""
    got = {base: read(base, run, family) for base in BASES}
    assert None not in got.values(), got
    assert got["memory.step_arguments_gib"] > 0
    assert got["memory.step_kept_outputs_gib"] > 0
    # both drivers step through update()'s default, which publishes where
    # the device reports no memory to crowd
    assert 0 < got["memory.published_grads_gib"] <= \
        got["memory.step_kept_outputs_gib"]
    assert got["memory.step_arguments_gib"] \
        + got["memory.step_kept_outputs_gib"] \
        + got["memory.step_scratch_gib"] + got["memory.unattributed_gib"] \
        == pytest.approx(run["obs"]["memory_peak_bytes"] / GIB, abs=1e-9)


@pytest.fixture
def fresh_gauges():
    """A cell is a process of its own; here other tests' train programs ran
    before, and the gauges hold the heaviest since the last reset."""
    from mxnet_tpu import telemetry

    telemetry.reset()


def test_the_fit_driver_reports_them(fresh_gauges):
    cfg, traffic = tiny_resnet()
    run = run_driver(cfg, traffic, builder_of="resnet50-v2", seconds=0.3)
    check_driver_run(run, "fit")


def test_the_bucketing_driver_reports_the_heaviest_bucket(fresh_gauges):
    cfg, traffic = tiny_lstm()
    run = run_driver(cfg, traffic, builder_of="lstm-ptb-large", seconds=0.3)
    check_driver_run(run, "seq")
    # of its two buckets' programs the gauges are one's, the heavier's
    from mxnet_tpu import aot

    steps = [r for r in aot.memory_table()["programs"]
             if r["label"].startswith("fused update [data(4, ")
             and r["launches"]]
    assert {r["label"] for r in steps} >= {
        "fused update [data(4, 4), ..., softmax_label(4, 4)]",
        "fused update [data(4, 8), ..., softmax_label(4, 8)]"}
    executor = run["obs"]["tm1"]["executor"]
    footprint = sum(executor["program_" + part + "_bytes"]["value"]
                    for part in ("argument", "kept_output", "temp", "code"))
    assert footprint == max(r["footprint_bytes"] for r in steps)
