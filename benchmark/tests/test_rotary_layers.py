"""``rotary.kernel_nodes_per_step.seq`` and ``rotary.device_ms_per_step.seq``:
each layer file loads and agrees with its entry in ``BENCHMARK.json``, which
lists the eight cells whose models hold a ``RotaryEmbedding``; the first reads
the program's counter over the window's steps, and 0 from a program without
it (the parent of the PR that brought it); the second sums the traced table's
rows of that operator over the slice's steps, 0.0 where the table has no such
row, and reads nothing, without raising, where the run has no table."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.test_drivers import CANNED_TRACE

COUNTER = "rotary.kernel_nodes_per_step.seq"
DEVICE_MS = "rotary.device_ms_per_step.seq"
CELLS = ["sdar-30b-a3b-train-1c", "keye-vl2-30b-train-1c",
         "ouro-2.6b-train-1c", "olmoe-1b7b-train-1c", "trinity-mini-train-1c",
         "qwen3-next-train-1c", "zaya1-8b-train-1c", "kanana2-30b-train-1c"]
ENTRIES = {
    COUNTER: ("1/step", "fused step", "train_tokens_per_s", "higher",
              "program_counter"),
    DEVICE_MS: ("ms", "kernels", "train_tokens_per_s", "lower",
                "device_trace"),
}


def counted_run(at_fence, at_end, steps=48):
    return {"obs": {"tm0": {"executor": dict(fused_plan_hit=6, **at_fence)},
                    "tm1": {"executor": dict(fused_plan_hit=54, **at_end)},
                    "steps": steps}}


def traced_run(rows, steps=4):
    """A traced slice of ``steps`` steps whose table by operator holds
    ``rows`` (operator, pass, ms of the slice) beside the canned ones."""
    table = dict(CANNED_TRACE["table"], by_operator=(
        CANNED_TRACE["table"]["by_operator"]
        + [{"operator": o, "pass": p, "ms": ms, "calls": steps}
           for o, p, ms in rows]))
    return {"obs": {"trace": dict(CANNED_TRACE, table=table),
                    "trace_slice": (steps, 1.0)}}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_layer_file_agrees_with_its_entry(name):
    bench = hx.load_json(hx.ROOT, "BENCHMARK.json")
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = hx.layer_readers()[name]
    assert mod.NAME == name
    assert (entry["unit"], entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER,
                                 mod.SOURCE) == ENTRIES[name]
    # a later PR may append cells: nothing here pins the list
    assert set(CELLS) <= set(entry["workloads"])
    assert "kimi-linear-train-1c" not in entry["workloads"]
    for cell in entry["workloads"]:
        assert name in hx.metrics_of(bench, cell, "per_layer")
        assert "train_tokens_per_s" in hx.metrics_of(bench, cell,
                                                     "end_to_end")


@pytest.mark.parametrize("nodes,warm_up,steps", [
    (4, 6, 48),    # the SDAR and Keye-VL-2.0 cells: four layers' queries
    (0, 6, 48),    # a cell under the rule: the counter is there and still
])
def test_counter_reads_the_nodes_over_the_windows_steps(nodes, warm_up,
                                                        steps):
    read = hx.layer_readers()[COUNTER].read
    run = counted_run({"rotary_kernel_nodes": nodes * warm_up},
                      {"rotary_kernel_nodes": nodes * (warm_up + steps)},
                      steps)
    assert read(run) == float(nodes)


def test_a_program_without_the_counter_reads_zero():
    run = counted_run({"conv_kernel_layers": 24}, {"conv_kernel_layers": 216})
    assert hx.layer_readers()[COUNTER].read(run) == 0.0


@pytest.mark.parametrize("rows,steps,want", [
    # the SDAR cell at the parent (ledger, PR 57), a slice of 8 steps
    ([("RotaryEmbedding", "backward", 104.4),
      ("RotaryEmbedding", "forward", 96.24)], 8, 25.08),
    # a recomputed forward counts too; other operators' rows do not
    ([("RotaryEmbedding", "forward", 8.0),
      ("RotaryEmbedding", "recompute", 8.0),
      ("RotaryEmbedding", "backward", 4.0), ("RMSNorm", "forward", 100.0)],
     4, 5.0),
])
def test_device_ms_sums_every_row_of_the_operator_a_step(rows, steps, want):
    read = hx.layer_readers()[DEVICE_MS].read
    assert read(traced_run(rows, steps)) == pytest.approx(want)


@pytest.mark.parametrize("run,want", [
    (traced_run([]), 0.0),                            # no such operator
    (traced_run([("RMSNorm", "forward", 3.0)]), 0.0),
    ({"obs": {"trace": {}, "trace_slice": (4, 1.0)}}, None),  # no table
    ({"obs": {"trace_slice": (4, 1.0)}}, None),               # no trace
], ids=["no_rows", "other_operators", "no_table", "no_trace"])
def test_device_ms_without_rows_is_zero_and_without_a_table_nothing(run,
                                                                     want):
    assert hx.layer_readers()[DEVICE_MS].read(run) == want
