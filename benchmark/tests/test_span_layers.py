"""The per-layer metrics that read the program's own spans: each reader on a
hand-made pair of snapshots, and the two drivers here on the CPU printing
every one of their family on a traced run."""

import pytest

from benchmark.lib import harness as hx
from benchmark.lib import spans
from benchmark.tests.test_drivers import (canned_trace, tiny_lstm,  # noqa: F401
                                          tiny_resnet)
from benchmark.tests.util import run_driver

US = 1_000_000


def hist(total, own=None, count=1):
    return {"count": count, "sum": total,
            "self_sum": total if own is None else own}


def snapshots():
    """tm0 at the end of warm-up (totals since process start) and tm1 at the
    end of a window of 10 steps, as ``telemetry.snapshot()`` nests them."""
    tm0 = {
        "startup": {"import": hist(3 * US)},
        "rnn": {"bucket_iter_build": hist(5 * US)},
        "module": {"bind": hist(8 * US, 7 * US, 2),
                   "init_params": hist(2 * US, 1.5 * US),
                   "init_optimizer": hist(1 * US, 0.5 * US)},
        "executor": {"trace_lower": hist(20 * US, count=2),
                     "compile": hist(30 * US, count=2),
                     "stage_args": hist(0.1 * US, count=4),
                     "launch": hist(0.4 * US, count=4),
                     "fused_plan_hit": 2},
        "fit": {"step": hist(60 * US, 0.2 * US, 4),
                "dispatch": hist(55 * US, 0.3 * US, 4),
                "data_wait": hist(0.5 * US, count=5),
                "metric": hist(1 * US, count=4),
                "callback": hist(2.5 * US, count=4),
                "steps_in_flight": hist(8, count=4),
                "batches": 0},
    }
    tm1 = {k: dict(v) for k, v in tm0.items()}
    tm1["fit"] = dict(tm0["fit"], step=hist(60 * US + 900_000,
                                            0.2 * US + 50_000, 14),
                      steps_in_flight=hist(8 + 25, count=14))
    tm1["executor"] = dict(tm0["executor"],
                           launch=hist(0.4 * US + 30_000, count=14))
    return tm0, tm1


ALL_PARTS = [{"name": m} for m in spans.SETUP_PARTS] + [
    {"name": "setup.unattributed_s"}]


def made_run(cell="c", per_layer=ALL_PARTS, setup_s=100.0):
    tm0, tm1 = snapshots()
    return {"obs": {"tm0": tm0, "tm1": tm1, "steps": 10}, "setup_s": setup_s,
            "bench": {"per_layer": per_layer}, "cell": {"name": cell}}


EXPECTED = {
    "setup.import_s": 3.0,
    "setup.input_build_s": 5.0,
    "setup.bind_s": 7.0,              # self time: less the compile inside it
    "setup.init_s": 2.0,              # 1.5 + 0.5
    "setup.trace_lower_s": 20.0,
    "setup.compile_or_load_s": 30.0,
    # self times of fit.step .dispatch .data_wait .metric .callback and of
    # executor.stage_args .launch
    "setup.warmup_steps_s": 0.2 + 0.3 + 0.5 + 1.0 + 2.5 + 0.1 + 0.4,
    "setup.unattributed_s": 100.0 - (3 + 5 + 7 + 2 + 20 + 30 + 5.0),
    "loop.step_ms.fit": 90.0, "loop.step_ms.seq": 90.0,
    "loop.self_ms_per_step.fit": 5.0, "loop.self_ms_per_step.seq": 5.0,
    "dispatch.launch_ms_per_step.fit": 3.0,
    "dispatch.launch_ms_per_step.seq": 3.0,
    "dispatch.steps_in_flight.fit": 2.5, "dispatch.steps_in_flight.seq": 2.5,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_hand_made_snapshots(name):
    reader = hx.layer_readers()[name]
    assert reader.read(made_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    """The parent of the PR that brought the spans: ``fit.dispatch`` and
    ``fit.data_wait`` with no ``self_sum``, and none of the new names."""
    old = {"fit": {"dispatch": {"count": 4, "sum": 9}, "batches": 3,
                   "data_wait": {"count": 4, "sum": 1}}}
    run = made_run()
    run["obs"].update(tm0=old, tm1=old)
    assert hx.layer_readers()[name].read(run) is None


def test_unattributed_is_setup_less_the_parts_the_cell_reports():
    readers = hx.layer_readers()
    # a cell that does not list the iterator's build leaves it unattributed
    listed = [{"name": m, "workloads": ["c"]} for m in spans.SETUP_PARTS
              if m != "setup.input_build_s"]
    listed.append({"name": "setup.input_build_s", "workloads": ["other"]})
    run = made_run(per_layer=listed)
    parts = [readers[m["name"]].read(run) for m in listed
             if m["name"] != "setup.input_build_s"]
    assert readers["setup.unattributed_s"].read(run) == pytest.approx(
        100.0 - sum(parts))
    assert readers["setup.unattributed_s"].read(run) == pytest.approx(
        EXPECTED["setup.unattributed_s"] + 5.0)
    # every setup.* metric of a cell sums to setup_s
    run = made_run()
    whole = sum(readers[m].read(run) for m in EXPECTED if m.startswith("setup."))
    assert whole == pytest.approx(run["setup_s"])


def bench_file():
    return hx.load_json(hx.ROOT, "BENCHMARK.json")


@pytest.fixture
def fresh_registry():
    """A run reads totals since process start: here the process is the
    test session, so start the registry where ``run_driver`` starts its
    clock."""
    import mxnet_tpu as mx

    mx.telemetry.reset()


def new_metrics(family):
    return [n for n in EXPECTED if n.startswith("setup.")
            or n.endswith("." + family)]


def check_traced_run(run, family, absent=()):
    readers = hx.layer_readers()
    values = {n: readers[n].read(run) for n in new_metrics(family)}
    for name, value in values.items():
        if name in absent:
            continue
        assert value is not None, name
        print(name, value, readers[name].UNIT)
    obs = run["obs"]
    assert values["loop.step_ms." + family] > 0
    assert 0 <= values["loop.self_ms_per_step." + family] \
        <= values["loop.step_ms." + family]
    assert values["dispatch.launch_ms_per_step." + family] \
        <= readers["dispatch.host_ms_per_step." + family].read(run)
    assert 0 <= values["dispatch.steps_in_flight." + family] <= 64
    # exactly `steps` whole iterations lie between the two snapshots
    assert hx.tm_delta(obs["tm0"], obs["tm1"], "fit.step", "count") \
        == obs["steps"]
    setup = [v for n, v in values.items()
             if n.startswith("setup.") and v is not None]
    assert sum(setup) == pytest.approx(run["setup_s"])
    assert all(v >= 0 for n, v in values.items()
               if v is not None and n != "setup.unattributed_s")


def test_fit_driver_traced_run_prints_every_new_fit_metric(
        canned_trace, fresh_registry):  # noqa: F811
    cfg, traffic = tiny_resnet()
    run = run_driver(cfg, traffic, builder_of="resnet50-v2", seconds=0.3,
                     trace=1, bench=bench_file())
    # run_driver names its cell test-cell, which no metric lists: hand the
    # readers the lists of the cell this stands for
    run["cell"] = dict(run["cell"], name="resnet50-train-1c")
    assert run["correct"]
    # no import and no sentence iterator since the registry was reset
    check_traced_run(run, "fit", absent=("setup.input_build_s",
                                         "setup.import_s"))


def test_bucketing_driver_traced_run_prints_every_new_seq_metric(
        canned_trace, fresh_registry):  # noqa: F811
    cfg, traffic = tiny_lstm()
    run = run_driver(cfg, traffic, builder_of="lstm-ptb-large", seconds=0.3,
                     trace=1, bench=bench_file())
    run["cell"] = dict(run["cell"], name="lstm-ptb-train-1c")
    assert run["correct"]
    check_traced_run(run, "seq", absent=("setup.import_s",))
    tm0 = run["obs"]["tm0"]
    assert spans.field_of(tm0, "module.bind", "count") == 2  # two buckets
    assert spans.field_of(tm0, "rnn.bucket_iter_build", "count") == 1
