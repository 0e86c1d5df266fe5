"""The drivers end to end, here on the CPU, on tiny configurations defined
in this file. Control flow and counts only: nothing timed here is a device
number."""

import pytest

from benchmark.lib import harness as hx
from benchmark.tests.util import run_driver

CANNED_TRACE = {"busy_s": 0.5, "busy_s_device0": 0.5, "window_s": 1.0,
                "idle_share_device0": 0.5, "collective_s_device0": 0.01,
                "device_ops": [["fusion.1", 0.5]],
                "idle_gaps": [["bench.fit", 0.1]], "devices": 1}


@pytest.fixture
def canned_trace(monkeypatch):
    """The CPU writes no /device:TPU plane: keep the profiler run, replace
    the reduction (tested on its own in test_trace.py)."""
    def reduce(self):
        self.result = dict(CANNED_TRACE)
        return self.result
    monkeypatch.setattr(hx.Tracer, "reduce", reduce)


def tiny_resnet():
    cfg = hx.load_json(hx.HERE, "configs", "resnet50-v2.json")
    cfg.update(image_shape=[3, 64, 64], num_classes=10,
               compute_dtype="float32")
    traffic = hx.load_json(hx.HERE, "traffic", "fit-resident-b256.json")
    traffic.update(batch_per_chip=2, slice_steps=2, warmup_cycle_steps=2,
                   min_slices=3, trace_steps=2, reference_check={"batch": 2})
    return cfg, traffic


def tiny_lstm():
    cfg = hx.load_json(hx.HERE, "configs", "lstm-ptb-large.json")
    cfg.update(num_hidden=16, num_embed=16, vocab_size=50, buckets=[4, 8])
    traffic = hx.load_json(hx.HERE, "traffic", "bucketed-ptb-b128.json")
    traffic.update(batch_size=4, length_mean=4, length_std=2,
                   batches_per_cycle=5, min_slices=3, trace_steps=5,
                   reference_check={"batch": 2, "seq_len": 4})
    return cfg, traffic


def check_window(run, unit):
    s, obs = run["summary"], run["obs"]
    assert run["correct"] and run["failed"] == 0
    assert len(s["rates"]) >= 3
    assert obs["units"] == obs["steps"] * unit
    # the end-to-end rate is all the work over all the time of the window
    rate = next(v for k, v in run["end_to_end"].items() if k != "setup_s")
    assert rate == obs["rate"] == pytest.approx(obs["units"] / obs["window_s"])
    assert obs["program_syncs"] == 0   # fit does not sync inside an epoch
    assert run["setup_s"] > 0 and run["obs"]["setup_compile_s"] > 0


def test_fit_driver_end_to_end():
    cfg, traffic = tiny_resnet()
    run = run_driver(cfg, traffic, builder_of="resnet50-v2", seconds=0.3)
    check_window(run, unit=2)
    assert set(run["end_to_end"]) == {"train_samples_per_s", "setup_s"}


def test_fit_driver_over_four_devices_checks_the_sharded_step(canned_trace):
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs four devices (conftest asks for them)")
    cfg, traffic = tiny_resnet()
    traffic["reference_check"] = {"batch": 8}
    run = run_driver(cfg, traffic, builder_of="resnet50-v2", seconds=0.3,
                     chips=4, trace=1)
    check_window(run, unit=8)
    mod = run["checked_module"]
    assert len(mod._context) == 4      # the reference check's own module
    assert run["correct"]              # sharded step against the reference


def test_bucketing_driver_traced_run_reads_every_seq_metric(canned_trace):
    cfg, traffic = tiny_lstm()
    run = run_driver(cfg, traffic, builder_of="lstm-ptb-large", seconds=0.3,
                     trace=1)
    assert run["correct"]  # includes the float32 reference check
    obs = run["obs"]
    assert obs["steps"] % 5 == 0 and obs["trace_slice"][0] == 5
    assert 0 < obs["pad_tokens"] < obs["all_tokens"]
    for name, mod in hx.layer_readers().items():
        if name.endswith(".seq") or name == "compile.setup_compile_s":
            assert mod.read(run) is not None, name
    switches = hx.layer_readers()[
        "dispatch.bucket_switches_per_step.seq"].read(run)
    assert switches > 0
