"""``BENCHMARK.json`` against the files it names: every cell resolves, every
per-layer metric has a reader that agrees with its entry, and every
``moves`` is an end-to-end metric that each of its cells reports."""

import json
import os

from benchmark.lib import flops, sentences
from benchmark.lib import harness as hx

BENCH = hx.load_json(hx.ROOT, "BENCHMARK.json")


def test_every_cell_resolves_to_files_that_exist():
    assert BENCH["paths"] == ["benchmark"]
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for cell in BENCH["workloads"]:
        _, _, entry, config, traffic = hx.find_cell(cell["name"])
        assert config["name"] == cell["config"] == entry["name"]
        assert traffic["name"] == cell["traffic"]
        assert config["reduced"] == entry["reduced"]
        assert config["source"] == entry["source"]
        for kind in ("configs", "reference"):
            assert os.path.exists(os.path.join(
                hx.HERE, kind, cell["config"] + ".py"))
        assert os.path.exists(os.path.join(
            hx.HERE, "drivers", traffic["driver"] + ".py"))
        for var, val in (traffic.get("env") or {}).items():
            assert str(val).lower() != "auto", (cell["name"], var)


def test_every_metric_has_its_cells_and_its_reader():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    readers = hx.layer_readers()
    cells = [w["name"] for w in BENCH["workloads"]]
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in cells:
        assert len(hx.metrics_of(BENCH, cell, "end_to_end")) >= 2
        assert hx.metrics_of(BENCH, cell, "per_layer")
    for m in BENCH["per_layer"]:
        mod = readers[m["name"]]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.BETTER, mod.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["better"], m["source"])
        for cell in m.get("workloads", cells):
            assert m["moves"] in hx.metrics_of(BENCH, cell, "end_to_end"), (
                m["name"], cell)


def test_peaks_know_the_v5e_and_refuse_the_rest():
    assert hx.peaks_of("TPU v5 lite")["bf16_tflops"] == 197
    try:
        hx.peaks_of("cpu")
    except hx.BenchError:
        return
    raise AssertionError("an unknown device must be an error")


def test_flops_arithmetic_matches_the_published_figures():
    resnet = hx.load_json(hx.HERE, "configs", "resnet50-v2.json")
    lstm = hx.load_json(hx.HERE, "configs", "lstm-ptb-large.json")
    # ResNet-50 at 224 is "4.1 GFLOPs" counted in multiply-adds
    assert 4.0e9 < flops.resnet_forward_macs(resnet) < 4.2e9
    builder = hx.config_module("configs", "lstm-ptb-large")
    assert builder.train_flops_per_unit(lstm) == 3 * 2 * (
        2 * 4 * 1500 * 3000 + 1500 * 10000)


def test_every_seed_gets_the_same_lengths_in_another_order():
    kw = dict(buckets=[10, 20, 30, 40, 50, 60], mean=21, std=10, batches=12,
              batch_size=8, vocab_size=100, zipf_a=1.0)
    a, b = sentences.make(1, **kw), sentences.make(2**31 + 7, **kw)
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert a != b and sentences.make(1, **kw) == a
    assert all(1 <= t < 100 for s in a for t in s)
    per_bucket, _ = sentences.bucket_batches(kw["buckets"], 21, 10, 12)
    assert sum(per_bucket) == 12 and min(per_bucket) >= 1


def test_result_line_of_the_contract(capsys):
    hx.emit({"correct": True})
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == {
        "correct": True}
