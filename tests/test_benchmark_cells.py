"""Every cell of ``BENCHMARK.json`` stays buildable: what tier-1 keeps alive
of the benchmark the driver measures (``benchmark/run.py``; its own tests,
``benchmark/tests``, are not part of tier-1).

For each cell the configuration and traffic files resolve as ``run.py``
resolves them, the configuration's builder gives its Symbol at the file's
published widths and depth (the parameters add up to the file's count),
and shape and type inference at the traffic's shapes succeed and give the
label and the output the driver feeds and reads. Inference only: nothing is
bound, nothing compiled.
"""

import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from benchmark.lib import harness as hx

with open(os.path.join(hx.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _fed(cell, config, traffic, builder):
    """[(symbol, {input: shape})] of every program the cell's driver binds."""
    if traffic["driver"] == "fit":
        batch = traffic["batch_per_chip"] * cell["chips"]
        return [(builder.symbol(config, mx),
                 builder.input_shapes(config, batch))]
    assert traffic["driver"] == "bucketing_fit"
    gen_sym, state_names = builder.sym_gen(config, mx)
    fed = []
    for key in config["buckets"]:
        shapes = builder.input_shapes(config, traffic["batch_size"], key)
        shapes.update({n: (traffic["batch_size"], config["num_hidden"])
                       for n in state_names})
        fed.append((gen_sym(key)[0], shapes))
    return fed


def test_the_benchmark_has_its_fourteen_cells():
    assert len(CELLS) == len(set(CELLS)) == 14


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_builds_and_infers_the_shapes_its_driver_feeds(name):
    _, cell, entry, config, traffic = hx.find_cell(name)
    assert config["name"] == entry["name"] == cell["config"]
    assert traffic["name"] == cell["traffic"]
    assert os.path.isfile(os.path.join(
        hx.HERE, "drivers", traffic["driver"] + ".py"))
    hx.config_module("reference", config["name"])    # the check's other side
    builder = hx.config_module("configs", config["name"])
    for sym, shapes in _fed(cell, config, traffic, builder):
        arg_shapes, out_shapes, _ = sym.infer_shape(**shapes)
        args = dict(zip(sym.list_arguments(), arg_shapes))
        assert all(args[n] == tuple(s) for n, s in shapes.items())
        rows = int(np.prod(shapes["softmax_label"]))
        assert int(np.prod(out_shapes[0][:-1])) == rows    # a row a label
        # (to a thousandth: the two oldest files carry the published count,
        # which leaves out the LSTM's second bias and two of ResNet's norms)
        assert sum(int(np.prod(s)) for n, s in args.items()
                   if n not in shapes) == pytest.approx(
                       config["parameters"], rel=1e-3)
        arg_types, out_types, _ = sym.infer_type(
            **{n: "float32" for n in shapes if n != "data"},
            data=config["compute_dtype"] if traffic["driver"] == "fit"
            else "float32")
        assert None not in arg_types and None not in out_types
