"""Subprocess driver for the persistent AOT-cache contract test.

Binds the bench-model family (model-zoo resnet18 at a small smoke shape)
in a FRESH process against a cache another process populated
(tools/aot_warm.py), exercises every steady-state program — train-step
gradients, the fused train update, eval forward — and prints the compile
counters as one JSON line. The parent asserts ``executor.jit_compile == 0``
and ``aot.cache_hit > 0``: a warm process must never touch XLA.

Run by tests/test_aot_cache.py with JAX_PLATFORMS=cpu (the established
subprocess pattern: children of the pytest process stay on the host
backend).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models
import mxnet_tpu.telemetry as tm


def main():
    batch, image = 2, (3, 32, 32)
    sym = models.resnet(num_classes=10, num_layers=18,
                        image_shape=",".join(map(str, image)))
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[mx.io.DataDesc("data", (batch,) + image)],
             label_shapes=[mx.io.DataDesc("softmax_label", (batch,))])
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    rng = np.random.RandomState(0)
    b = mx.io.DataBatch(
        data=[mx.nd.array(rng.uniform(-1, 1, (batch,) + image)
                          .astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 10, (batch,))
                           .astype(np.float32))],
    )
    # train-step program: gradients read before update() materialize the
    # fused fwd+bwd (then the per-param update path consumes them)
    mod.forward_backward(b)
    grad = mod._exec_group._exec.grad_dict["fc1_weight"].asnumpy()
    mod.update()
    # fused train-update program (the steady-state training executable)
    mod.forward_backward(b)
    mod.update()
    # eval forward program
    mod.forward(b, is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    print(json.dumps({
        "jit_compile": tm.counter("executor.jit_compile").value,
        "cache_hit": tm.counter("aot.cache_hit").value,
        "cache_miss": tm.counter("aot.cache_miss").value,
        "deserialize_error": tm.counter("aot.deserialize_error").value,
        "grad_norm": float(np.abs(grad).sum()),
        "out_shape": list(out.shape),
    }))


if __name__ == "__main__":
    main()
