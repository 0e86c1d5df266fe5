"""What the Pallas kernel families share (``grouped_matmul``,
``flash_attention``, ``gated_delta_kernels``, ``causal_conv_kernels``,
``rotary_kernels``, ``row_sum_kernels``) and
none of them owns: what the program knows of its device (the VMEM of the one
TPU the process holds: every family's rule reads it), Pallas itself, imported
when a kernel is first traced, and the store that keeps a traced kernel
beside jax's compilation cache, so that a later process pays for neither.
"""

from __future__ import annotations

import functools
import hashlib
import os
import sys
from typing import Optional

import jax

# VMEM of one TensorCore by ``device_kind`` (jax 0.9.0's own table,
# ``jax._src.pallas.mosaic.tpu_info``); a kind not listed gets no kernel.
_VMEM_BYTES = {
    "TPU v2": 16 << 20, "TPU v3": 16 << 20, "TPU v4 lite": 16 << 20,
    "TPU v4": 16 << 20, "TPU v5 lite": 128 << 20, "TPU v5e": 128 << 20,
    "TPU v5": 64 << 20, "TPU v5p": 64 << 20, "TPU v6 lite": 128 << 20,
    "TPU v6e": 128 << 20, "TPU7x": 64 << 20,
}


def attached_vmem_bytes() -> Optional[int]:
    """VMEM of one core of the one TPU this process holds, from its device
    kind. None where it holds none (the CPU: nothing lowers for a TPU) or
    several: a program over several chips is partitioned by XLA, which
    cannot partition a Mosaic call, so until the kernels sit in a
    ``shard_map`` (ROADMAP Reach B2) every rule gives such a process its
    plain form (``ragged_dot``, the ``jax.numpy`` blocks)."""
    if jax.default_backend() != "tpu":
        return None
    devices = jax.devices()
    return _VMEM_BYTES.get(devices[0].device_kind) if len(devices) == 1 \
        else None


@functools.cache
def _pallas():
    """``(pallas, pallas.tpu)``, imported when a kernel is first traced:
    1.7 s on the v5e's host (PERF.md section 6, PR 30) that a process with
    no kernel to run on a TPU, or with its kernels in the store below,
    never pays."""
    from jax.experimental import pallas
    from jax.experimental.pallas import tpu

    return pallas, tpu


# --- kernels kept across processes -------------------------------------------
_EXPORTED = {}  # key -> jax.export.Exported, this process's


def _kernel_cache_dir():
    """Where traced kernels are kept: a directory of jax's persistent
    compilation cache, so they live and move with the executables
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``); None
    where that cache is off."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    root = jax.config.jax_compilation_cache_dir
    return os.path.join(root, "mxnet_tpu-kernels") if root else None


@functools.cache
def _source_digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _kernel(impl, operands, **static):
    """``impl(*operands, **static)``, a jitted Pallas kernel of any of the
    families, traced once a cache directory and not once a process. Tracing
    the six kernels of a ``MoE`` layer, lowering them to Mosaic
    and importing Pallas to do so cost 2.8 s of every process's set-up on
    the v5e's host (PERF.md section 6, PR 30); ``jax.export`` keeps the
    lowered module (17 KB a kernel), and a later process reads it back and
    calls it: no trace, no Pallas. The key holds the text of the kernel's
    module, jax's version, the device kind, the kernel, its static
    arguments and the operands' shapes and types. A directory that cannot
    be written only loses the saving."""
    directory = _kernel_cache_dir()
    if directory is None or static["interpret"]:
        return impl(*operands, **static)
    from jax import export

    leaves, tree = jax.tree.flatten(operands)
    key = hashlib.sha256(repr((
        _source_digest(sys.modules[impl.__module__].__file__),
        jax.__version__, jax.devices()[0].device_kind, impl.__name__,
        sorted(static.items()), str(tree),
        [(a.shape, str(a.dtype)) for a in leaves])).encode()).hexdigest()
    exported = _EXPORTED.get(key)
    path = os.path.join(directory, key)
    if exported is None:
        try:
            with open(path, "rb") as f:
                exported = export.deserialize(bytearray(f.read()))
        except (OSError, ValueError):
            exported = export.export(
                jax.jit(lambda *leaves: impl(*jax.tree.unflatten(tree, leaves),
                                             **static)),
                platforms=("tpu",))(
                    *[jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in leaves])
            try:
                os.makedirs(directory, exist_ok=True)
                with open(f"{path}.{os.getpid()}", "wb") as f:
                    f.write(exported.serialize())
                os.replace(f"{path}.{os.getpid()}", path)
            except OSError:
                pass
        _EXPORTED[key] = exported
    return exported.call(*leaves)
