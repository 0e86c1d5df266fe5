"""Device contexts.

Reference: ``include/mxnet/base.h:101-318`` (``Context{kCPU,kGPU,...}``) and
``python/mxnet/context.py``. Here a Context names a jax device: ``cpu(i)``
maps to the i-th CPU device, ``tpu(i)`` to the i-th TPU chip. ``gpu(i)`` is
accepted as an alias for the i-th accelerator so reference scripts keep
running unmodified on TPU machines.
"""

from __future__ import annotations

import threading

from .base import MXNetError


class Context:
    """A device context. Thread-local default stack like the reference."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # --- jax integration -------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device.

        ``cpu`` → jax CPU backend devices. ``tpu``/``gpu`` → the default
        (accelerator) backend's devices; on a TPU machine ``gpu(i)`` therefore
        lands on TPU chip ``i``, which is exactly the portability the
        reference scripts need. When the default backend IS the CPU (no
        accelerator found, or ``JAX_PLATFORMS=cpu``) an accelerator context
        raises instead of quietly computing on the host.
        """
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            devs = jax.devices("cpu")
        else:
            devs = jax.devices()  # default backend: tpu when present
            if devs[0].platform == "cpu":
                raise MXNetError(
                    f"{self}: no accelerator — the default jax backend is "
                    f"'cpu' ({len(devs)} device(s)); use mx.cpu() to run "
                    "on the host"
                )
        if jax.process_count() > 1:
            # multi-host: device ids index THIS process's devices (the
            # reference's dev_id is per-worker); the global list would
            # resolve rank>0 contexts to other hosts' devices
            devs = [d for d in devs if d.process_index == jax.process_index()]
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self} out of range: backend has {len(devs)} devices"
            )
        return devs[self.device_id]

    def empty_cache(self):
        # PJRT owns the allocator; nothing to do. Kept for API parity with
        # the reference's pooled storage manager release.
        return None

    def memory_stats(self):
        """Device memory statistics from the PJRT allocator — the storage
        manager's stats surface (reference GPUPooledStorageManager pool
        accounting). Keys are backend-defined (e.g. bytes_in_use,
        peak_bytes_in_use); {} when the backend doesn't report."""
        dev = self.jax_device()  # invalid contexts raise, as elsewhere
        try:
            return dict(dev.memory_stats() or {})
        except (AttributeError, NotImplementedError):
            return {}  # backend doesn't report stats


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cppu_pinned" if False else "cpu_pinned", device_id)


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def is_tpu(ctx=None):
    """True when ``ctx`` (default: the default jax backend) is a TPU chip.

    The one device test behind the TPU-only choice, NHWC lowering
    (``ops/layout.py``). A context that cannot resolve raises rather than
    answering "no": a chip that failed to initialise must not silently
    select the host code paths."""
    import jax

    dev = jax.devices()[0] if ctx is None else ctx.jax_device()
    return dev.platform == "tpu"


def num_gpus():
    """Number of accelerator devices visible (TPU chips on a TPU host)."""
    import jax

    devs = jax.devices()
    if devs and devs[0].platform == "cpu":
        return 0
    return len(devs)
