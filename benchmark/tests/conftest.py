"""Four virtual CPU devices, so that the four-chip path of the ``fit``
driver runs here; set before anything imports jax."""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
