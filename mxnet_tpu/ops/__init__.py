"""Operator registry and definitions (analogue of ``src/operator/``)."""

from . import registry
from .registry import OpDef, OpMode, Param, register, get, exists, list_ops

# Importing the defs modules populates the registry.
from . import defs_elemwise  # noqa: F401
from . import defs_tensor  # noqa: F401
from . import defs_reduce  # noqa: F401
from . import defs_nn  # noqa: F401
from . import defs_transformer  # noqa: F401
from . import defs_random  # noqa: F401
from . import defs_optimizer  # noqa: F401
from . import defs_contrib  # noqa: F401
from . import defs_rnn  # noqa: F401
from . import defs_vision  # noqa: F401
from . import defs_custom  # noqa: F401
