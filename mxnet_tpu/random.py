"""Global PRNG state for imperative ops.

Reference: ``mx.random.seed`` (``python/mxnet/random.py``) seeding the
per-device mshadow PRNGs via the ResourceManager (``src/resource.cc:66-120``).
Here there is one jax PRNG key chain; every stochastic imperative op splits a
fresh key off it, so ``mx.random.seed(n)`` makes imperative sampling
deterministic. Executors fold their own per-step counters into a key derived
from this seed at bind time.
"""

from __future__ import annotations

import threading

_state = threading.local()
_DEFAULT_SEED = 0


def seed(seed_state: int):
    """Seed the global generator (reference: python/mxnet/random.py:seed)."""
    import jax

    _state.key = jax.random.PRNGKey(int(seed_state))


def _get_key():
    import jax

    if not hasattr(_state, "key"):
        _state.key = jax.random.PRNGKey(_DEFAULT_SEED)
    return _state.key


def next_key():
    """Split and return a fresh subkey for one sampling call."""
    import jax

    key = _get_key()
    _state.key, sub = jax.random.split(key)
    return sub


def get_state():
    """The raw key data of the global generator as a list of ints —
    JSON-serializable for checkpoint manifests."""
    import jax
    import numpy as np

    key = _get_key()
    return [int(x) for x in np.asarray(jax.random.key_data(key)).ravel()]


def set_state(state):
    """Restore a key captured by :func:`get_state` (checkpoint resume)."""
    import jax.numpy as jnp
    import numpy as np

    _state.key = jnp.asarray(np.asarray(state, dtype=np.uint32))
