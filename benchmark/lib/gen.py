"""Weights and inputs made on the device from the seed, in one jitted call.

The seed is a traced argument, so every seed runs the same compiled
program (one entry in the compile cache). Random leaves are slices of two
flat draws, one normal and one uniform, which keeps the program small: a
draw per leaf would compile some hundreds of generators.
"""

from __future__ import annotations

import numpy as np


def seed_word(seed):
    """``--seed`` folded into 32 bits (the driver's seeds pass 2**31)."""
    return np.uint32(int(seed) & 0xFFFFFFFF)


def make_leaves(jax, seed, specs, out_shardings=None):
    """{name: array} for ``specs`` = [(name, shape, dtype, kind, scale,
    offset)]; a leaf is ``draw * scale + offset``.

    ``kind`` names the draw: "normal" (standard), "uniform" (-1..1),
    "uniform01" (0..1), "randint" (whole numbers 0..scale-1, kept in
    ``dtype``) or "const" (no draw: the leaf is ``scale + offset``).
    """
    import jax.numpy as jnp

    sizes = {k: 0 for k in ("normal", "uniform", "uniform01", "randint")}
    for _, shape, _, kind, _, _ in specs:
        if kind in sizes:
            sizes[kind] += int(np.prod(shape))

    def build(word):
        key = jax.random.fold_in(jax.random.key(0), word)
        k_n, k_u, k_01, k_i = jax.random.split(key, 4)
        flat = {
            "normal": jax.random.normal(k_n, (sizes["normal"],), jnp.float32),
            "uniform": jax.random.uniform(k_u, (sizes["uniform"],),
                                          jnp.float32, -1.0, 1.0),
            "uniform01": jax.random.uniform(k_01, (sizes["uniform01"],),
                                            jnp.float32),
            "randint": jax.random.uniform(k_i, (sizes["randint"],),
                                          jnp.float32),
        }
        at = dict.fromkeys(flat, 0)
        out = {}
        for name, shape, dtype, kind, scale, offset in specs:
            if kind == "const":
                out[name] = jnp.full(shape, scale + offset, dtype)
                continue
            n = int(np.prod(shape))
            piece = flat[kind][at[kind]:at[kind] + n].reshape(shape)
            at[kind] += n
            if kind == "randint":
                piece = jnp.floor(piece * scale) + offset
            else:
                piece = piece * scale + offset
            out[name] = piece.astype(dtype)
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(seed_word(seed))
