"""Inputs made on the device from ``--seed``.

The seed is any whole number, often wider than 32 bits; it is
hashed into one threefry key, so every seed gives its own data and the
same seed the same data.  Samples are ``bit_depth``-bit integers,
uniform noise, so every subband carries full-range data.  A
configuration that states ``samples`` (an integer dtype such as
``"uint16"``) gets them as the product stores them: unsigned, in that
container.  Without it they are DC-level-shifted as JPEG 2000 does
before its transform and held in the configuration's ``dtype``.
"""
from __future__ import annotations

import numpy as np


def key(seed: int):
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def host_rng(seed: int) -> np.random.Generator:
    """The host-side generator (arrival order, samples) of a seed."""
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def samples_dtype(config: dict) -> np.dtype:
    """The dtype a configuration's samples are held in: ``samples``
    where it states one, else its ``dtype``."""
    return np.dtype(config.get("samples", config["dtype"]))


def integer_samples(config: dict) -> bool:
    return np.issubdtype(samples_dtype(config), np.integer)


def images(seed: int, n: int, shape, bit_depth: int,
           dtype="float32") -> tuple:
    """``n`` images of ``shape`` in ``dtype``, made in one jitted call:
    unsigned samples in ``[0, 2**bit_depth)`` for an integer ``dtype``,
    DC-level-shifted ones for a float ``dtype``; the same draws either
    way."""
    import jax
    dtype = np.dtype(dtype)
    integer = np.issubdtype(dtype, np.integer)
    if integer and (1 << bit_depth) - 1 > np.iinfo(dtype).max:
        raise ValueError(f"{bit_depth}-bit samples do not fit {dtype}")
    half = 1 << (bit_depth - 1)
    shift = 0 if integer else half

    def make(k):
        ks = jax.random.split(k, n)
        return tuple((jax.random.randint(ki, tuple(shape), 0, 2 * half)
                      - shift).astype(dtype) for ki in ks)

    return jax.jit(make)(key(seed))


def pyramid_shapes(shape, levels: int) -> list:
    """Leaf shapes of a pyramid of ``shape``: LL, then HL, LH, HH of
    each level, coarsest first."""
    *batch, h, w = shape
    out = [tuple(batch) + (h >> levels, w >> levels)]
    for lvl in range(levels, 0, -1):
        out += [tuple(batch) + (h >> lvl, w >> lvl)] * 3
    return out


def pyramids(seed: int, n: int, shape, levels: int, bit_depth: int) -> list:
    """``n`` coefficient pyramids of an image of ``shape``, as leaf
    lists (see :func:`pyramid_shapes`), made in one jitted call: the
    decoder's input, drawn from the seed like an image."""
    import jax
    import jax.numpy as jnp
    half = 1 << (bit_depth - 1)
    shapes = pyramid_shapes(shape, levels)

    def make(k):
        ks = jax.random.split(k, n * len(shapes))
        leaves = [(jax.random.randint(ki, s, 0, 2 * half) - half)
                  .astype(jnp.float32)
                  for ki, s in zip(ks, shapes * n)]
        return tuple(tuple(leaves[i * len(shapes):(i + 1) * len(shapes)])
                     for i in range(n))

    return [list(p) for p in jax.jit(make)(key(seed))]
