"""Inputs made from ``--seed``: Gaussian rows generated on the device in
chunks of one fixed shape (one compiled program, small device memory)
and brought to the host, where the program under test takes them."""

from __future__ import annotations

import functools

import numpy as np

GEN_ROWS = 1 << 16


def seed_words(seed: int, *path) -> tuple:
    """Two 31-bit words derived from a seed of any size and a path of
    small non-negative ints (one stream per purpose)."""
    ss = np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *path])
    a, b = ss.generate_state(2)
    return int(a) & 0x7FFFFFFF, int(b) & 0x7FFFFFFF


def host_rng(seed: int, *path) -> np.random.Generator:
    """A numpy generator for the seed and path."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & ((1 << 64) - 1), *path]))


@functools.lru_cache(maxsize=None)
def _gen_fn(rows: int, d: int, unit: bool):
    import jax
    import jax.numpy as jnp

    def gen(w0, w1, chunk):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(w0), w1),
                                 chunk)
        x = jax.random.normal(key, (rows, d), jnp.float32)
        if unit:
            x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        return x

    return jax.jit(gen)


def gaussian_rows(seed: int, stream: int, n: int, d: int, *,
                  unit: bool = False) -> np.ndarray:
    """[n, d] float32 rows with i.i.d. standard normal entries (or random
    unit vectors, ``unit``), the same for the same (seed, stream)."""
    w0, w1 = seed_words(seed, stream)
    rows = min(GEN_ROWS, max(8, -(-n // 8) * 8))
    fn = _gen_fn(rows, d, unit)
    out = np.empty((n, d), np.float32)
    for c, start in enumerate(range(0, n, rows)):
        part = np.asarray(fn(w0, w1, c))
        out[start:start + rows] = part[:n - start]
    return out

