"""The corpus and the query pool, made on the device from the run's seed.

A Gaussian mixture with `rows_per_component` rows per component on
average, as `chip_smoke.py` draws its SIFT1M-shaped data: centres scaled by
`cluster_scale`, unit noise around them.  The pool of held-out queries is
drawn from the same components.  One jitted call makes both, in float32,
so a run's set-up does not pass the corpus through the host.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative seed: the high word is folded in, so
    seeds above 2**32 do not collide with their low 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("n", "pool", "d", "components"))
def _mixture(key, scale, noise, *, n: int, pool: int, d: int,
             components: int):
    kc, kx, kxn, kq, kqn = jax.random.split(key, 5)
    centers = jax.random.normal(kc, (components, d), jnp.float32) * scale
    X = (centers[jax.random.randint(kx, (n,), 0, components)]
         + noise * jax.random.normal(kxn, (n, d), jnp.float32))
    Q = (centers[jax.random.randint(kq, (pool,), 0, components)]
         + noise * jax.random.normal(kqn, (pool, d), jnp.float32))
    return X, Q


def make_data(config: dict, n: int, pool: int, seed: int):
    """(corpus (n, d), query pool (pool, d)) float32 device arrays."""
    spec = config["data"]
    components = max(1, (n + pool) // int(spec["rows_per_component"]))
    X, Q = _mixture(seed_key(seed), jnp.float32(spec["cluster_scale"]),
                    jnp.float32(spec["noise"]), n=n, pool=pool,
                    d=int(config["d"]), components=components)
    return jax.block_until_ready((X, Q))
