"""The benchmark's data: a seeded Gaussian mixture made on the device.

A copy of the program's synthetic generator, kept here so that a change to
the program's generator cannot move the yardstick.  Every array is made in
one jitted call from the run's seed.
"""
from __future__ import annotations

from functools import partial

import jax

# streams derived from one run seed; each use of randomness has its own
DATA, INIT, SOLVE = range(3)


def run_key(seed: int, stream: int) -> jax.Array:
    """The key of one stream of a run: ``--seed`` may exceed 32 bits."""
    return jax.random.fold_in(jax.random.key(seed), stream)


@partial(jax.jit, static_argnames=("n", "k", "d"))
def gaussian_mixture(key: jax.Array, n: int, k: int, d: int,
                     spread: float = 6.0, sigma: float = 1.0):
    """n points (f32) from k isotropic Gaussians with centers drawn from
    U[-spread, spread]^d -> (points (n, d), centers (k, d), component (n,))."""
    kc, kx, ka = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (k, d), minval=-spread, maxval=spread)
    comp = jax.random.randint(ka, (n,), 0, k)
    noise = jax.random.normal(kx, (n, d)) * sigma
    return centers[comp] + noise, centers, comp


def make_points(seed: int, config: dict) -> jax.Array:
    """The configuration's data set, on the device, from the run's seed."""
    mix = config["data"]
    points, _, _ = gaussian_mixture(
        run_key(seed, DATA), int(config["n_points"]),
        int(mix["components"]), int(config["dim"]),
        float(mix["spread"]), float(mix["sigma"]))
    return jax.block_until_ready(points)
