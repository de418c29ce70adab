"""The synthetic corpus: a Gaussian mixture in a low-dimensional latent
space, lifted to the stored width by a fixed random projection, plus
isotropic noise in every stored dimension.

Real descriptor sets such as SIFT have an intrinsic dimension far below
their width, so a query's neighbours are local and an inverted-file index
has to probe several lists to find them.  The mixture keeps that: its
blobs overlap in the latent space, and the noise keeps every stored
dimension in use.

The mixture's structure (blob centres and the projection) comes from the
configuration's ``structure_seed``; ``--seed`` draws the rows and the
queries.  Every seed therefore samples the same distribution, so runs
with different seeds do the same amount of work.  Everything is made on
the device in one jitted call, in the dtype the index stores.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number, also one wider than 32 bits."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                 (seed >> 64) & 0xFFFFFFFF):
        key = jax.random.fold_in(key, word)
    return key


@partial(jax.jit, static_argnames=("rows", "queries", "dim", "blobs",
                                   "latent_dim", "center_spread", "noise",
                                   "dtype"))
def _sample(structure, key, *, rows, queries, dim, blobs, latent_dim,
            center_spread, noise, dtype):
    ks, kp = jax.random.split(structure)
    centers = center_spread * jax.random.normal(ks, (blobs, latent_dim))
    # columns of unit expected norm: a latent unit step moves the stored
    # vector by about one unit
    lift = jax.random.normal(kp, (latent_dim, dim)) / jnp.sqrt(latent_dim)

    def draw(k, n):
        kb, kz, ke = jax.random.split(k, 3)
        label = jax.random.randint(kb, (n,), 0, blobs)
        z = centers[label] + jax.random.normal(kz, (n, latent_dim))
        x = jnp.matmul(z, lift, precision="highest")
        return (x + noise * jax.random.normal(ke, (n, dim))).astype(dtype)

    kb, kq = jax.random.split(key)
    return draw(kb, rows), draw(kq, queries)


def make(data: dict, mixture: dict, seed: int):
    """``(base, queries)`` device arrays for one run: ``data`` holds
    ``rows``, ``queries``, ``dim`` and ``dtype``; ``mixture`` holds
    ``structure_seed``, ``blobs``, ``latent_dim``, ``center_spread`` and
    ``noise``."""
    return _sample(seed_key(int(mixture["structure_seed"])), seed_key(seed),
                   rows=int(data["rows"]), queries=int(data["queries"]),
                   dim=int(data["dim"]), blobs=int(mixture["blobs"]),
                   latent_dim=int(mixture["latent_dim"]),
                   center_spread=float(mixture["center_spread"]),
                   noise=float(mixture["noise"]),
                   dtype=jnp.dtype(data["dtype"]).name)
