"""Seeded synthetic scientific fields, made on the device.

The same formula as the repository's ``synth_field`` (four octaves, each a
sum of one sine per axis, plus Gaussian noise of standard deviation 0.02),
written in ``jnp`` so that a Hurricane-sized field is made by one jitted call
on the device instead of seconds of host numpy.  The octave phases and
frequencies come from a numpy generator seeded by ``(seed, dataset, field)``;
the noise comes from ``jax.random``.  The same seed gives the same field,
bit for bit, in every process.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

OCTAVES = 4
NOISE_STD = 0.02


def field_params(dataset: str, field: int, seed: int, ndim: int):
    """Phases ``(OCTAVES, ndim)``, frequencies ``(OCTAVES,)`` and the noise
    key's seed of one field."""
    rng = np.random.default_rng(
        [seed % (1 << 64), zlib.crc32(dataset.encode()), field])
    phases = rng.uniform(0, 2 * np.pi, size=(OCTAVES, ndim)).astype(np.float32)
    freqs = np.array([rng.uniform(1.5, 4.0) * 2.0 ** k
                      for k in range(1, OCTAVES + 1)], np.float32)
    return phases, freqs, int(rng.integers(0, 2 ** 31))


@functools.partial(jax.jit, static_argnums=0)
def _make(dims: tuple[int, ...], phases, freqs, key):
    out = jnp.zeros(dims, jnp.float32)
    for k in range(OCTAVES):
        wave = jnp.zeros(dims, jnp.float32)
        for a, d in enumerate(dims):
            g = jnp.linspace(0, 1, d, dtype=jnp.float32)
            line = jnp.sin(2 * np.pi * freqs[k] * g + phases[k, a])
            wave = wave + line.reshape([-1 if i == a else 1
                                        for i in range(len(dims))])
        out = out + wave / (2.0 ** (k + 1))
    return out + NOISE_STD * jax.random.normal(key, dims, jnp.float32)


def make_field(dataset: str, field: int, dims, seed: int) -> jax.Array:
    """One field of ``dims`` float32 values on the default device."""
    phases, freqs, k = field_params(dataset, field, seed, len(dims))
    return _make(tuple(int(d) for d in dims), jnp.asarray(phases),
                 jnp.asarray(freqs), jax.random.PRNGKey(k))
