"""Everything random in a run comes from ``--seed`` through these two
functions.  Seeds may exceed 32 bits; both spread the whole integer over
their state, so large seeds that differ only in their high bits differ."""
from __future__ import annotations

import numpy as np


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A NumPy generator for host-side draws (traffic, samples)."""
    return np.random.default_rng(np.random.SeedSequence([seed, *salt]))


def jax_key(seed: int, *salt: int):
    """A JAX threefry key for device-side draws (weights, activations)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed, *salt]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")
