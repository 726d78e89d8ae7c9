"""Weights and inputs of a run, made from ``--seed`` alone.

Weights are made on the device in one jitted call, in float32 (the type
they are served in); images are made on the host with numpy, since the
serving path takes numpy batches.  The same seed always gives the same
weights and images, on any backend the same ones up to the random
number generator's platform."""

from __future__ import annotations

import numpy as np

#: streams of ``numpy.random.default_rng([seed, stream])``
IMAGES, ORDER = 1, 2


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


def prng_key(seed: int):
    """A JAX key from a seed of up to 64 bits (``PRNGKey`` alone keeps
    only the low 32)."""
    import jax
    s = seed % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def make_params(spec, seed: int) -> dict:
    """``{node: {"w", "b"}, "head": {"w", "b"}}`` for ``spec``: He-normal
    conv weights (variance 2 / fan-in, so activations keep their scale
    through the ReLUs), N(0, 0.05^2) biases, an N(0, 1/C) head."""
    import jax
    import jax.numpy as jnp

    convs = spec.convs

    def gen(key):
        keys = jax.random.split(key, 2 * len(convs) + 2)
        p = {}
        for i, nd in enumerate(convs):
            fan_in = nd.k * nd.k * (nd.cin // nd.groups)
            w_shape = (nd.k, nd.k, nd.cin // nd.groups, nd.channels)
            p[nd.name] = {
                "w": jax.random.normal(keys[2 * i], w_shape, jnp.float32)
                * np.float32(np.sqrt(2.0 / fan_in)),
                "b": jax.random.normal(keys[2 * i + 1], (nd.channels,),
                                       jnp.float32) * np.float32(0.05)}
        if spec.classes:
            c = spec.nodes[-1].channels
            p["head"] = {
                "w": jax.random.normal(keys[-2], (c, spec.classes),
                                       jnp.float32)
                * np.float32(1.0 / np.sqrt(c)),
                "b": jax.random.normal(keys[-1], (spec.classes,),
                                       jnp.float32) * np.float32(0.05)}
        return p

    return jax.jit(gen)(prng_key(seed))


def make_pool(spec, n: int, seed: int) -> np.ndarray:
    """``n`` N(0, 1) float32 images of the network's input shape."""
    return rng(seed, IMAGES).standard_normal(
        (n,) + spec.input_shape, dtype=np.float32)
