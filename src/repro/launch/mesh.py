"""Production mesh construction.

Called as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax init).
"""

from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (the installed JAX defaults
    to Explicit, which the sharding rules here do not use)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips.  Multi-pod: 2 x (16, 16) = 512.

    The 'pod' axis composes with 'data' for batch/gradient sharding; the
    'model' axis carries TP/EP/SP.  Scaling beyond 2 pods is increasing
    the pod extent — no code changes.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (smoke tests / examples)."""
    n = jax.device_count()
    return auto_mesh((n // model, model), ("data", "model"))


def make_conv_mesh(data: int, spatial: int):
    """The conv mesh (DESIGN.md §6): images over 'data', output H-strips
    over 'model' — the axes ``distributed.sharding.CONV_RULES`` maps the
    conv's logical axes onto.  Uses the first ``data * spatial`` local
    devices (force host CPU devices with ``launch.hostdevices`` first)."""
    import numpy as np
    ndev = data * spatial
    if ndev > jax.device_count():
        raise ValueError(
            f"need {ndev} devices, have {jax.device_count()} — force "
            f"host CPU devices before the first jax import "
            f"(launch.hostdevices)")
    devs = np.array(jax.devices()[:ndev]).reshape(data, spatial)
    return jax.sharding.Mesh(devs, ("data", "model"))
