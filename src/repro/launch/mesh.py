"""Host mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state: a caller can set ``XLA_FLAGS`` before the first call.

The federated client axes of a mesh (everything except ``model``) are
what :mod:`repro.federated.simulation` shards the flat server path
over — see :func:`client_sharding` and ``FedSimConfig(mesh=...)``.
"""
from __future__ import annotations

import jax

from repro.utils.sharding import ShardSpec


def make_host_mesh(model: int = 1, devices=None):
    """Tiny mesh over the real local devices (tests / examples), or over
    the ``devices`` given (a benchmark point on a subset of the host).

    Raises a clear ``ValueError`` when ``model`` does not divide the
    device count (``model > n`` used to silently produce a ``data = 0``
    axis and an opaque mesh error downstream).
    """
    n = len(jax.devices() if devices is None else devices)
    if model < 1 or model > n or n % model:
        raise ValueError(
            f"make_host_mesh(model={model}): need 1 <= model <= {n} with "
            f"model dividing the local device count ({n}); set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=<n> before importing "
            f"jax to widen a CPU host."
        )
    # every axis Auto: the compiler places what the program leaves open
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices)


def client_axes(mesh) -> tuple:
    """The federated client axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def client_sharding(mesh) -> ShardSpec:
    """:class:`ShardSpec` over ``mesh``'s client axes (major-to-minor)."""
    axes = client_axes(mesh)
    if not axes:
        raise ValueError(
            f"mesh {mesh.axis_names} has no client axes (only 'model')"
        )
    return ShardSpec(axes=axes, sizes=tuple(mesh.shape[a] for a in axes))
