"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls these.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``data`` carries DP (+FSDP param storage), ``model`` carries TP/EP,
    ``pod`` is an outer pure-DP axis (gradient reduction crosses pods over
    DCN; params are stored FSDP *within* a pod so weight all-gathers stay on
    ICI — DESIGN.md §7).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel),
        ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )


def make_sim_multihost_mesh(num_hosts: int, model_parallel: int = 1):
    """Mesh with an explicit outer ``host`` DP axis for the simulated
    multi-host lane (``--hosts``, DESIGN.md §16).

    Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so the
    CPU runtime exposes enough devices; each host owns a contiguous device
    block, matching the contiguous rank-block partition `ShardedWindow`
    uses, so host ``h``'s admitted shard lands on host ``h``'s devices.
    """
    n = jax.device_count()
    if num_hosts < 1 or n % (num_hosts * model_parallel) != 0:
        raise ValueError(
            f"device count {n} not divisible by hosts={num_hosts} "
            f"x model_parallel={model_parallel}"
        )
    return jax.make_mesh(
        (num_hosts, n // (num_hosts * model_parallel), model_parallel),
        ("host", "data", "model"),
        axis_types=(AxisType.Auto,) * 3,
    )


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "host", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1) if hasattr(mesh.shape, "get") else mesh.shape["model"]
