"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices; smoke tests and benchmarks see the
plain 1-device CPU.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with GSPMD-propagated (``Auto``) axes.

    The partition rules (distributed/sharding.py) fix storage layouts and
    leave compute shardings to propagation; explicit axes (jax's default)
    would instead type every intermediate, and reject e.g. an embedding
    gather whose table and tokens both name the 'data' axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; (2, 16, 16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU tests (requires forced host device count)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


# ----------------------------------------------------------------------
# Multi-process (multi-host) launch
# ----------------------------------------------------------------------
def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int) -> None:
    """Join this process to a ``jax.distributed`` group.

    After this returns, ``jax.devices()`` is the GLOBAL device list (all
    hosts) while ``jax.local_devices()`` stays per-host — every mesh built
    from the global list is a multi-host mesh and every collective in the
    EP dispatch spans hosts.  Must run before any other jax call touches
    the backend."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def multiprocess_compute_supported() -> bool:
    """Whether the active backend can RUN multi-process computations.

    ``jax.distributed.initialize`` succeeds on CPU (coordination service +
    global device visibility work) but jit dispatch across processes does
    not ("Multiprocess computations aren't implemented on the CPU
    backend"), so CPU smoke launches must fall back to a single-process
    forced-device-count mesh after the coordination handshake."""
    return jax.default_backend() != "cpu" or jax.process_count() == 1


def make_ep_mesh(ep: int | None = None, axis: str = "model"):
    """1-D expert-parallel mesh over the global device list.

    ``ep=None`` uses every visible device (multi-host when
    ``init_distributed`` ran first).  The EP dispatch only needs the one
    named axis; serving meshes that also batch-shard should build a 2-D
    mesh via ``make_debug_mesh``/``make_production_mesh`` instead."""
    n = len(jax.devices()) if ep is None else ep
    if len(jax.devices()) % n:
        raise ValueError(
            f"ep={n} does not divide the {len(jax.devices())}-device mesh")
    return _mesh((n,), (axis,))
