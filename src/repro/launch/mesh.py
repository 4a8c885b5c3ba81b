"""Production mesh builders + jax.distributed initialisation helpers.

FUNCTIONS (never module-level constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialisation, and `initialize_multihost` must configure the CPU
collectives implementation before the backend comes up.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

# the flag construction and device validation live in repro.util.env
# (shared with benchmark/smoke subprocess children); re-exported here
# because this module has always been their import point
from repro.util.env import device_count_flag, require_devices  # noqa: F401


def _make_mesh(shape, axes):
    """`jax.make_mesh` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = 1
    for s in shape:
        n *= s
    require_devices(n)
    return _make_mesh(shape, axes)


# --------------------------------------------------------------------------
# multi-process (jax.distributed)
# --------------------------------------------------------------------------

def distributed_initialized() -> bool:
    """True once `jax.distributed.initialize` has run in this process."""
    try:
        from jax._src import distributed
        return distributed.global_state.coordinator_address is not None
    except Exception:            # private API moved — assume not up
        return False


def initialize_multihost(*, coordinator_address: str, num_processes: int,
                         process_id: int,
                         local_devices: Optional[Sequence[int]] = None,
                         expect_local_devices: Optional[int] = None
                         ) -> None:
    """Stand up this process's membership in a jax.distributed cluster.

    Call BEFORE anything queries jax devices: on CPU the collectives
    implementation (gloo) must be configured before the backend
    initialises, and forcing host device counts (see
    `device_count_flag`) only works pre-initialisation. Process 0 at
    ``coordinator_address`` doubles as the coordination service — a dev
    cluster is just N local processes pointed at one localhost port
    (see scripts/smoke_multihost.py).

    ``expect_local_devices`` validates, post-init, that this process
    sees that many devices of its own (the shared `require_devices`
    helper, so the remedy message matches `make_host_mesh`'s).
    """
    if distributed_initialized():
        return
    try:
        # CPU backends cross processes via gloo; harmless elsewhere
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass                     # jax without the option (gpu/tpu-only)
    kwargs = {}
    if local_devices is not None:
        kwargs["local_device_ids"] = list(local_devices)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    if expect_local_devices is not None:
        require_devices(expect_local_devices, local=True)


def ensure_multihost_initialized(config) -> None:
    """Initialise jax.distributed from a `FitConfig`'s coordinator
    fields (no-op when they are unset or the cluster is already up)."""
    if getattr(config, "coordinator_address", None) is None:
        return
    initialize_multihost(coordinator_address=config.coordinator_address,
                         num_processes=config.num_processes,
                         process_id=config.process_id)


def make_multihost_mesh(data_axes=("data",)):
    """One flat data axis over EVERY device of EVERY process.

    The multihost engine row-shards points over this mesh and keeps the
    cluster stats replicated; with one process this is exactly the mesh
    engine's layout, which is what makes the two bit-identical there.
    """
    data_axes = tuple(data_axes)
    if len(data_axes) != 1:
        raise ValueError(
            f"make_multihost_mesh builds one flat data axis; got "
            f"data_axes={data_axes!r} (pass a mesh to MultiHostEngine "
            f"for multi-axis layouts)")
    return _make_mesh((jax.device_count(),), data_axes)
