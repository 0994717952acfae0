"""The distributed + kernel layers' one seam onto the jax API.

The repo targets the installed jax (0.9: ``jax.shard_map`` with
``check_vma``, mesh ``axis_types``, ``pltpu.CompilerParams``).  Repo code
and tests go through these wrappers instead of calling jax directly, so an
API move touches this module only.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name):
    """Static size of a mesh axis inside ``shard_map``."""
    return jax.lax.axis_size(axis_name)


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_num_devices(mesh) -> int:
    """Total device count of a mesh (``mesh.size``; kept here so sharding
    callers have a single seam if the Mesh API drifts)."""
    return int(mesh.size)


def mesh_from_devices(devices, axis: str = "batch"):
    """1-D mesh over an explicit device list (elastic shrink: survivors only).

    ``make_mesh`` always spans the default device order; after a host loss the
    new world is an arbitrary subset, so the Mesh is built directly."""
    import numpy as np
    devs = np.asarray(list(devices), dtype=object)
    return jax.sharding.Mesh(devs, (axis,),
                             axis_types=(jax.sharding.AxisType.Auto,))


def process_count() -> int:
    """Number of jax processes in the job (1 unless jax.distributed ran)."""
    return int(jax.process_count())


def mesh_is_multihost(mesh) -> bool:
    """True when ``mesh`` spans devices owned by more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def global_batch_put(x, sharding):
    """Place a host value onto a (possibly multi-host) batch sharding.

    Single-host this is ``jax.device_put``.  Multi-host, every process holds
    the SAME full value (the sharded-search inputs are deterministic
    functions of arguments every process passes identically), and each
    contributes its addressable shards via ``make_array_from_callback`` —
    no cross-process transfer.  Works for typed prng key arrays too."""
    if not mesh_is_multihost(sharding.mesh):
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def replicate_to_hosts(tree, mesh):
    """All-gather a batch-sharded result pytree so every process holds the
    full value (fully-replicated arrays are addressable everywhere).  The one
    cross-process collective of the sharded-search path."""
    rep = replicated_sharding(mesh)
    return jax.jit(lambda t: t, out_shardings=rep)(tree)


def init_distributed_cpu(coordinator: str, num_processes: int,
                         process_id: int) -> None:
    """``jax.distributed.initialize`` for multi-process CPU runs.

    XLA:CPU only executes multi-process programs with the gloo collectives
    backend; the config flag must be set before the backend initializes, so
    this must be the first jax call of the process."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def batch_sharding(mesh, axis=None):
    """NamedSharding that splits leading array axes over ``axis`` (default:
    the mesh's first axis name).  The one place the sharding-construction API
    is touched, mirroring ``shard_map``/``make_mesh`` above."""
    if axis is None:
        axis = mesh.axis_names[0]
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))


def replicated_sharding(mesh):
    """NamedSharding replicating a value on every device of ``mesh``."""
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def tpu_compiler_params(*, dimension_semantics):
    """Pallas TPU compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
