"""Batch-axis sharding for batched multi-root search (DESIGN.md §9, §13).

``search_batch`` runs B independent searches as one vmapped XLA program on a
single device.  ``shard_search_batch`` runs the *same* program partitioned
over a 1-D device mesh: the stacked-domain pytree and the per-root rng keys
are sharded along the batch axis with ``jit`` + ``NamedSharding``, so each
device executes B/ndev roots of an identical per-root computation — the
array-decomposed analogue of root parallelism on "large parallel machines"
(the regime the paper targets).

The mesh may span multiple processes (``jax.distributed``-initialized
multi-host jobs): inputs are then placed with
``compat.global_batch_put`` (every process holds the same host value and
contributes its addressable shards — no cross-process input transfer, which
is sound because the inputs are deterministic functions of arguments every
process passes identically), the per-root programs still run without any
cross-device communication, and the results are all-gathered back to every
process with ``compat.replicate_to_hosts`` so each host returns the full
``SearchResult``.

Contracts (tested in tests/test_sharding.py and tests/test_multihost.py):

* **Per-root semantics are identical** to ``search_batch``: the rng is split
  into exactly B keys *before* any padding or placement, and every batch
  element i reproduces ``search(domains[i], cfg, jax.random.split(rng, B)[i])``
  bit-for-bit on ``action_visits``/``stats`` — on one device, on a
  single-process mesh, and on a multi-host mesh.
* **Padding**: B is padded up to a multiple of the mesh's device count by
  repeating row 0 (a valid domain + key); padded rows run a real search
  whose outputs are sliced off before returning.
* **API seam**: meshes and shardings are built through
  ``repro.parallel.compat``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.compat import (batch_sharding, global_batch_put,
                                   mesh_is_multihost, mesh_num_devices,
                                   replicate_to_hosts, shard_map)

__all__ = ["shard_search_batch"]


def _default_mesh():
    from repro.launch.mesh import make_search_mesh
    return make_search_mesh()


def _pad_rows(x, pad: int):
    """Append ``pad`` copies of row 0 (works for typed prng key arrays too —
    jnp.broadcast_to/concatenate dispatch on the extended dtype)."""
    if pad == 0:
        return x
    fill = jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])
    return jnp.concatenate([x, fill], axis=0)


def per_device(f, mesh, n_args: int, replicated=()):
    """``f`` run by every device of ``mesh`` on its own rows: its
    ``n_args`` arguments (but the ``replicated`` positions) and every
    output are split along their leading axis over the mesh's first axis.
    A ``shard_map``, not compiler partitioning: a Mosaic (Pallas TPU)
    kernel inside ``f`` cannot be partitioned automatically."""
    spec = P(mesh.axis_names[0])
    in_specs = tuple(P() if i in replicated else spec for i in range(n_args))
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=spec,
                     check_vma=False)


def shard_search_batch(domains, cfg, rng, *, mesh=None):
    """``search_batch`` with the batch axis sharded over a device mesh.

    ``mesh`` is a 1-D mesh (default: ``repro.launch.mesh.make_search_mesh()``
    over every visible device — *global* devices in a multi-host job).
    Returns the same ``SearchResult`` pytree as
    ``search_batch(domains, cfg, rng)`` — same leading batch axis B, same
    per-root values — with every leaf sharded along the mesh's batch axis
    (re-replicated to every process first when the mesh is multi-host).
    """
    domains = list(domains)
    if not domains:
        raise ValueError("shard_search_batch needs at least one domain")
    # rng contract: split into exactly B keys BEFORE padding or placement, so
    # element i matches search(domains[i], cfg, jax.random.split(rng, B)[i])
    rngs = jax.random.split(rng, len(domains))
    return shard_search_keys(domains, cfg, rngs, mesh=mesh)


def shard_search_keys(domains, cfg, keys, *, mesh=None):
    """``shard_search_batch`` with the per-root keys already split out.

    The elastic driver (search/ft.py) re-runs arbitrary subsets of roots
    under their ORIGINAL keys; this is the shared implementation that makes
    a requeued root bit-for-bit identical to its uninterrupted run.
    """
    from repro.search.api import _batch_domains, search

    domains = list(domains)
    if mesh is None:
        mesh = _default_mesh()
    ndev = mesh_num_devices(mesh)
    b = len(domains)
    pad = (-b) % ndev
    make, batched = _batch_domains(domains)

    sharded = batch_sharding(mesh)
    multihost = mesh_is_multihost(mesh)
    rngs = global_batch_put(_pad_rows(keys, pad), sharded)
    # each device vmaps over its own rows (per_device)
    if batched is None:
        d0 = domains[0]
        fn = jax.jit(per_device(jax.vmap(lambda r: search(d0, cfg, r)),
                                mesh, 1))
        res = fn(rngs)
    else:
        batched = jax.tree_util.tree_map(
            lambda x: global_batch_put(_pad_rows(x, pad), sharded), batched)
        fn = jax.jit(per_device(
            jax.vmap(lambda bat, r: search(make(bat), cfg, r)), mesh, 2))
        res = fn(batched, rngs)
    if multihost:
        res = replicate_to_hosts(res, mesh)
    if pad:
        res = jax.tree_util.tree_map(lambda x: x[:b], res)
    return res
