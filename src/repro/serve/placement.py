"""Device placement for the serving engine: TP shard maps + DP replica policy.

This is the one module that knows about device topology on the serving side.
``Engine`` / ``Scheduler`` stay device-agnostic: they hand their jitted step
builders a :class:`Placement` and their admitted requests to a
:class:`ReplicaPlacer`, and never touch ``jax.devices()`` themselves.

Sharding contract (see serve/README.md "Multi-device serving"):

* the packed pool shards on the KV-head axis over a single ``('model',)``
  mesh axis (``distributed.sharding.serve_pool_partition``); page tables,
  tokens, and positions are replicated; weights are replicated (carve-out —
  serving TP here is KV/attention/expert parallelism, not weight sharding);
* each DP replica owns a disjoint ``tp``-device mesh
  (``launch.mesh.make_serve_meshes``) plus its own PagedCache, prefix cache,
  and telemetry registry — replicas never communicate;
* everything is exactness-preserving: head/expert slices + tiled all_gather
  concats only, never a cross-shard reduction, so a sharded engine emits
  bit-identical tokens to the single-device engine.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import serve_pool_partition
from repro.launch.mesh import make_serve_meshes


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """User-facing knob on :class:`~repro.serve.engine.EngineConfig`.

    ``tp`` shards each replica's pool/attention/experts over a ``('model',)``
    mesh; ``dp`` runs that many independent engine replicas on disjoint
    device groups (``serve.replica.ReplicatedEngine``)."""

    tp: int = 1
    dp: int = 1

    def __post_init__(self):
        if self.tp < 1 or self.dp < 1:
            raise ValueError(f"tp/dp must be >= 1, got tp={self.tp} dp={self.dp}")


class Placement:
    """One engine replica's device placement: a ``('model',)`` mesh of ``tp``
    devices plus helpers to put the pool (head-sharded) and everything else
    (replicated) onto it.  ``tp == 1`` without a mesh is the no-op placement
    — single-device engines then never touch device state here; ``tp == 1``
    WITH a one-device mesh (a data-parallel replica) pins the replica's pool
    and params to that mesh's device, so its jitted steps run there."""

    AXIS = "model"

    def __init__(self, tp: int = 1, mesh: Mesh | None = None):
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        self.tp = tp
        if tp > 1 and mesh is None:
            mesh = make_serve_meshes(tp, 1)[0]
        if mesh is not None and mesh.size != tp:
            raise ValueError(
                f"placement mesh has {mesh.size} devices, want tp={tp}")
        self.mesh = mesh

    def pool_specs(self, pool):
        """Head-axis PartitionSpecs for a pool pytree (replicated if tp==1)."""
        if self.tp == 1:
            return jax.tree.map(lambda l: P(*([None] * l.ndim)), pool)
        return serve_pool_partition(pool, self.mesh)

    def shard_pool(self, pool):
        if self.mesh is None:
            return pool
        specs = self.pool_specs(pool)
        return jax.tree.map(
            lambda l, s: jax.device_put(l, NamedSharding(self.mesh, s)),
            pool, specs)

    def replicate(self, tree):
        """Replicate a pytree (params, tables, dense caches) over the mesh."""
        if self.mesh is None:
            return tree
        return jax.tree.map(
            lambda l: jax.device_put(
                l, NamedSharding(self.mesh, P(*([None] * l.ndim)))), tree)


class ReplicaPlacer:
    """Places admitted requests onto DP replicas from their local slot/page
    inventories: most free pages first (pages are the scarce, fragmenting
    resource), free slots break ties, round-robin breaks exact ties so equal
    replicas interleave instead of piling onto replica 0."""

    def __init__(self, n_replicas: int):
        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        self.n = n_replicas
        self._rr = 0

    def place(self, free_pages, free_slots) -> int:
        """free_pages/free_slots: per-replica inventories (len == n)."""
        assert len(free_pages) == self.n and len(free_slots) == self.n
        order = [(self._rr + i) % self.n for i in range(self.n)]
        best = max(order, key=lambda r: (free_pages[r], free_slots[r]))
        self._rr = (best + 1) % self.n
        return best
