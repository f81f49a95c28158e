"""Device mesh construction.

The reference's process topology (Zero + N servers in Raft groups serving
predicate tablets, SURVEY.md §1) maps onto TPU as:

  - mesh axis "shard": uid-range sharding of a predicate's CSR row space —
    the intra-tablet parallelism that replaces the reference's per-uid
    goroutine fan-in. Collectives ride ICI.
  - tablets (predicate → group routing, worker/groups.go BelongsTo) stay a
    host-level map: each predicate's sharded CSR lives across the mesh, and
    multi-predicate queries issue per-predicate device steps exactly like the
    reference issues per-predicate RPCs.

Multi-host: the same mesh spans hosts (jax distributed initialization);
DCN-crossing axes should shard the *predicate* dimension (coarse, low
chatter) while "shard" stays intra-pod, mirroring BASELINE's ICI-for-data /
DCN-for-control split.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(n_shards: int | None = None, devices=None) -> Mesh:
    devs = list(devices if devices is not None else jax.devices())
    n = n_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("shard",))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def row_sharded(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec("shard"))
