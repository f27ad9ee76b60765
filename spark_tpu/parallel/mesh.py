"""Mesh construction: the device topology the engine schedules onto.

One 1-D "data" axis for now (row sharding + exchanges); the Mesh API
generalizes to multi-axis layouts (e.g. ("data", "model")) without
changing operator code, because every collective names its axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map  # noqa: F401 — re-exported to the engine
from jax.sharding import Mesh

AXIS = "data"

EXCLUDE_KEY = "spark_tpu.sql.mesh.excludeDevices"


def _all_reduce(x, axis_name, reduce_all, reduce_local):
    """Cross-shard max/min the way XLA:TPU gets right (libtpu 0.0.34,
    found on four v5e chips in PR 22); only 32-bit operands take the
    library's all-reduce as they are.

    - 64-bit: refused at compile time ("UNIMPLEMENTED: Supported
      lowering only of Sum all reduce" for an s64/f64 pmax/pmin), and
      with x64 on every Python int in the stats channel is 64-bit.
      Gather the per-shard values and reduce locally: exact, and the
      operands are scalars and bounds, never tables.
    - 8/16-bit: accepted, and WRONG element-wise on the chip (a uint8
      pmax over Bloom bits lost set bits, the runtime filter pruned
      matching probe rows, Q3 under mesh.size=4 answered wrongly).
      Widen to 32 bits for the reduce and narrow the result."""
    x = jnp.asarray(x)
    if x.dtype.itemsize == 8:
        return reduce_local(jax.lax.all_gather(x, axis_name), axis=0)
    if x.dtype.itemsize < 4:
        wide = jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.int32
        return reduce_all(x.astype(wide), axis_name).astype(x.dtype)
    return reduce_all(x, axis_name)


def pmax(x, axis_name):
    return _all_reduce(x, axis_name, jax.lax.pmax, jnp.max)


def pmin(x, axis_name):
    return _all_reduce(x, axis_name, jax.lax.pmin, jnp.min)


def mesh_size(conf) -> int:
    n = int(conf.get("spark_tpu.sql.mesh.size"))
    return max(1, n)


def excluded_device_ids(conf) -> set:
    """Decommissioned device ids (spark_tpu.sql.mesh.excludeDevices):
    drained by the elastic-mesh layer (parallel/elastic.py) or pinned
    by an operator — never meshed over again this session. Malformed
    entries WARN (an operator's typo'd pin-out silently keeping the
    bad device in the gang would be worse than noise)."""
    from .elastic import _parse_int_set
    return _parse_int_set(conf.get(EXCLUDE_KEY))


def get_mesh(conf) -> Optional[Mesh]:
    """Build the 1-D data mesh from conf, or None for single-chip.

    With no exclusions a short device pool is a setup ERROR (the
    remediation-hint diagnostic below). With exclusions — a graceful
    decommission drained part of the gang — the mesh shrinks to the
    surviving pool instead: elasticity means a smaller gang, not a
    failed query. A pool of <= 1 survivors degrades to single-chip,
    which runs on the process's JAX DEFAULT device without consulting
    the exclusion list (see the excludeDevices conf doc) — excluding
    the default device needs JAX visible-device flags, not conf."""
    n = mesh_size(conf)
    if n <= 1:
        return None
    init_distributed(conf)  # no-op unless cluster.coordinator is set
    devices = jax.devices()
    import numpy as np
    if len(devices) < n:
        # a pool short even BEFORE exclusions is a setup error, never
        # elasticity — exclusions must not swallow the diagnostic
        raise RuntimeError(
            f"mesh.size={n} but only {len(devices)} devices visible "
            f"({[d.platform for d in devices[:4]]}...); for CI use "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    excluded = excluded_device_ids(conf)
    if excluded:
        pool = [d for d in devices
                if int(getattr(d, "id", -1)) not in excluded]
        n = min(n, len(pool))
        if n <= 1:
            return None
        return Mesh(np.array(pool[:n]), (AXIS,))
    return Mesh(np.array(devices[:n]), (AXIS,))


#: (mesh, its stage token): the gang in use only, so a gang that was
#: shrunk or restarted leaves no buffer behind on the devices it had
_TOKEN: tuple = (None, None)


def stage_token(mesh: Mesh):
    """The [n] int32 argument that gives a mesh stage its shard axis,
    made once for the mesh in use and laid over it: a dispatch that
    made its own on the default device would have it moved to the
    shards every time."""
    global _TOKEN
    held, token = _TOKEN
    if held != mesh:
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec
        token = jax.device_put(
            np.zeros((int(mesh.devices.size),), np.int32),
            NamedSharding(mesh, PartitionSpec(AXIS)))
        _TOKEN = (mesh, token)
    return token


def shard_hosts(mesh: Mesh) -> list:
    """Per-shard host identity for telemetry records: the JAX process
    index owning each data-axis position's device (0 for every shard on
    a single-host/virtual-CPU mesh). Multi-host straggler reports need
    the shard -> host mapping to name the slow MACHINE, not just the
    slow mesh position."""
    return [int(getattr(d, "process_index", 0) or 0)
            for d in mesh.devices.flat]


def init_distributed(conf) -> int:
    """Multi-host bring-up: initialize the JAX distributed runtime so
    `jax.devices()` spans every host's chips and the engine's collectives
    ride ICI within a slice and DCN across slices.

    The control-plane analog of the reference's executor registration
    (`CoarseGrainedExecutorBackend.main:405` dialing the driver): every
    host runs the SAME engine process, pointed at one coordinator:

        spark_tpu.sql.cluster.coordinator = host0:8476
        spark_tpu.sql.cluster.numProcesses = <hosts>
        spark_tpu.sql.cluster.processId   = <this host's rank>

    After init, set spark_tpu.sql.mesh.size to the GLOBAL device count;
    gang SPMD replaces the reference's scheduler/shuffle-service fleet —
    there is no other inter-host protocol to deploy. Returns the global
    device count. No-op (returns local count) when no coordinator is
    configured; idempotent per process."""
    coord = str(conf.get("spark_tpu.sql.cluster.coordinator") or "")
    if not coord:
        return len(jax.devices())
    num = int(conf.get("spark_tpu.sql.cluster.numProcesses"))
    pid = int(conf.get("spark_tpu.sql.cluster.processId"))
    if not jax.distributed.is_initialized():
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=num, process_id=pid)
    return len(jax.devices())
