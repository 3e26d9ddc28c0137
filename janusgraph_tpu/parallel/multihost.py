"""Multi-host execution: the DCN-scale runtime path.

The reference scales OLAP beyond one machine by shipping vertex programs to
Spark executors over Hadoop input splits (reference:
janusgraph-hadoop/src/main/java/org/janusgraph/hadoop/formats/util/
HadoopInputFormat.java:34 + TinkerPop SparkGraphComputer via
janusgraph-hadoop/pom.xml:59); inter-node communication rides the storage
backend's RPC plus the KCVSLog control bus (SURVEY.md §2.4).

The TPU-native design needs no separate execution framework: JAX's
multi-controller runtime makes every host run the SAME program over one
global mesh, with XLA routing collectives over ICI within a slice and DCN
across slices. Everything the sharded executor already does — boundary
all_to_all exchange, psum aggregator barriers, fused while_loop spans —
works unchanged on a multi-host mesh, because shard_map compiles against
the mesh's GLOBAL device set. This module supplies the (small) glue:

  1. `init_multihost()` — jax.distributed.initialize wrapper (coordinator
     address + process count + process id, from args or the standard
     JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID env).
  2. `global_mesh()` — a 1-D partition Mesh over the global device list,
     ordered so each host's local devices are contiguous (shard i lives on
     the host that loaded partition i's CSR block).
  3. `host_partition_range()` — which storage partitions this host should
     load (couples with olap/distributed_load.py, whose split unit is the
     same contiguous partition key range the mesh shards by).

Single-process operation (num_processes == 1) skips
jax.distributed.initialize entirely, so the same code path runs in tests
and on the virtual 8-device CPU mesh. The driver's dryrun certifies the
compile/execute path on a virtual mesh; real multi-host hardware is not
available in this environment (SURVEY.md §2.4.3), so the glue is kept
deliberately thin and fully exercised minus the actual DCN transport.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple


def init_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    config=None,
) -> int:
    """Initialize the JAX multi-controller runtime. Returns the process id.

    Arguments default to the standard env vars, then to the graph's
    cluster.* options when a GraphConfiguration is passed
    (cluster.coordinator-address / num-processes / process-id — the
    config-file deployment shape; env always wins so launchers can
    override). With one process (or no configuration at all) this is a
    no-op returning 0, so library code can call it unconditionally.
    """
    cfg_addr = cfg_procs = cfg_pid = None
    if config is not None:
        cfg_addr = config.get("cluster.coordinator-address") or None
        cfg_procs = config.get("cluster.num-processes") or None
        cfg_pid = config.get("cluster.process-id")
    coordinator_address = (
        coordinator_address
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
        or cfg_addr
    )
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env is not None else (cfg_procs or 1)
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env is not None else (cfg_pid or 0)
    if num_processes <= 1:
        return 0
    if not coordinator_address:
        raise ValueError(
            "multi-host run needs a coordinator address "
            "(JAX_COORDINATOR_ADDRESS or coordinator_address=)"
        )
    import jax

    # CPU multi-process needs an explicit cross-host collectives transport:
    # without one, the first sharded device_put/psum dies with
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Pick gloo (shipped in this jaxlib) unless the operator already chose;
    # harmless on TPU runs, which ride ICI/DCN and ignore the CPU setting.
    try:
        if jax.config.values.get(
            "jax_cpu_collectives_implementation"
        ) in (None, "none"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # unknown option on this jax: leave defaults alone
        pass

    # the black box should carry the cluster-formation timeline: a wedged
    # coordinator (or one host missing) is the first question an incident
    # review asks, and by then the process that knows may be gone
    from janusgraph_tpu.observability import flight_recorder

    flight_recorder.record(
        "multihost", action="init",
        processes=int(num_processes), process_id=int(process_id),
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except Exception as e:
        flight_recorder.record(
            "multihost", action="init_failed",
            processes=int(num_processes), process_id=int(process_id),
            error=f"{type(e).__name__}: {e}"[:200],
        )
        raise
    flight_recorder.record(
        "multihost", action="init_ok",
        processes=int(num_processes), process_id=int(process_id),
    )
    return process_id


def global_mesh(axis: str = "p"):
    """A 1-D Mesh over the GLOBAL device list (all hosts), host-contiguous.

    jax.devices() already orders devices process-by-process, so shard k of
    the mesh lands on host k // local_device_count — matching
    `host_partition_range`'s assignment of storage partitions to hosts.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis,))


def host_partition_range(
    num_partitions: int,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
) -> Tuple[int, int]:
    """[lo, hi) storage-partition ids this host loads (contiguous blocks,
    remainder spread over the leading hosts) — the input-split assignment
    for olap/distributed_load.py on a multi-host run."""
    import jax

    if process_id is None:
        process_id = jax.process_index()
    if num_processes is None:
        num_processes = jax.process_count()
    base, extra = divmod(num_partitions, num_processes)
    lo = process_id * base + min(process_id, extra)
    hi = lo + base + (1 if process_id < extra else 0)
    return lo, hi


def host_shard_range(
    num_shards: int,
    process_id: Optional[int] = None,
    num_processes: Optional[int] = None,
) -> Tuple[int, int]:
    """[lo, hi) MESH SHARDS whose blocked-plan blocks this host builds
    (parallel/halo.BlockedPlan.build_local). Deliberately the same
    contiguous assignment as host_partition_range: a host's loaded
    storage partitions are exactly the source-side edge sets of its
    shards, so distributed CSR loading feeds the local plan build with
    no edge redistribution — only the compact per-pair destination
    lists (the halo index) are exchanged as metadata."""
    return host_partition_range(num_shards, process_id, num_processes)
