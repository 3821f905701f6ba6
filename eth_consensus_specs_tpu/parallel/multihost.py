"""Multi-host scaling: jax.distributed bootstrap + hybrid ICI/DCN meshes.

The reference has no communication backend at all (SURVEY §2.3 — no
NCCL/MPI/Gloo anywhere; it is a single-process executable spec). Here the
"backend" is XLA collectives, and multi-host is the same SPMD code the
single-host meshes run, over a mesh whose axes are laid out so that the
high-traffic collectives ride ICI (within a host's chips) and only the
low-traffic ones cross DCN (between hosts):

  * ``dp`` (validator axis) spans HOSTS: the epoch kernel's cross-shard
    traffic is two psums per epoch — one u64 scalar and one dense
    O(n_validators) scatter-add (parallel/epoch.py MeshReductions) — a
    few MB/epoch, comfortably inside DCN budgets.
  * ``sp`` (chunk/sequence axis) stays WITHIN a host: the sharded merkle
    tree all-gathers per-device subtree roots every level pair
    (parallel/merkle.py), the latency-sensitive path that wants ICI.

This is the scaling-book recipe: pick the mesh, put bandwidth-hungry
axes on ICI, let pjit/shard_map insert the collectives.

Process bootstrap wraps `jax.distributed.initialize`, which speaks the
same coordinator protocol on TPU pods (host metadata autodetection) and
CPU/GPU clusters (explicit coordinator + process count, e.g. from a job
scheduler's env). Single-process callers get a no-op, so every entry
point in this module is safe to call unconditionally.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

from eth_consensus_specs_tpu import obs

from . import DP_AXIS, SP_AXIS

_initialized = False


def _runtime_client():
    """The live ``jax.distributed`` client (or None) WITHOUT touching the
    local backend: ``jax.process_count()`` would finalize the runtime,
    after which ``jax.distributed.initialize`` refuses to run at all —
    the probe must not destroy what it probes for."""
    from jax._src import distributed as _dist

    return getattr(_dist.global_state, "client", None)


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join (or skip joining) the multi-host runtime. Returns True when a
    multi-process runtime is live after the call.

    Resolution order: explicit args > JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID env > TPU-pod autodetection
    (jax.distributed.initialize with no args works on TPU pods) > no-op
    single process."""
    global _initialized
    if _initialized or _runtime_client() is not None:
        # joined already (here, or by an external bootstrap)
        _initialized = True
        return jax.process_count() > 1
    with obs.span("multihost.initialize"):
        live = _initialize_distributed(coordinator_address, num_processes, process_id)
    obs.count("multihost.initializations", 1)
    obs.count("multihost.processes", jax.process_count())
    return live


def _initialize_distributed(
    coordinator_address: str | None,
    num_processes: int | None,
    process_id: int | None,
) -> bool:
    global _initialized
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # no explicit cluster config: on a TPU pod slice, initialize()
        # autodetects; everywhere else stay single-process
        if jax.default_backend() == "tpu":
            try:
                jax.distributed.initialize()
                _initialized = True
            except Exception as exc:
                # autodetection failing on a pod slice is a real operational
                # signal (mis-set env, dead coordinator) — leave a breadcrumb
                # instead of degrading to single-process silently
                obs.count("multihost.init_failures", 1)
                obs.event("multihost.init_failed", error=repr(exc)[:200])
                return False
        return jax.process_count() > 1
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return jax.process_count() > 1


def maybe_initialize_for_replica() -> bool:
    """The replica-boot seam of the two-tier fleet: with
    ``ETH_SPECS_SERVE_DISTRIBUTED=1`` a spawned replica joins the
    multi-host runtime (coordinator env / TPU-pod autodetection, see
    :func:`initialize_distributed`) BEFORE building its service, so its
    serve mesh becomes a whole pod slice instead of a local-device
    slice. Single-host fleets (the default) skip the bootstrap entirely
    — no env, no-op. Returns True when a multi-process runtime is
    live."""
    if os.environ.get("ETH_SPECS_SERVE_DISTRIBUTED") != "1":
        return False
    return initialize_distributed()


def make_hybrid_mesh(sp_per_host: int | None = None) -> Mesh:
    """A (dp, sp) mesh laid out host-major: sp varies WITHIN each host's
    devices (collective-heavy axis on ICI), dp spans hosts (scalar psums
    cross DCN).

    Single-process fallback degrades to the flat make_mesh layout, so
    tests and the virtual CPU mesh exercise the same entry point."""
    devices = jax.devices()
    n_local = len(jax.local_devices())
    n_hosts = max(jax.process_count(), 1)
    if sp_per_host is None:
        sp_per_host = 2 if n_local % 2 == 0 and n_local >= 2 else 1
    if n_hosts <= 1:
        from . import make_mesh

        obs.count("multihost.meshes_flat", 1)
        return make_mesh()
    # [host, local] grid: host-major ordering keeps each host's devices
    # contiguous along the trailing (sp) axis
    dp_per_host = n_local // sp_per_host
    grid = np.asarray(devices).reshape(n_hosts * dp_per_host, sp_per_host)
    obs.count("multihost.meshes_hybrid", 1)
    obs.event(
        "multihost.mesh",
        dp=n_hosts * dp_per_host,
        sp=sp_per_host,
        hosts=n_hosts,
        devices=len(devices),
    )
    return Mesh(grid, (DP_AXIS, SP_AXIS))


class ShardRemainderError(ValueError):
    """`n_global` does not divide the mesh's shard count — an even
    per-shard split would silently orphan the remainder rows. Pad the
    global axis to :func:`padded_global` (and pass ``pad=True``) or keep
    the axis divisible."""

    def __init__(self, n_global: int, n_shards: int):
        self.n_global = n_global
        self.n_shards = n_shards
        self.remainder = n_global % n_shards
        super().__init__(
            f"n_global={n_global} leaves {self.remainder} rows beyond an even "
            f"{n_shards}-shard split; pad to {padded_global(n_global, n_shards)} "
            "(host_local_slice(..., pad=True) slices the padded domain) or "
            "keep the axis divisible"
        )


def padded_global(n_global: int, n_shards: int) -> int:
    """Smallest multiple of the shard count >= n_global — the padded
    domain ``host_local_slice(..., pad=True)`` slices."""
    return n_shards * -(-n_global // n_shards)


def host_local_slice(mesh: Mesh, n_global: int, pad: bool = False) -> tuple[int, int]:
    """[start, stop) of the validator rows this process owns under a
    dp-sharded array on `mesh` — the addressable block a host feeds or
    reads without cross-host transfers (jax.Array per-shard semantics).

    A `n_global` that does not divide the shard count used to silently
    truncate: every shard got ``n_global // n_shards`` rows and the
    remainder belonged to nobody. Now the remainder is counted
    (``multihost.slice_remainder``) and either raises the typed
    :class:`ShardRemainderError` (default) or, with ``pad=True``, slices
    the :func:`padded_global` domain — callers pad their arrays to it,
    exactly like the kernels pad their batch axes."""
    n_shards = mesh.shape[DP_AXIS] * mesh.shape[SP_AXIS]
    rem = n_global % n_shards
    if rem:
        obs.count("multihost.slice_remainder", rem)
        obs.event(
            "multihost.slice_remainder",
            n_global=int(n_global),
            n_shards=int(n_shards),
            remainder=int(rem),
            padded=bool(pad),
        )
        if not pad:
            raise ShardRemainderError(n_global, n_shards)
    per = padded_global(n_global, n_shards) // n_shards if rem else n_global // n_shards
    local_ids = {
        i for i, d in enumerate(mesh.devices.flat) if d.process_index == jax.process_index()
    }
    if not local_ids:
        # a process can legitimately own no devices of this mesh (e.g. a
        # coordinator-only host, or a mesh built from a device subset):
        # its addressable block is empty, not a min()-over-nothing crash
        obs.event(
            "multihost.no_local_devices",
            process=jax.process_index(),
            mesh_devices=int(mesh.devices.size),
        )
        return 0, 0
    lo, hi = min(local_ids), max(local_ids)
    return lo * per, (hi + 1) * per
