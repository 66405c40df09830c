"""Multi-host initialisation — the distributed communication backend hook.

The reference's "communication backend" is PCIe memcpys + pthread barriers
inside one process (SURVEY §2); it cannot leave one machine.  The TPU build
scales past a host boundary with the standard JAX runtime: every host runs
the same SPMD program, `jax.distributed.initialize` wires the hosts into one
global device mesh, and the identical `shard_map` code from
:mod:`.sharded` then spans ICI within a slice and DCN across slices — the
collectives (the stripe-axis ``psum``) are inserted by XLA either way.

Call :func:`initialize` once per process before building meshes.  On a
single host it is a no-op, so the same entry scripts work everywhere.
"""

from __future__ import annotations

import os


def _enable_cpu_collectives() -> None:
    """Select the gloo CPU collectives implementation when the option is
    still at its 'none' default.

    Multi-process jobs on the CPU backend (the 2-process integration
    tests, CPU-only fleet tooling) need a cross-process collectives
    layer or XLA refuses with "Multiprocess computations aren't
    implemented on the CPU backend".  Must run before the CPU client
    initialises; harmless on TPU (the knob only steers CPU client
    construction)."""
    import jax

    if jax.config._read("jax_cpu_collectives_implementation") == "none":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    auto: bool = False,
) -> None:
    """Initialise multi-host JAX.

    Explicit configuration comes from the arguments or the standard env
    vars (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``); if ANY of the three is present, a full explicit
    init is performed (jax validates completeness).  ``auto=True`` (or env
    ``RS_DISTRIBUTED=auto``) requests the Cloud-TPU metadata auto-detection
    (bare ``jax.distributed.initialize()``).  With neither, this is a
    no-op — safe to call unconditionally in single-process scripts.
    """
    import jax

    if jax.distributed.is_initialized():
        _mark_telemetry_epoch(jax)
        return  # idempotent: callers (library AND cli) may both invoke this

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if auto or os.environ.get("RS_DISTRIBUTED") == "auto":
        _enable_cpu_collectives()
        jax.distributed.initialize()
        _mark_telemetry_epoch(jax)
        return
    if coordinator_address is None and num_processes is None and process_id is None:
        return  # single process, nothing configured
    # CPU-backend multi-process jobs need a collectives layer (gloo)
    # selected before the client initialises.
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _mark_telemetry_epoch(jax)


def _mark_telemetry_epoch(jax) -> None:
    """Capture the shared trace-alignment epoch (obs/aggregate.py).

    ``jax.distributed.initialize`` is a barrier every process crosses
    near-simultaneously, so the wall clock HERE is the common time anchor
    that lets per-process Perfetto traces fuse onto one axis.  Marked only
    once (re-init calls keep the first, earliest anchor).
    """
    from ..obs import tracing

    if tracing._EPOCH is None:
        tracing.mark_epoch(process_index=jax.process_index())


def global_mesh(stripe: int = 1):
    """Mesh over ALL devices of the (possibly multi-host) job.

    Lay the stripe axis within hosts where possible so the per-segment
    psum rides ICI; the cols axis (no communication) is the one that may
    span DCN.
    """
    from .mesh import make_mesh

    return make_mesh(stripe=stripe)
