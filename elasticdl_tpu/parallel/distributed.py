"""jax.distributed lifecycle for elastic multi-host worlds.

The reference re-initializes Horovod whenever the master bumps the
rendezvous id (/root/reference/elasticdl/python/worker/
allreduce_trainer.py:46-75: hvd.shutdown() + hvd.init()). The TPU analog:
tear down and re-create the JAX coordination service connection with the new
(coordinator, world_size, rank) triple, after which jax.devices() shows the
new global device set and freshly-built meshes span the new world.

Single-process deployments (tests, LOCAL strategy, one TPU host) never call
initialize — the local platform is the world.
"""

import jax

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("parallel.distributed")


def _clear_backends():
    try:
        from jax.extend.backend import clear_backends

        clear_backends()
    except Exception:
        logger.warning("could not clear XLA backends", exc_info=True)

_current = {
    "coordinator": None,
    "world": 0,
    "rank": -1,
    "epoch": -1,
    "live": False,
}

# Elasticity-tuned timeouts. The shutdown barrier is best-effort: after a
# peer SIGKILL the survivors' barrier can never complete, so it must fail
# fast (and be swallowed) rather than hold up the re-mesh for the default
# 300 s. Heartbeat stays above the gloo collective timeout (~30 s) so an
# in-flight collective surfaces a catchable step error before the
# coordination client's process-killing health check fires.
INITIALIZATION_TIMEOUT_SECONDS = 120
SHUTDOWN_TIMEOUT_SECONDS = 10
HEARTBEAT_TIMEOUT_SECONDS = 60


def _shutdown_quietly():
    try:
        jax.distributed.shutdown()
    except Exception:
        # Failed shutdown barrier (dead peer) or an already-errored
        # coordination client: the world is being abandoned either way.
        logger.warning(
            "Distributed shutdown was not clean (peer death is the usual "
            "cause); proceeding with teardown",
            exc_info=True,
        )


def ensure_world(coordinator_addr, world_size, rank, epoch=None):
    """(Re)join the distributed world described by the triple. No-ops only
    when already a member of this world AT THIS membership epoch — the epoch
    matters because a survivor's (coordinator, world, rank) can be unchanged
    across a swap (B dies, C joins) while the coordination service still
    needs a full re-init for the newcomer to rendezvous. world_size == 1
    tears down any previous multi-host state and runs single-process."""
    same = (
        _current["live"]
        and _current["coordinator"] == coordinator_addr
        and _current["world"] == world_size
        and _current["rank"] == rank
        and epoch is not None
        and _current["epoch"] == epoch
    )
    if same:
        return
    if _current["live"]:
        logger.info("Leaving distributed world %s", _current)
        _shutdown_quietly()
        _current["live"] = False
        # Drop the cached backends so the old world's device topology
        # can't leak into world_size<=1 callers; the join path below also
        # clears unconditionally before re-initializing. Compiled
        # functions from the old world are invalid either way; trainers
        # rebuild their jitted steps after a regroup.
        _clear_backends()
    if world_size <= 1:
        _current.update(coordinator=None, world=1, rank=0, epoch=epoch)
        return
    logger.info(
        "Joining world coordinator=%s size=%d rank=%d epoch=%s",
        coordinator_addr,
        world_size,
        rank,
        epoch,
    )
    try:
        # Cross-process CPU collectives need the gloo implementation; a
        # no-op on TPU deployments.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        logger.warning(
            "could not select gloo CPU collectives; cross-process CPU "
            "worlds may fail",
            exc_info=True,
        )
    # jax.distributed.initialize refuses to run once a backend is
    # initialized — true both on a FIRST join from a process that already
    # ran JAX computations (a trainer that built params before discovering
    # its world) and on a rejoin. Drop any cached backends; callers must
    # host-snapshot device state BEFORE calling (the trainer does).
    _clear_backends()
    init_kwargs = dict(
        coordinator_address=coordinator_addr,
        num_processes=world_size,
        process_id=rank,
        initialization_timeout=INITIALIZATION_TIMEOUT_SECONDS,
        shutdown_timeout_seconds=SHUTDOWN_TIMEOUT_SECONDS,
        heartbeat_timeout_seconds=HEARTBEAT_TIMEOUT_SECONDS,
    )
    jax.distributed.initialize(**init_kwargs)
    _current.update(
        coordinator=coordinator_addr,
        world=world_size,
        rank=rank,
        epoch=epoch,
        live=True,
    )


def is_live():
    """True while this process is a member of a live multi-host world —
    i.e. a world change would re-initialize jax.distributed and tear
    down every compiled executable (the regroup fast path keys on the
    negation)."""
    return _current["live"]


def leave_world():
    if _current["live"]:
        _shutdown_quietly()
        _current["live"] = False
