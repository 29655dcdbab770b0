"""Distributed communication facade.

TPU-native analog of ``deepspeed.comm`` (deepspeed/comm/comm.py:222-521 module-level
ops, ``init_distributed:604``).  The reference wraps torch.distributed/NCCL; here
collectives are XLA mesh-axis operations with two calling conventions:

1. **In-graph** (inside jit / shard_map over a Mesh): ``all_reduce(x, axis="data")``
   lowers to ``lax.psum`` and friends — XLA routes them over ICI and overlaps with
   compute.  This is the hot path ZeRO/MoE/Ulysses use.
2. **Host-level** (eager, outside jit): same function names operate on jax.Arrays
   by jitting a trivial collective over the current topology — used for control
   plane work (broadcast of initial params, barriers, scalar consensus) where the
   reference used eager NCCL calls.

Every op is profiled through the CommsLogger (analog of ``timed_op`` comm.py:101).
"""

import functools
import os
import threading
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.mesh import MeshTopology, get_topology
from ..runtime.heartbeat import (COLLECTIVE_TIMEOUT_ENV, INIT_RETRIES_ENV,
                                 INIT_RETRY_BACKOFF_ENV, get_heartbeat)
from ..utils.comms_logging import get_comms_logger
from ..utils.env import env_float, env_int
from ..utils.logging import logger, warning_once

ReduceOp = type("ReduceOp", (), {"SUM": "sum", "AVG": "avg", "MAX": "max", "MIN": "min", "PRODUCT": "prod"})

_INITIALIZED = False

# -------------------------------------------------------- bounded collectives
# Default wall-clock bound for HOST-LEVEL collectives (barrier and anything
# routed through bounded_collective).  None = unbounded (the historical
# behavior).  Set from config (fault_tolerance.collective_timeout_s via
# initialize()/the engine), set_default_collective_timeout(), or the env the
# elastic agent exports to its workers (collective_timeout_s agent param /
# launcher --collective_timeout).
_DEFAULT_COLLECTIVE_TIMEOUT_S: Optional[float] = None


class CollectiveTimeoutError(RuntimeError):
    """A host-level collective exceeded its wall-clock bound.

    The whole point of bounding collectives: a rank stuck in (or absent from)
    a collective otherwise deadlocks every peer SILENTLY — the job burns its
    deadline with zero diagnostics.  This error names the collective, this
    process's rank, and the elapsed time, so the supervisor (elastic agent)
    gets a fast, attributable failure to restart from instead of a hang."""

    def __init__(self, collective: str, rank: int, elapsed_s: float, timeout_s: float):
        self.collective = collective
        self.rank = rank
        self.elapsed_s = elapsed_s
        self.timeout_s = timeout_s
        super().__init__(
            f"collective '{collective}' timed out on rank {rank} after "
            f"{elapsed_s:.1f}s (timeout {timeout_s:.1f}s) — a peer likely "
            f"crashed, hung, or entered a different collective; check the "
            f"elastic agent's cross-rank hang snapshot for the stuck ranks")


def set_default_collective_timeout(timeout_s: Optional[float]) -> None:
    global _DEFAULT_COLLECTIVE_TIMEOUT_S
    _DEFAULT_COLLECTIVE_TIMEOUT_S = None if timeout_s is None else float(timeout_s)


def _resolve_timeout(timeout_s) -> Optional[float]:
    if timeout_s is not None:
        return float(timeout_s) if timeout_s > 0 else None
    env_val = env_float(COLLECTIVE_TIMEOUT_ENV)
    if env_val is not None:
        return env_val if env_val > 0 else None
    return _DEFAULT_COLLECTIVE_TIMEOUT_S


def bounded_collective(fn, *args, timeout_s: Optional[float] = None,
                       name: str = "collective", **kwargs):
    """Run a blocking host-level collective with a wall-clock bound.

    Stamps the heartbeat (``enter_collective(name)`` / ``exit_collective``)
    around the wait so the agent's hang dump can NAME the collective each
    rank sat in, then executes ``fn`` on a daemon worker thread and joins
    with the resolved timeout.  On expiry raises
    :class:`CollectiveTimeoutError`; the worker thread stays parked on the
    wedged collective (there is no portable way to cancel it) — the expected
    response is process exit + agent restart, which is exactly what the
    error exists to trigger.  ``timeout_s=None`` falls back to the
    module/env default; no default means a direct (unbounded) call, still
    heartbeat-stamped."""
    timeout = _resolve_timeout(timeout_s)
    hb = get_heartbeat()
    hb.enter_collective(name)
    timed_out = False
    try:
        if timeout is None:
            return fn(*args, **kwargs)
        result: list = []
        failure: list = []

        def _run():
            try:
                result.append(fn(*args, **kwargs))
            except BaseException as exc:  # noqa: BLE001 — re-raised on the caller thread below
                failure.append(exc)

        t0 = time.monotonic()
        worker = threading.Thread(target=_run, name=f"dstpu-{name}", daemon=True)
        worker.start()
        worker.join(timeout)
        if worker.is_alive():
            timed_out = True
            raise CollectiveTimeoutError(name, get_rank(), time.monotonic() - t0, timeout)
        if failure:
            raise failure[0]
        return result[0]
    finally:
        # on timeout the worker thread is STILL wedged inside the collective:
        # keep its name stamped so the agent's hang dump can attribute the
        # deadlock (clearing it would erase exactly that diagnosis and reset
        # the staleness clock on a rank that is not making progress)
        if not timed_out:
            hb.exit_collective()


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     init_method: Optional[str] = None,
                     rank: int = -1,
                     world_size: int = -1,
                     timeout=None,
                     verbose=True):
    """Host control plane init — analog of ``deepspeed.init_distributed``
    (comm/comm.py:604).  Multi-host JAX uses ``jax.distributed.initialize`` (the
    rendezvous analog of the reference's NCCL TCP store); single-host is a no-op.

    Env discovery: honors COORDINATOR_ADDRESS / JAX_COORDINATOR_ADDRESS plus the
    reference's RANK/WORLD_SIZE spellings for familiarity.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coord = (init_method or os.environ.get("COORDINATOR_ADDRESS") or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coord:
        nproc = world_size if world_size > 0 else int(os.environ.get("WORLD_SIZE", "1"))
        pid = rank if rank >= 0 else int(os.environ.get("RANK", "0"))
        _initialize_with_retries(coord, nproc, pid, timeout)
        if verbose:
            logger.info(f"jax.distributed initialized: process {pid}/{nproc} via {coord}")
    from ..utils import logging as _logging
    _logging.set_rank_provider(jax.process_index)
    _INITIALIZED = True


# Module defaults for the process-group setup retry loop.  Set from config
# (fault_tolerance.init_retries/init_retry_backoff_s, applied by
# deepspeed_tpu.initialize() BEFORE init_distributed runs) via
# set_init_retry_defaults(); the agent-exported env wins over both.
_DEFAULT_INIT_RETRIES = 3
_DEFAULT_INIT_RETRY_BACKOFF_S = 0.5


def set_init_retry_defaults(retries: Optional[int] = None,
                            backoff_s: Optional[float] = None) -> None:
    """Default attempts/backoff for ``_initialize_with_retries`` (None keeps
    the current value for that knob)."""
    global _DEFAULT_INIT_RETRIES, _DEFAULT_INIT_RETRY_BACKOFF_S
    if retries is not None:
        _DEFAULT_INIT_RETRIES = max(int(retries), 0)
    if backoff_s is not None:
        _DEFAULT_INIT_RETRY_BACKOFF_S = max(float(backoff_s), 0.0)


def _initialize_with_retries(coord: str, nproc: int, pid: int, timeout=None) -> None:
    """``jax.distributed.initialize`` under bounded exponential-backoff
    retries — process-group setup fails transiently in exactly the situations
    elastic training creates (restarted coordinator not listening yet, a peer
    of the previous generation still holding the port).  Attempts/backoff
    come from the env the elastic agent exports (``DSTPU_INIT_RETRIES`` /
    ``DSTPU_INIT_RETRY_BACKOFF_S``), falling back to the module defaults
    config set via :func:`set_init_retry_defaults`; the last failure
    propagates unchanged."""
    retries = max(env_int(INIT_RETRIES_ENV, _DEFAULT_INIT_RETRIES), 0)
    backoff = max(env_float(INIT_RETRY_BACKOFF_ENV, _DEFAULT_INIT_RETRY_BACKOFF_S), 0.0)
    kwargs = {} if timeout is None else {"initialization_timeout": timeout}
    for attempt in range(retries + 1):
        try:
            jax.distributed.initialize(coordinator_address=coord, num_processes=nproc,
                                       process_id=pid, **kwargs)
            return
        except Exception as exc:
            if attempt >= retries:
                raise
            # a failed initialize leaves jax's global distributed state
            # assigned (client, and on rank 0 the coordinator service), so
            # without a reset every later attempt would die on 'distributed
            # .initialize should only be called once' instead of retrying
            try:
                jax.distributed.shutdown()
            except Exception as reset_exc:
                logger.debug(f"init_distributed: state reset between retries "
                             f"raised {reset_exc!r} (continuing)")
            delay = backoff * (2 ** attempt)
            logger.warning(f"init_distributed: attempt {attempt + 1}/{retries + 1} "
                           f"failed ({exc!r}); retrying in {delay:.2f}s")
            if delay > 0:
                time.sleep(delay)


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank(group=None) -> int:
    """Rank within ``group`` (a comm.groups.ProcessGroup) or the process index
    (reference comm.py:547 — group=None means the world group)."""
    if group is not None and hasattr(group, "rank"):
        return group.rank()
    return jax.process_index()

def get_world_size(group=None) -> int:
    """Size of ``group`` (device count over its mesh axes) or the host-process
    world size (device-level parallelism is the mesh's business)."""
    if group is not None and hasattr(group, "size"):
        return group.size()
    return jax.process_count()


def get_local_rank() -> int:
    return 0  # one process per host owns all local chips in JAX


def barrier(group=None, timeout_s: Optional[float] = None):
    """Synchronize all processes/devices (reference comm.py:521).

    Bounded: with a resolved timeout (arg > config/env default) a barrier a
    peer never reaches raises :class:`CollectiveTimeoutError` instead of
    blocking forever; the heartbeat records 'in barrier' either way."""

    def _sync():
        x = jnp.zeros(())
        x.block_until_ready()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("dstpu_barrier")

    return bounded_collective(_sync, timeout_s=timeout_s, name="barrier")


# --------------------------------------------------------------------------
# In-graph collectives (usable under shard_map / pjit with named mesh axes)
# --------------------------------------------------------------------------

AxisArg = Union[str, Sequence[str]]  # or a comm.groups.ProcessGroup


def _axes(axis):
    """Unwrap a ProcessGroup into its mesh-axes tuple (lax takes str|tuple)."""
    ax = getattr(axis, "axes", axis)
    return ax if isinstance(ax, str) else tuple(ax)


def _trace_log(op: str, x) -> None:
    cl = get_comms_logger()
    if cl.should_profile(op):
        try:
            cl.record_traced(op, int(np.prod(x.shape)) * x.dtype.itemsize)
        except Exception as exc:  # odd operand (no shape/dtype): skip the sample
            warning_once(f"comms logger: could not size a traced {op} operand "
                         f"({exc!r}); that collective is missing from the summary")


def all_reduce(x, axis: AxisArg, op: str = "sum"):
    """lax.psum/pmax/pmin over a mesh axis (reference comm.py:478 all_reduce)."""
    _trace_log("all_reduce", x)
    axis = _axes(axis)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "avg" or op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(x, axis: AxisArg, *, tiled: bool = True, gather_dim: int = 0):
    """Gather shards along a mesh axis (reference all_gather_into_tensor comm.py:308).
    tiled=True concatenates along ``gather_dim`` (the flat-bucket layout ZeRO uses)."""
    _trace_log("all_gather", x)
    return lax.all_gather(x, _axes(axis), axis=gather_dim, tiled=tiled)


def reduce_scatter(x, axis: AxisArg, *, scatter_dim: int = 0, tiled: bool = True):
    """Reduce + scatter shards (reference reduce_scatter_fn comm.py:246)."""
    _trace_log("reduce_scatter", x)
    return lax.psum_scatter(x, _axes(axis), scatter_dimension=scatter_dim, tiled=tiled)


def all_to_all(x, axis: AxisArg, *, split_dim: int, concat_dim: int, tiled: bool = True):
    """All-to-all over a mesh axis (reference all_to_all_single comm.py:334) —
    the Ulysses/MoE dispatch primitive."""
    _trace_log("all_to_all", x)
    return lax.all_to_all(x, _axes(axis), split_axis=split_dim, concat_axis=concat_dim, tiled=tiled)


def ppermute(x, axis: AxisArg, perm):
    """Point-to-point ring shift — the TPU-native analog of pipeline p2p send/recv
    (reference runtime/pipe/p2p.py:50,71); perm is [(src, dst), ...]."""
    _trace_log("ppermute", x)
    return lax.ppermute(x, _axes(axis), perm)


def axis_index(axis: AxisArg):
    if hasattr(axis, "axis_index"):
        return axis.axis_index()  # ProcessGroup: linearized over its axes
    return lax.axis_index(axis)


def broadcast(x, axis: AxisArg, src: int = 0):
    """Broadcast the src rank's shard to all ranks on the axis (comm.py:222).
    Implemented as select + psum (ppermute requires unique sources; select rather
    than multiply so non-src NaN/Inf shards cannot poison the sum)."""
    _trace_log("broadcast", x)
    axis = _axes(axis)
    idx = lax.axis_index(axis)
    contribution = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(contribution, axis)


# --------------------------------------------------------------------------
# Host-level (eager) collectives over the global topology
# --------------------------------------------------------------------------


def _timed(op_name):

    def deco(fn):

        @functools.wraps(fn)
        def wrapper(*args, log_name=None, **kwargs):
            cl = get_comms_logger()
            if not cl.should_profile(op_name):
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            x = args[0]
            size = int(np.prod(np.shape(x))) * jnp.asarray(x).dtype.itemsize
            world = get_topology().world_size
            cl.append(op_name, log_name or op_name, dt, size, world)
            return out

        return wrapper

    return deco


_REDUCERS = {
    "sum": jnp.sum,
    "avg": jnp.mean,
    "mean": jnp.mean,
    "max": jnp.max,
    "min": jnp.min,
    "prod": jnp.prod,
}


@functools.lru_cache(maxsize=None)
def _host_reduce_fn(op: str):
    reducer = _REDUCERS[op]
    return jax.jit(lambda v: reducer(v, axis=0))


@_timed("all_reduce")
def host_all_reduce(x, topo: Optional[MeshTopology] = None, op: str = "sum"):
    """Eager reduction over the leading ("per-contributor") axis of a global array.

    In single-controller JAX, arrays are globally consistent — there is no eager
    per-rank value to reduce the way torch.distributed.all_reduce does.  The
    control-plane uses (overflow consensus, loss averaging) stack contributions on
    the leading axis; in-graph consensus belongs inside the jitted step via
    ``all_reduce``.  The jitted reducer is cached per op (no per-call retrace).
    """
    if op not in _REDUCERS:
        raise ValueError(f"unsupported reduce op {op!r}; one of {sorted(_REDUCERS)}")
    if jnp.ndim(x) == 0:
        raise ValueError("host_all_reduce expects a leading contributor axis; got a scalar")
    return _host_reduce_fn(op)(x)


def host_broadcast(x, topo: Optional[MeshTopology] = None):
    """Replicate a host value across all devices (reference _broadcast_model
    engine.py:1052 analog: rank0's value wins; with SPMD jax arrays the host value
    is already consistent, so this is a device_put with replicated sharding)."""
    topo = topo or get_topology()
    return jax.device_put(x, topo.replicated())


def log_summary(show_straggler=False):
    """Reference dist.log_summary (comm/comm.py:422)."""
    return get_comms_logger().log_summary(show_straggler=show_straggler)


def monitor_events(step: int = 0):
    """Comms-logger summary as monitor ``(tag, value, step)`` events, for the
    telemetry collector's event stream (empty when nothing was profiled)."""
    return get_comms_logger().as_events(step)


def configure(comms_config=None):
    if comms_config is not None:
        get_comms_logger().configure(comms_config)
