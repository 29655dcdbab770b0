"""Collective bandwidth microbenchmarks — the ``ds_bench`` analog.

The reference ships ``bin/ds_bench`` (driving DeepSpeedExamples' comm sweep) and
tracks allgather bucket bandwidth as a tuning signal (allgather_bucket_size 5e8,
runtime/zero/config.py:105,124).  Here each op is timed as a jitted shard_map
collective over the live topology: the reported **algbw** is message_bytes/time
and **busbw** applies the standard ring-correction factor ((n-1)/n for
allgather/reduce-scatter, 2(n-1)/n for allreduce) so numbers are comparable to
NCCL-tests / the reference's CommsLogger accounting (utils/comms_logging.py:67).
"""

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..compat import shard_map
from ..parallel.mesh import MeshTopology, get_topology
from . import comm


def _sync(x):
    # a value fetch on top of block_until_ready: the window closes only once
    # a result has reached the host
    jax.block_until_ready(x)
    leaf = jax.tree_util.tree_leaves(x)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def _time_op(fn, x, iters: int) -> float:
    # always re-feed the ORIGINAL input: the output's sharding generally differs
    # from in_specs, and feeding it back would hide a reshard+recompile inside
    # the timed region. Dispatch is async, so iterations still pipeline.
    out = fn(x)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def collective_bandwidth(op: str = "all_gather",
                         elems: int = int(5e8 // 2),
                         dtype=jnp.bfloat16,
                         axis: str = "data",
                         topology: Optional[MeshTopology] = None,
                         iters: int = 10,
                         compiled_loop: bool = False) -> Dict[str, float]:
    """Measure one collective's bandwidth over a mesh axis.

    ``elems`` is the GLOBAL bucket element count (default = the reference's
    5e8-element allgather bucket in bf16 bytes).  Returns {time_ms, algbw_gbps,
    busbw_gbps, world, bytes}.

    ``compiled_loop`` runs all ``iters`` inside ONE jitted fori_loop with a
    chained carry — use it where per-call dispatch round-trips would
    otherwise dominate the timing.
    """
    topo = topology or get_topology()
    world = topo.axis_size(axis)
    mesh = topo.mesh
    elems = int(elems) // (world * 128) * (world * 128) or world * 128
    itemsize = jnp.dtype(dtype).itemsize
    spec_sharded = PartitionSpec(axis)
    spec_rep = PartitionSpec()

    if op == "all_gather":
        in_spec, out_spec = spec_sharded, spec_rep
        body = lambda x: comm.all_gather(x, axis)
        factor = (world - 1) / world
    elif op == "reduce_scatter":
        in_spec, out_spec = spec_rep, spec_sharded
        body = lambda x: comm.reduce_scatter(x, axis)
        factor = (world - 1) / world
    elif op == "all_reduce":
        in_spec, out_spec = spec_rep, spec_rep
        body = lambda x: comm.all_reduce(x, axis)
        factor = 2 * (world - 1) / world
    elif op == "all_to_all":
        in_spec, out_spec = spec_sharded, spec_sharded
        body = lambda x: comm.all_to_all(x, axis, split_dim=0, concat_dim=0)
        factor = (world - 1) / world
    else:
        raise ValueError(f"unknown op {op!r}")

    if compiled_loop:
        # the whole iteration loop in one program: the per-shard input is fed
        # through the collective, and a slice of each result perturbs the next
        # input so XLA cannot elide the repeats
        from jax import lax

        def looped(x):
            def step(i, acc):
                out = body(acc)
                return acc + out.ravel()[0] * 0.0  # depend on this iteration
            return lax.fori_loop(0, iters, step, x)

        shard_fn = jax.jit(
            shard_map(looped, mesh=mesh, in_specs=in_spec, out_specs=in_spec,
                      check_vma=False))
        x = jax.device_put(jnp.zeros((elems,), dtype), NamedSharding(mesh, in_spec))
        _sync(shard_fn(x))  # compile + settle
        t0 = time.perf_counter()
        _sync(shard_fn(x))
        dt = (time.perf_counter() - t0) / iters
    else:
        shard_fn = jax.jit(
            shard_map(body, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                      check_vma=False))
        x = jax.device_put(jnp.zeros((elems,), dtype),
                           NamedSharding(mesh, in_spec))
        dt = _time_op(shard_fn, x, iters)
    nbytes = elems * itemsize
    algbw = nbytes / dt / 1e9
    return {
        "op": op,
        "time_ms": dt * 1e3,
        "algbw_gbps": algbw,
        "busbw_gbps": algbw * factor,
        "world": world,
        "bytes": nbytes,
    }


def run_sweep(ops=("all_gather", "all_reduce", "reduce_scatter", "all_to_all"),
              elems: int = int(5e8 // 2), axis: str = "data",
              topology: Optional[MeshTopology] = None, iters: int = 10):
    """Sweep the standard ops at the reference bucket size; returns a list of
    result dicts (and prints a table when run as a CLI via bin/dstpu_bench)."""
    topo = topology or get_topology()
    if topo.axis_size(axis) <= 1:
        return []
    return [collective_bandwidth(op, elems=elems, axis=axis, topology=topo, iters=iters)
            for op in ops]


def main(argv=None):
    import argparse
    parser = argparse.ArgumentParser(description="dstpu collective microbench (ds_bench analog)")
    parser.add_argument("--elems", type=float, default=5e8 / 2)
    parser.add_argument("--axis", default="data")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--ops", nargs="*", default=["all_gather", "all_reduce", "reduce_scatter", "all_to_all"])
    args = parser.parse_args(argv)
    from ..parallel.mesh import set_topology
    try:
        topo = get_topology()
    except Exception:
        topo = MeshTopology.from_axis_dict({args.axis: jax.device_count()})
        set_topology(topo)
    results = run_sweep(args.ops, elems=int(args.elems), axis=args.axis, topology=topo, iters=args.iters)
    if not results:
        print(f"axis {args.axis!r} has world size 1 — nothing to measure")
        return
    print(f"{'op':<16}{'bytes':>14}{'time_ms':>10}{'algbw GB/s':>12}{'busbw GB/s':>12}")
    for r in results:
        print(f"{r['op']:<16}{r['bytes']:>14}{r['time_ms']:>10.2f}{r['algbw_gbps']:>12.2f}{r['busbw_gbps']:>12.2f}")


if __name__ == "__main__":
    main()
