"""The benchmark's command.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips.  It reads the cell from
``BENCHMARK.json``, its configuration from ``chipbench/configs/``, its traffic
from ``chipbench/traffic/`` (+ the generator the file names), runs the entry
point the configuration names (``chipbench/entries/``), has each of the cell's
metrics computed by its own reader (``chipbench/metrics/`` + ``readers/``), and
prints one JSON object as its last line.  No cell, model or metric is named in
this file.

A run that cannot be a measurement (no TPU, fewer chips than the cell asks
for, a device with no recorded peak, interpreted kernels) exits non-zero and
prints no result.  ``--rehearse`` walks the same control flow on the CPU at the
tiny widths of the configuration's ``rehearsal`` block with interpreted
kernels; it prints what it computed on a line that is not a result and exits 3.
``--control 1`` runs the configuration's precision control in the program's
place: its ``correct`` has to come out false.
"""

import time

T_START = time.perf_counter()  # before anything heavy is imported: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_metrics(bench: dict, cell: str, traced: bool):
    """The metric entries this run reports: the cell's end-to-end metrics
    without the profiler, its per-layer metrics with it."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"chipbench: no workload {args.workload!r}; known: {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]

    # the machine's cap on the compile cache (192 MiB) is smaller than one
    # cell's programs, and an LRU cache smaller than its working set never hits
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:  # else the caller's stands
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={cell['chips']}").strip()

    from chipbench import common
    from chipbench.common import Refused, say
    from chipbench.reduce.peaks import peaks_for
    import jax
    from deepspeed_tpu.ops import _pallas
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    config = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    os.makedirs(common.OUT, exist_ok=True)
    try:
        if args.rehearse:
            _pallas.INTERPRET = True
            peaks = next(iter(common.load_json("reduce", "peaks.json").values()))
        else:
            place_compile_cache(ROOT)
            # small programs too: every run is a new process and meets them all again
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = jax.devices()
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices)}
        say("device", **device, jax=jax.__version__, cell=cell["name"], seed=args.seed,
            seconds=args.seconds, trace=args.trace,
            compile_cache=jax.config.jax_compilation_cache_dir
            or os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        if not args.rehearse:
            if device["platform"] != "tpu":
                raise Refused(f"JAX found no TPU (platform {device['platform']!r})")
            if _pallas.INTERPRET:
                raise Refused("the kernels are interpreted")
            peaks = peaks_for(device["kind"])
        if len(devices) < cell["chips"]:
            raise Refused(f"the cell asks for {cell['chips']} chips, JAX found {len(devices)}")
    except (Refused, KeyError) as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2

    ctx = common.new_run(args=args, cell=cell, config=config, traffic=traffic,
                         sizes=common.published_sizes(config, args.rehearse),
                         devices=devices[:cell["chips"]], peaks=peaks, t_start=T_START)
    run = common.load_module("entries", config["entry"]).run(ctx)

    metrics = {}
    for entry in cell_metrics(bench, cell["name"], bool(args.trace)):
        spec = common.load_json("metrics", entry["name"] + ".json")
        found = common.load_module("readers", spec["reader"]).read(run)
        if found is None:
            say("metric", name=entry["name"], value="nothing to read")
            continue
        value, note = found if isinstance(found, tuple) else (found, {})
        say("metric", name=entry["name"], value=value, unit=entry["unit"], **note)
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    device["memory_peak_bytes"] = run.memory_peak_bytes
    result = {"correct": run.correct, "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"], device["window_s"] = run.trace.busy_s, run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    if args.rehearse:
        print("[rehearsal-not-a-result] " + json.dumps(
            dict(result, correct=False, would_be_correct=run.correct)), flush=True)
        print("chipbench: rehearsal walked the control flow on the CPU with interpreted "
              "kernels; this is not a chip run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
