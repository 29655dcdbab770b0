"""What both entry points share: printing, files, the program's model
configuration, the device block, the profiler window."""

import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


class Refused(Exception):
    """The run cannot be a measurement (no chip, too few chips, an unknown
    device, an interpreted kernel): exit non-zero, print no result."""


def say(tag: str, **facts) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, found by the name a data file gives."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"chipbench.{kind}.{name}")


def published_sizes(config: dict, rehearse: bool) -> dict:
    """The configuration's own value under every key that the source model
    publishes (``chipbench/published/<model>.json``, the source's size keys):
    whatever the architecture calls its sizes reaches the program's key map
    and the reference with no list of names kept here."""
    published = load_json("published", config["published"] + ".json")["config"]
    sizes = {k: config[k] for k in published}
    if rehearse:
        sizes.update(config["rehearsal"]["sizes"])
    return sizes


def correct_limits(config: dict, rehearse: bool) -> dict:
    """The limits ``correct`` is held to: the configuration's, read on the
    chip at the cell's own size; a rehearsal's tiny size has its own."""
    limits = dict(config["correct"])
    if rehearse:
        limits.update(config["rehearsal"].get("correct", {}))
    return limits


def program_model(config: dict, sizes: dict, **overrides):
    """The program's model module and its configuration object, built from
    the published sizes through the key map the configuration file gives."""
    spec = config["program"]
    module = importlib.import_module(spec["model_module"])
    kwargs = {theirs: sizes[ours] for ours, theirs in spec["config_keys"].items()}
    kwargs.update(overrides)
    return module, getattr(module, spec["config_class"])(**kwargs)


def count_params(tree) -> int:
    """Every element of a tree of arrays or of shapes: the model's size as it
    was drawn, whatever its architecture."""
    import jax
    return sum(math.prod(leaf.shape) for leaf in jax.tree_util.tree_leaves(tree))


def memory_peak_bytes(devices) -> int:
    stats = [d.memory_stats() for d in devices]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


@contextlib.contextmanager
def profiler_window(trace_dir: str):
    """One profiler trace into a directory that is emptied first."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # TraceAnnotations stay (host tracer); Python frames do not
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reduce_trace(trace_dir: str, annotations, rehearse: bool = False):
    """The traced window reduced; None in a rehearsal, whose CPU trace holds
    no accelerator plane to reduce."""
    from chipbench.reduce import xplane
    path = xplane.find_xplane(trace_dir)
    loaded = xplane.load(path)
    say("trace", file=os.path.relpath(path, ROOT), bytes=os.path.getsize(path),
        device_planes=len(loaded["devices"]), host_events=len(loaded["host"]))
    with open(os.path.join(OUT, "trace_lines.json"), "w") as f:
        json.dump(loaded["lines"], f, indent=1)
    if rehearse and not loaded["devices"]:
        return None
    return xplane.Reduction(loaded, annotations)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values: no interpolation, no trimming."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))  # ceil
    return ordered[int(rank) - 1]


def new_run(**fields):
    return types.SimpleNamespace(**fields)
