"""Device time by the scope an operation belongs to: the program's own table
laid over the trace's operation line.

The program says, for each of its compiled programs, which ``jax.named_scope``
every instruction lies under (``deepspeed_tpu/monitor/program_scopes.py``: read
off the executables the engines hold, asked for here AFTER the window).  The
trace names a device operation by its instruction (``%fusion.735 bf16[..]``)
and the program that ran by its module (``jit_fwd_n32_t1_b20(123)``), so:

- an operation event belongs to the program event that covers its start;
- its instruction is the first word of its name less ``%``;
- its time is its self time (``xplane.self_times``, a program's operations at
  a time: they nest inside one program and never across two), containers
  (``while``, ``conditional``, ``call``) left out as everywhere else;
- it is ``scoped`` (a name of ``SCOPES`` on its path: counted under the whole
  path), ``unscoped`` (program known, no scope on the path: kept by program and
  operation), or ``unattributed`` (no program event covers it, the program has
  no table, the table has no such instruction, or executables of one name
  disagree on it); ``mixed`` is the part of the scoped time spent in fusions
  that straddle a scope's boundary, ``inherited`` the part spent in operations
  that carry no scope themselves and are read under one scope alone (a layer
  scan's slice of the stacked weights).

The arithmetic (:func:`split_events`) works on plain lists and a plain
``{program: {instruction: path}}``, so it can be checked on a hand-built list;
:func:`split` is what the readers ask, once a run.  The readers first import
the program (as ``reduce/setup_account.py`` does): a program without
``program_scopes`` (the parent of the PR that brought it) is nothing to read.
"""

import bisect
import time

from chipbench.reduce import xplane

# every scope a serving program can hold, in exactly one group; an operation's
# group is the first of these, in this order, that has a name on its path (so a
# dense FFN inside an expert scope is the experts', a family's FFN inside a
# mixer layer is not the mixer's, and what is left of ``layer_finish`` is
# attention's: the output projection, the residuals and the norms)
GROUPS = (
    ("expert", ("moe_route", "moe_expert_ffn", "moe_shared_expert", "moe_shared_gate",
                "moe_identity", "scmoe_shortcut")),
    ("dense_ffn", ("dense_ffn", )),
    ("mixer", ("mixer_layer", "conv_mixer", "gdn_mixer", "gdn_scan", "gdn_state", "ssm_mixer",
               "ssm_scan", "ssm_update", "ssm_state", "seq_state")),
    ("head", ("embed", "head", "pick")),
    ("attention", ("attn_qkv", "kv_write", "attn_kernel", "layer_finish", "mla_absorb",
                   "dsa_index", "dsa_select", "attn_gate")),
)
TRAIN = ("forward_backward", "grad_norm_clip", "optimizer")
# kernels a note names inside their scope: an event's instruction begins with the kernel's name
KERNELS = ("paged_attention", "kv_write", "gmm", "moe_combine", "ssd_scan", "ssd_update",
           "gdn_scan", "dsa_index_scores")


def group_of(path):
    """The group of an operation under ``path``; None with no serving scope on it."""
    for group, names in GROUPS:
        if any(name in names for name in path):
            return group
    return None


def innermost(path, group):
    """The innermost name of ``path`` that is ``group``'s: what a note lists apart."""
    names = dict(GROUPS)[group]
    return next(name for name in reversed(path) if name in names)


def program_of(module_event: str) -> str:
    """``fwd_n32_t1_b20`` of the trace's ``jit_fwd_n32_t1_b20(123)``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def instruction_of(op_event: str) -> str:
    """``fusion.735`` of ``%fusion.735 bf16[32,1,16768]``."""
    return op_event.split(" ", 1)[0].lstrip("%")


def by_program(ops, modules):
    """``[(program or None, [op events])]``: each operation event with the
    program event that covers its start, the operations no program covers
    under None."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    groups = {}
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        covered = i >= 0 and op[1] < modules[i][1] + modules[i][2]
        groups.setdefault(i if covered else None, []).append(op)
    return [(None if i is None else program_of(modules[i][0]), events)
            for i, events in groups.items()]


def split_events(ops, modules, tables):
    """One device's time by scope, in ns: ``{"paths": {path: ns}, "kernels":
    {(scope, kernel): ns}, "unscoped": {(program, operation): ns},
    "unattributed": ns, "mixed": ns, "inherited": ns, "largest": [(operation, ns, path or None)]}``."""
    out = {"paths": {}, "kernels": {}, "unscoped": {}, "unattributed": 0, "mixed": 0, "inherited": 0}
    largest = {}
    for program, events in by_program(ops, modules):
        table = tables.get(program)
        for name, own in xplane.self_times(events):
            if xplane.CONTAINER.search(name) or own <= 0:
                continue
            instruction = instruction_of(name)
            path = None if table is None else table.get(instruction)
            if path is None or getattr(path, "ambiguous", False):
                out["unattributed"] += own
                path = None
            elif not path:
                out["unscoped"][program, name] = out["unscoped"].get((program, name), 0) + own
            else:
                key = tuple(path)
                out["paths"][key] = out["paths"].get(key, 0) + own
                if getattr(path, "mixed", ()):
                    out["mixed"] += own
                if getattr(path, "inherited", False):
                    out["inherited"] += own
                kernel = next((k for k in KERNELS if instruction.startswith(k)), None)
                if kernel:
                    at = key[-1], kernel
                    out["kernels"][at] = out["kernels"].get(at, 0) + own
            seen = largest.setdefault((program, name), [0, path])
            seen[0] += own
    out["largest"] = sorted(((name, ns, None if path is None else tuple(path))
                             for (_, name), (ns, path) in largest.items()),
                            key=lambda item: -item[1])[:10]
    return out


def programs_run(devices):
    return sorted({program_of(name) for d in devices.values() for name, _, _ in d["modules"]})


def split(run):
    """``{"devices": {device: split_events(...)}, "tables_s", "programs",
    "tables", "largest_table"}`` of a traced run, computed once a run; None
    where the program has no ``program_scopes``, the run no trace, or no
    program that ran has a table."""
    if getattr(run, "trace", None) is None:
        return None
    if getattr(run, "scope_split", None) is not None:
        return run.scope_split or None
    try:
        from deepspeed_tpu.monitor import program_scopes
    except ImportError:
        return None
    devices = run.trace.devices
    names = programs_run(devices)
    t = time.perf_counter()
    tables = program_scopes.tables(names)
    tables_s = time.perf_counter() - t
    run.scope_split = False  # asked, and nothing found: the next reader does not ask again
    if tables:
        run.scope_split = {
            "devices": {name: split_events(d["ops"], d["modules"], tables) for name, d in devices.items()},
            "tables_s": tables_s, "programs": len(names), "tables": len(tables),
            "largest_table": max(len(table) for table in tables.values())}
    return run.scope_split or None


def seconds(found, key):
    """A scalar of every device's split (``unattributed``, ``mixed``, ``inherited``),
    averaged, in seconds."""
    return sum(d[key] for d in found["devices"].values()) / 1e9 / len(found["devices"])


def summed(found, key):
    """A dictionary of every device's split (``paths``, ``kernels``, ``unscoped``),
    averaged over the devices, in seconds."""
    out = {}
    for d in found["devices"].values():
        for k, ns in d[key].items():
            out[k] = out.get(k, 0.0) + ns / 1e9 / len(found["devices"])
    return out


def group_seconds(found, group):
    """``(seconds, {innermost scope: seconds})`` of one group."""
    by_scope = {}
    for path, s in summed(found, "paths").items():
        if group_of(path) == group:
            name = innermost(path, group)
            by_scope[name] = by_scope.get(name, 0.0) + s
    return sum(by_scope.values()), by_scope


def group_share(run, group):
    """What the five group readers return: the group's share of the traced
    wave's busy time, each of its scopes' and kernels' seconds in the note."""
    found = split(run) if getattr(run, "kind", None) == "serve" else None
    if found is None or run.trace.busy_s <= 0:
        return None
    total, by_scope = group_seconds(found, group)
    note = {"group_s": round(total, 4), "busy_s": round(run.trace.busy_s, 4)}
    note.update((f"{name}_s", round(s, 4)) for name, s in sorted(by_scope.items()))
    note.update((f"{scope}.{kernel}_s", round(s, 4))
                for (scope, kernel), s in sorted(summed(found, "kernels").items())
                if scope in dict(GROUPS)[group])
    return 100.0 * total / run.trace.busy_s, note
