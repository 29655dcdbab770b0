"""The share of the device's busy time spent in operations that carry a scope
of the program's ``SCOPES``: how much of the busy time the five ``scope.*``
group shares account for (they add up to this).  The note says where the rest
is: ``unscoped_s`` (the program is known and no scope lies on the operation's
path; the five largest by program), ``unattributed_s`` (no table for the
program, or no such instruction in it), ``mixed_s`` (scoped time in fusions
that straddle a scope's boundary), ``inherited_s`` (scoped time of operations
with no scope of their own that one scope alone reads) and ``tables_s`` (what asking the program for
its tables cost, after the window)."""

from chipbench.reduce import scopes


def read(run):
    found = scopes.split(run) if getattr(run, "kind", None) == "serve" else None
    if found is None or run.trace.busy_s <= 0:
        return None
    scoped = sum(s for path, s in scopes.summed(found, "paths").items() if scopes.group_of(path))
    unscoped = scopes.summed(found, "unscoped")
    top = sorted(unscoped.items(), key=lambda item: -item[1])[:5]
    return 100.0 * scoped / run.trace.busy_s, {
        "scoped_s": round(scoped, 4), "busy_s": round(run.trace.busy_s, 4),
        "unscoped_s": round(sum(unscoped.values()), 4),
        "unattributed_s": round(scopes.seconds(found, "unattributed"), 4),
        "mixed_s": round(scopes.seconds(found, "mixed"), 4),
        "inherited_s": round(scopes.seconds(found, "inherited"), 4),
        "top_unscoped": ",".join(f"{program}:{op.replace(' ', ':')}:{s:.4f}"
                                 for (program, op), s in top),
        "tables_s": round(found["tables_s"], 3), "programs": found["programs"],
        "tables": found["tables"], "largest_table": found["largest_table"]}
