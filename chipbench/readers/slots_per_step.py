"""How many table slots one grid step of the paged kernel took: the block-table
entries its grid walked over the steps of that grid along the table.  Both
counts are the program's own (``ServeCounters``); a program without
``kernel_steps`` (one table slot a step, before PR 35) gives nothing."""


def read(run):
    if run.kind != "serve" or not run.counters.get("kernel_steps"):
        return None
    slots, steps = run.counters.get("table_slots", 0), run.counters["kernel_steps"]
    return slots / steps, {"table_slots": slots, "kernel_steps": steps}
