"""How full the shortcut expert layers' grouped matmuls TRULY were: the picks on
experts held here (``ServeCounters.moe_held_picks``, tallied on the device) over
the rows those matmuls ran over (``moe_expert_rows``, from static shapes).
``moe.row_fill`` counts every pick as a row, also one on an identity expert or
on an expert another chip holds, which multiply nothing: on a share it reads
the slots' fill, not the matmuls'.  A program without the tally gives nothing."""


def read(run):
    counters = getattr(run, "counters", None) or {}
    if run.kind != "serve" or "moe_held_picks" not in counters or not counters.get("moe_expert_rows"):
        return None
    held, rows = counters["moe_held_picks"], counters["moe_expert_rows"]
    return 100.0 * held / rows, {"moe_held_picks": held, "moe_expert_rows": rows}
