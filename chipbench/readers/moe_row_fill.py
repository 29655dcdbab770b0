"""How full the expert FFNs' grouped matmuls were: rows a live token was
routed to over the rows those matmuls ran over.  Both counts are the program's
own (``ServeCounters.moe_routed_rows`` / ``moe_expert_rows``); a program
without them, or a dense model whose counts stay zero, gives nothing."""


def read(run):
    if run.kind != "serve" or not run.counters.get("moe_expert_rows"):
        return None
    routed, rows = run.counters["moe_routed_rows"], run.counters["moe_expert_rows"]
    return 100.0 * routed / rows, {"moe_routed_rows": routed, "moe_expert_rows": rows}
