"""How much of the block tables the paged kernel's grid walked named a live
sequence's own block: the tables are as wide as the longest row needs, and as
tall as the bucket.  Both counts are the program's own (``ServeCounters``); a
program without them gives nothing."""


def read(run):
    if run.kind != "serve" or not run.counters.get("table_slots"):
        return None
    live, slots = run.counters["live_blocks"], run.counters["table_slots"]
    return 100.0 * live / slots, {"live_blocks": live, "table_slots": slots}
