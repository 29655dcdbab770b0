"""How full the chunks of the Gated DeltaNet layers' scans were: the positions
that held a live token over the positions of the chunks walked, from the
engine's counters (``scan_live_positions`` / ``scan_positions``: every sequence
of a compacted pass begins on a chunk's edge, a decode row beside a chunk takes
a chunk of its own, and the chunks a bucket's shape allows past the live ones
are walked empty).  A program without those counters, or whose steps walk no
chunk, gives nothing."""


def read(run):
    counters = getattr(run, "counters", None) or {}
    if run.kind != "serve" or not counters.get("scan_positions"):
        return None
    return 100.0 * counters["scan_live_positions"] / counters["scan_positions"], {
        "chunks": counters["scan_chunks"], "positions": counters["scan_positions"],
        "live_positions": counters["scan_live_positions"]}
