"""Share of the pool's live (block, layer) pairs that lie wholly behind a
windowed layer's window (``kv_blocks_behind_window`` over ``live_blocks`` times
the layers of both kinds): what an allocator that frees a windowed layer's
blocks would hold no longer.  Which layers are windowed the published sizes say
(``chipbench/reduce/swa_shapes.py``); nothing to read from a program that does
not count the blocks."""

from chipbench.reduce import swa_shapes


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or not swa_shapes.is_family(run.sizes) or not counters.get("live_blocks")
            or "kv_blocks_behind_window" not in counters):
        return None
    windows = swa_shapes.layer_windows(run.sizes)
    return (100.0 * counters["kv_blocks_behind_window"] / (counters["live_blocks"] * len(windows)),
            {"blocks_behind_window": counters["kv_blocks_behind_window"],
             "live_blocks": counters["live_blocks"], "layers": len(windows),
             "window_layer_share": round(sum(w is not None for w in windows) / len(windows), 4)})
