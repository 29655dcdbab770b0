"""How full the paged kernel's q was: tokens that advanced a sequence over the
token positions the ATTENTION layout of the launched passes held (n x t a padded
pass; a compacted one its S flat slots and the few positions that begin each
sequence on a whole tile of q rows, since PR 40).  Both counts are the program's
own (``ServeCounters``); a program without ``attn_token_slots`` (before PR 40),
or a window that advanced no token, gives nothing."""


def read(run):
    if run.kind != "serve":
        return None
    live, slots = run.counters.get("live_tokens"), run.counters.get("attn_token_slots")
    if not (live and slots):
        return None
    return 100.0 * live / slots, {"live_tokens": live, "attn_token_slots": slots}
