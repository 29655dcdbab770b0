"""The share of the window's picks that fell on identity experts: the program's
own tally (``ServeCounters.moe_identity_picks``, summed on the device and read
once a wave) over every pick of the window's live tokens (``moe_routed_rows`` =
live tokens x ``moe_topk`` x layers).  A program without the tally (a family
with no identity experts, or a parent commit) gives nothing."""


def read(run):
    counters = getattr(run, "counters", None) or {}
    if run.kind != "serve" or "moe_identity_picks" not in counters:
        return None
    identity, held = counters["moe_identity_picks"], counters.get("moe_held_picks", 0)
    picks = counters.get("moe_routed_rows", 0)
    if picks <= 0:
        return None
    return 100.0 * identity / picks, {"moe_identity_picks": identity, "moe_held_picks": held,
                                      "held_elsewhere": picks - identity - held, "picks": picks}
