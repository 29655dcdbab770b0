"""Key rows the attention multiplied a selected one (``dsa_attended_keys /
dsa_selected_keys`` of the window): 1 where the attention touches only what was
selected, ``1 / dsa.selected_share`` and more where it walks the whole cache
and masks (whole steps of blocks up to a sequence's length)."""


def read(run):
    counters = getattr(run, "counters", None) or {}
    if run.kind != "serve" or not counters.get("dsa_selected_keys"):
        return None
    return (counters.get("dsa_attended_keys", 0) / counters["dsa_selected_keys"],
            {"attended_keys": counters.get("dsa_attended_keys", 0),
             "selected_keys": counters["dsa_selected_keys"],
             "scored_keys": counters.get("dsa_scored_keys", 0)})
