"""How sparse the traffic made the attention: of the keys plain causal
attention would see, the share the selection keeps (``dsa_selected_keys /
dsa_causal_keys`` of the window: ``min(position + 1, index_topk)`` over
``position + 1``, summed over live query tokens and layers).  It says what the
traffic is, and moves only if the lengths do."""


def read(run):
    counters = getattr(run, "counters", None) or {}
    if run.kind != "serve" or not counters.get("dsa_causal_keys"):
        return None
    return (100.0 * counters.get("dsa_selected_keys", 0) / counters["dsa_causal_keys"],
            {"selected_keys": counters.get("dsa_selected_keys", 0),
             "causal_keys": counters["dsa_causal_keys"]})
