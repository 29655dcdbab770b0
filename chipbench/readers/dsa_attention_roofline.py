"""The attention kernel's share of the roofline of the SELECTED pairs: the
least time the chip could take for the attention over the keys the traced wave
selected (``chipbench/reduce/dsa_shapes.py``, from the engine's
``dsa_selected_keys``: the mathematics' operations, whatever implements it)
over the device time of the events named ``paged_attention``.  A kernel that
walks unselected keys and masks them reads low by as much: that is the finding."""

from chipbench.reduce import dsa_shapes

KERNEL = "paged_attention"


def read(run):
    counters = getattr(run, "counters", None) or {}
    if (run.kind != "serve" or run.trace is None or not dsa_shapes.is_family(run.sizes)
            or not counters.get("dsa_selected_keys")):
        return None
    spent = run.trace.kernel_seconds(KERNEL)
    if spent <= 0:
        return None
    queries = counters.get("live_tokens", 0) * run.sizes["num_hidden_layers"]
    least = dsa_shapes.attention_least_seconds(run.sizes, counters["dsa_selected_keys"], queries,
                                               run.peaks)
    bound = max((k for k in least if k != "seconds"), key=least.get)
    return 100.0 * least["seconds"] / spent, {"kernel_s": round(spent, 4), "mostly": bound,
                                              **{k: round(v, 5) for k, v in least.items()}}
