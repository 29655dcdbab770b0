"""Counts of a decoder whose attention layers are of two kinds, windowed and
full (Arcee's ``afmoe``: ``layer_types`` of ``sliding_attention`` /
``full_attention`` beside one ``sliding_window``), from the published sizes and
a wave's lengths: which layers are which, and the least time the chip could take
for the wave's attention when each windowed layer is counted over the keys inside
its window and each full layer over all.  ``chipbench/reduce/shapes.py`` counts
ONE window for all layers (``sizes["sliding_window"]``), which is Mistral's case;
here its functions are asked once a layer kind.  Nothing comes from ``deepspeed_tpu``."""

from chipbench.reduce import shapes

SLIDING, FULL = "sliding_attention", "full_attention"


def is_family(sizes) -> bool:
    kinds = set((sizes.get("layer_types") or ())[:sizes.get("num_hidden_layers", 0)])
    return bool(kinds) and kinds <= {SLIDING, FULL} and "sliding_window" in sizes


def layer_windows(sizes):
    """One window a layer that is run: ``sliding_window`` or None (a full layer)."""
    return [sizes["sliding_window"] if kind == SLIDING else None
            for kind in sizes["layer_types"][:sizes["num_hidden_layers"]]]


def by_kind(sizes):
    """``{"window": sizes of the windowed layers alone, "full": of the full ones}``: the
    published sizes with the depth and the window of one kind, as ``shapes`` reads them."""
    windows = layer_windows(sizes)
    n_window = sum(w is not None for w in windows)
    return {"window": {**sizes, "num_hidden_layers": n_window},
            "full": {**sizes, "num_hidden_layers": len(windows) - n_window, "sliding_window": None}}


def attention_least_seconds(sizes, prompt_lens, new_tokens: int, peaks) -> dict:
    """Least time for the attention of one wave, each layer counted by its kind
    (``shapes.paged_attention_least_seconds`` a kind), and each kind's part."""
    parts = {kind: shapes.paged_attention_least_seconds(of, prompt_lens, new_tokens, peaks)
             for kind, of in by_kind(sizes).items() if of["num_hidden_layers"]}
    out = {"seconds": sum(p["seconds"] for p in parts.values())}
    out.update((f"{kind}_layers_s", p["seconds"]) for kind, p in parts.items())
    for bound in ("decode_memory_s", "prefill_compute_s", "prefill_memory_s"):
        out[bound] = sum(p[bound] for p in parts.values())
    return out
