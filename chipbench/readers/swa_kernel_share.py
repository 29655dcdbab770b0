"""Device time of the attention kernel's scope by the kind of its layer: the
operations under ``attn_window`` (or ``attn_full``) inside ``attn_kernel``, the
program's own scopes read off its executables (``chipbench/reduce/scopes.py``),
over the traced wave's busy time.  Nothing to read without a trace, or from a
program whose layers tell no kind (it has no such scope)."""

from chipbench.reduce import scopes


def kind_share(run, kind: str):
    found = scopes.split(run) if getattr(run, "kind", None) == "serve" else None
    if found is None or run.trace.busy_s <= 0:
        return None
    inside = {path: s for path, s in scopes.summed(found, "paths").items()
              if "attn_kernel" in path and kind in path}
    total = sum(inside.values())
    if total <= 0:
        return None
    kernel = scopes.summed(found, "kernels").get((kind, "paged_attention"), 0.0)
    return 100.0 * total / run.trace.busy_s, {
        "scope_s": round(total, 4), "paged_attention_s": round(kernel, 4),
        "busy_s": round(run.trace.busy_s, 4)}


def read(run):
    return kind_share(run, "attn_window")
