"""The optimizer's share of the traced train steps' busy time, on the device
where it is largest: device time of the operations under the step's
``optimizer`` scope.  The note has the other two scopes of the step
(``forward_backward``, ``grad_norm_clip``), what carries none, and the scope of
each of the step's ten largest operations (whether a ``dynamic-update-slice``
fusion is a matmul fused with its write, in the forward and backward, or the
optimizer's copy).  The scopes are the program's own, read off the train step's
executable (``chipbench/reduce/scopes.py``)."""

from chipbench.reduce import scopes, xplane


def read(run):
    found = scopes.split(run) if getattr(run, "kind", None) == "train" else None
    if found is None:
        return None
    worst = None
    for name, device in found["devices"].items():
        busy = xplane.total(run.trace.busy[name])
        by_scope = {scope: 0 for scope in scopes.TRAIN}
        for path, ns in device["paths"].items():
            outer = next((scope for scope in path if scope in scopes.TRAIN), None)
            if outer:
                by_scope[outer] += ns
        if busy > 0 and (worst is None or by_scope["optimizer"] / busy > worst[0]):
            worst = (by_scope["optimizer"] / busy, name, busy, by_scope, device)
    if worst is None:
        return None
    share, name, busy, by_scope, device = worst
    note = {"device": name.rsplit("device:", 1)[-1].replace(" ", ""), "busy_s": round(busy / 1e9, 4)}
    note.update((f"{scope}_s", round(ns / 1e9, 4)) for scope, ns in by_scope.items())
    note.update(unscoped_s=round(sum(device["unscoped"].values()) / 1e9, 4),
                unattributed_s=round(device["unattributed"] / 1e9, 4),
                mixed_s=round(device["mixed"] / 1e9, 4),
                inherited_s=round(device["inherited"] / 1e9, 4),
                largest=",".join(f"{op.replace(' ', ':')}:{ns / 1e9:.4f}:"
                                 f"{'/'.join(path) if path else 'unscoped' if path is not None else 'unattributed'}"
                                 for op, ns, path in device["largest"]),
                tables_s=round(found["tables_s"], 3))
    return 100.0 * share, note
