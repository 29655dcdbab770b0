"""Programs the engine's compile ledger recorded inside the window."""


def read(run):
    return run.compiles_in_window if run.kind == "serve" else None
