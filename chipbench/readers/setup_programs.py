"""Executables the process built or loaded before the window: the ``load``
rows of the program's set-up account, with how many names they bear and how
many more ended inside the window.  (Which of them are the engines' own is the
``CompileLedger``'s to say, ``health()["perf"]["compile_ledger"]``: ``run``
does not carry the ledger.)"""

from chipbench.reduce import setup_account


def read(run):
    found = setup_account.cut(run)
    if found is None or not found["totals"]["loads"]:
        return None
    names = sum(1 for p in found["programs"].values() if p["loads"])
    return found["totals"]["loads"], {"names": names, "in_window": found["in_window"]}
