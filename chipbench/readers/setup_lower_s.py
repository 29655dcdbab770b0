"""Seconds of lowering jaxprs to MLIR modules before the window: the ``lower``
rows of the program's set-up account.  The persistent cache saves none of it."""

from chipbench.reduce import setup_account


def read(run):
    return setup_account.seconds(run, "lower")
