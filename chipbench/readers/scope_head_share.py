"""The share of the device's busy time spent outside the layers: ``embed``, ``head``
(the last rows' gather, the final norm, the vocabulary product) and ``pick``
(sampling, in a step's pick program and in a burst's body), each apart.

The scopes are the program's own, read off its executables
(``chipbench/reduce/scopes.py``); nothing to read without a trace or from a
program that has no ``program_scopes``."""

from chipbench.reduce import scopes


def read(run):
    return scopes.group_share(run, "head")
