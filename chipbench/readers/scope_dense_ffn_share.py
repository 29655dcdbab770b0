"""The dense FFN's share of the device's busy time: ``dense_ffn`` (``swiglu_mlp``,
``gelu_mlp``) outside any expert scope.

The scopes are the program's own, read off its executables
(``chipbench/reduce/scopes.py``); nothing to read without a trace or from a
program that has no ``program_scopes``."""

from chipbench.reduce import scopes


def read(run):
    return scopes.group_share(run, "dense_ffn")
