"""The share of the device's busy time spent in layers without attention, by
scope: everything a family's ``mix`` runs under ``conv_mixer`` / ``gdn_mixer`` /
``ssm_mixer`` (scan, update and state apart), what the driver does around it
(``seq_state``: a state's slots read and written) and what is left of the
mixer layer (``mixer_layer``: its norms and residuals), its FFN not: that is the
experts' or the dense FFN's.

The scopes are the program's own, read off its executables
(``chipbench/reduce/scopes.py``); nothing to read without a trace or from a
program that has no ``program_scopes``."""

from chipbench.reduce import scopes


def read(run):
    return scopes.group_share(run, "mixer")
