"""The expert FFN's share of the device's busy time, by scope: ``moe_route``,
``moe_expert_ffn`` (the ``gmm`` and ``moe_combine`` kernels' seconds inside it
apart), ``moe_shared_expert`` (``moe_shared_gate``), ``moe_identity`` and what
else lies under ``scmoe_shortcut``, whatever the shapes of their results.

The scopes are the program's own, read off its executables
(``chipbench/reduce/scopes.py``); nothing to read without a trace or from a
program that has no ``program_scopes``."""

from chipbench.reduce import scopes


def read(run):
    return scopes.group_share(run, "expert")
