"""Attention's share of the device's busy time, by scope: the projections
(``attn_qkv``, with ``mla_absorb`` inside), the KV write (``kv_write``), the
kernel and what selects its keys (``attn_kernel``, with ``dsa_index`` /
``dsa_select`` inside), a gated output (``attn_gate``) and the part of
``layer_finish`` under no FFN scope (the output projection, the residuals, the
norms), each apart in the note with the kernels' own seconds.

The scopes are the program's own, read off its executables
(``chipbench/reduce/scopes.py``); nothing to read without a trace or from a
program that has no ``program_scopes``."""

from chipbench.reduce import scopes


def read(run):
    return scopes.group_share(run, "attention")
