"""Seconds of Python tracing before the window: the outermost ``trace`` rows
of the program's set-up account, with the operator-level traces inside them
counted (``inner_traces``: a ``jnp`` operator on a traced value is one where
JAX's trace cache misses) and what listening cost the process so far
(``events`` arrivals, ``callback_s`` inside the account's listener: it pays
most where most is traced)."""

from chipbench.reduce import setup_account


def read(run):
    return setup_account.seconds(run, "trace", "inner_traces", "events", "callback_s")
