"""Seconds constructing the engines before the window, less JAX's trace, lower
and load seconds that fell inside (those are the other three metrics').
``other_s`` is what of ``setup_s`` no row accounts for: imports, the weight
draw, the warm-up wave's execution, the harness."""

from chipbench.reduce import setup_account


def read(run):
    found = setup_account.cut_on_chip(run)
    if found is None or found["engine_init_s"] <= 0:
        return None
    return found["engine_init_s"], {"other_s": round(found["other_s"], 3),
                                    "setup_s": round(run.setup_s, 3)}
