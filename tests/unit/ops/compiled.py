"""``compiled(fn, **facts)(*arrays)``: a kernel's entry through ``jax.jit``.

``ops/attention/paged.py`` jits nothing itself (the program always calls it
inside a jitted forward), so a test that calls it with ``_pallas.INTERPRET`` set
runs the interpreter op by op.  Jitted, interpret mode lowers to ONE XLA program
a shape.  The facts a call site fixes (numbers, None) are closed over; the
arrays among them (a selection, the slopes) are arguments.  Every ``compiled``
is a trace of its own: a tile, a budget or a loop that a case has monkeypatched
is what the trace finds; calls of one ``compiled`` at one shape share its
program.  A case whose point is the eager call itself (an error raised as the
entry is called, an ``InterpretParams`` that models DMA) does not come here."""

import functools

import jax


def compiled(fn, **facts):
    traced = {name: fact for name, fact in facts.items() if isinstance(fact, jax.Array)}
    jitted = jax.jit(functools.partial(fn, **{name: fact for name, fact in facts.items() if name not in traced}))
    return lambda *arrays: jitted(*arrays, **traced)
