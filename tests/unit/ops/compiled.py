"""``compiled(fn, **facts)(*arrays)``: a kernel's entry through ``jax.jit``.

``ops/attention/paged.py`` jits nothing itself (the program always calls it
inside a jitted forward), so a test that calls it with ``_pallas.INTERPRET`` set
runs the interpreter op by op.  Jitted, interpret mode lowers to ONE XLA program
a shape.  The facts a call site fixes (numbers, None) are closed over; the
arrays among them (a selection, the slopes) are arguments.  Every ``compiled``
is a trace of its own: a tile, a budget or a loop that a case has monkeypatched
is what the trace finds; calls of one ``compiled`` at one shape share its
program.  A case whose point is the eager call itself (an error raised as the
entry is called, an ``InterpretParams`` that models DMA) does not come here.

``entry(fn)(*arrays)``: the same for the scans of ``ops/linear_attention/``, whose
entries do more outside their kernel (the layout on chunk edges, the window's gathers,
the loop of trips): called bare, each of those ops is a program of its own, several
hundred a file.  ONE ``jax.jit`` a function and a form for the whole run
(``_pallas.INTERPRET`` is read as the trace is made, so it is part of the key), so the
cases of one shape share a program.  For files whose cases patch nothing else that a
trace reads.

``dense_fallback(*arrays, *facts, **facts)``: ``paged._dense_fallback``, the reference of
the paged kernel's cases (a gather of the whole table and a masked softmax: some thirty
ops), as ONE program a case; the arrays are the first seven, a selection or slopes among
the facts are constants of the trace."""

import functools

import jax

from deepspeed_tpu.ops import _pallas


def compiled(fn, **facts):
    traced = {name: fact for name, fact in facts.items() if isinstance(fact, jax.Array)}
    jitted = jax.jit(functools.partial(fn, **{name: fact for name, fact in facts.items() if name not in traced}))
    return lambda *arrays: jitted(*arrays, **traced)


@functools.lru_cache(None)
def _jitted(fn, interpreted):
    return jax.jit(fn)


def entry(fn):
    return _jitted(fn, _pallas.INTERPRET)


def dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens, *facts, **more):
    from deepspeed_tpu.ops.attention import paged
    return jax.jit(lambda *arrays: paged._dense_fallback(*arrays, *facts, **more))(
        q, kpool, vpool, tables, lengths, start_pos, n_tokens)
