"""A learned selection of the cache (ISSUE 45), the attending half: the paged
attention kernel over a selection alone, interpreted against ``_dense_fallback``
in both layouts, and what the selection adds to the kernel's body (ISSUE 47).
The top-k and the index scores are ``test_dsa.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import _pallas
from deepspeed_tpu.ops.attention import paged

from .compiled import compiled
from .test_dsa import flat_of
from .test_paged_slots_chooser import every_equation


SELECTED_CASES = {  # heads, t, n, starts, counts, row splits (1: as the shapes give)
    "chunks": (16, 8, 3, [0, 40, 30], [8, 1, 5], 1),
    "decode": (16, 1, 4, [5, 77, 0, 95], [1, 1, 0, 1], 1),
    "row_tiles": (32, 16, 2, [17, 64], [16, 3], 1),
    "glm_group": (64, 4, 2, [3, 50], [3, 4], 1),
    # the flat axis: the second window's first token at each place among a group of SEL_GROUP
    **{f"offset_{k % paged.SEL_GROUP}": (64, 8, 2, [3, 50], [k, 5], 1) for k in range(1, 9)},
    "two_tokens_a_tile": (128, 4, 2, [3, 50], [3, 4], 1),
    # a KV head's 2,048 rows in four grid steps of 16 tokens: windows that end inside a split
    "row_splits": (32, 64, 4, [20, 18, 0, 70], [40, 1, 64, 3], 4),
    # one tile of SMALL_ROWS for a decode row beside a chunk of two row tiles
    "decode_in_a_chunks_bucket": (16, 32, 3, [7, 20, 60], [1, 32, 1], 1),
}


def selected_case(heads, t, n, starts, counts, bs=16, maxb=6, dk=48, dv=32):
    rng = np.random.default_rng(2)
    nb = n * maxb + 1
    pool = jnp.asarray(rng.normal(size=(nb, 1, bs, dk)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:n * maxb].reshape(n, maxb), jnp.int32)
    q = jnp.asarray(rng.normal(size=(n, t, heads, dk)), jnp.float32)
    start, count = jnp.asarray(starts, jnp.int32), jnp.asarray(counts, jnp.int32)
    pos = start[:, None] + jnp.arange(t)[None]
    chosen = jnp.asarray(rng.random((n, t, maxb * bs)) < 0.4) \
        & (jnp.arange(maxb * bs)[None, None] <= pos[..., None])
    chosen = chosen.at[..., 0].set(True)
    facts = dict(block_size=bs, softmax_scale=0.2, value_dim=dv)
    return q, chosen, (pool, None, tables, start + count, start, count), facts


@pytest.mark.parametrize("heads,t,n,starts,counts,splits", list(SELECTED_CASES.values()),
                         ids=list(SELECTED_CASES))
def test_the_paged_kernel_attends_the_selection_alone(interpreted_kernels, monkeypatch, heads, t, n,
                                                      starts, counts, splits):
    """The kernel walks the live blocks and masks what was not selected: a
    token's row of the selection reaches all its ``group`` rows whatever tile
    and whatever offset its window begins at, padded and flat."""
    q, chosen, args, facts = selected_case(heads, t, n, starts, counts)
    if splits > 1:
        monkeypatch.setattr(paged, "VMEM_BUDGET_BYTES", paged._step_vmem_bytes(
            1, t * heads // splits, paged.ROW_TILE, q.shape[-1], 16, 4, 4, facts["value_dim"]))
    assert paged.step_tile(t, heads, 1, q.shape[-1], 16, q.dtype, q.dtype, facts["value_dim"])[2] == splits
    count = args[-1]
    got = compiled(paged.paged_attention, selection=chosen, **facts)(q, *args)
    (qf, chosenf), live, at = flat_of(count, t, q, chosen)
    gotf = compiled(paged.paged_attention_flat, chunk=t, selection=chosenf, **facts)(qf, *args)
    monkeypatch.setattr(_pallas, "INTERPRET", False)
    want = compiled(paged.paged_attention, selection=chosen, **facts)(q, *args)  # the fallback, one program
    every = compiled(paged.paged_attention, **facts)(q, *args)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(gotf[live], want[at][live], atol=2e-6)
    assert float(jnp.max(jnp.abs(want - every))) > 0.1  # the selection is not every key


@pytest.mark.parametrize("case,products", [("decode", 2), ("glm_group", 4), ("row_tiles", 4)])
def test_the_selection_adds_no_product_to_the_kernels_body(interpreted_kernels, case, products):
    """ISSUE 47: a token's row of the selection is read where it lies and
    broadcast over the token's rows.  The body traced with a selection holds the
    ``dot_general``s of the body traced without one (``q k^T`` and ``p v``, a
    tile size each) and nothing of a one-hot's shape; the body traced without
    one has no operand for a selection."""
    heads, t, n, starts, counts, _ = SELECTED_CASES[case]
    q, chosen, args, facts = selected_case(heads, t, n, starts, counts)
    bodies = {}
    for how, selection in (("selected", chosen), ("plain", None)):
        traced = jax.make_jaxpr(lambda q, selection: paged.paged_attention(
            q, *args, selection=selection, **facts))(q, selection)
        call, = (e for e in every_equation(traced.jaxpr) if e.primitive.name == "pallas_call")
        bodies[how] = call.params["jaxpr"]
    count = lambda body: sum(e.primitive.name == "dot_general" for e in every_equation(body))
    assert count(bodies["selected"]) == count(bodies["plain"]) == products
    operands = {how: [v.aval.shape for v in body.invars] for how, body in bodies.items()}
    (groups, one, tokens, keys), = [s for s in operands["selected"] if s not in operands["plain"]]
    assert (one, tokens, keys) == (1, paged.SEL_GROUP, 64)  # a window's tokens among a step's keys
    assert len(operands["selected"]) == len(operands["plain"]) + 1
    # and no [rows, tokens of some groups of SEL_GROUP]: what is two-dimensional over a
    # tile's rows is the tokens' rows of the selection, broadcast, a step's keys wide
    over_rows = [v.aval.shape for e in every_equation(bodies["selected"]) for v in e.outvars
                 if len(v.aval.shape) == 2 and v.aval.shape[0] > 1]
    assert over_rows and all(shape[1] == keys for shape in over_rows), over_rows
