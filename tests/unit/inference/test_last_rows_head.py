"""A serving step's head runs over each row's last live token alone (ISSUE 44).

``paged_forward(last_rows=True)`` takes the N rows ``x[n, n_tokens[n] - 1]``
before the head and returns ``[N, 1, V]``; the engine's step forward always
asks for it, ``pick`` reads ``logits[:, 0]``, and ``ServeCounters.head_rows``
counts the rows the head multiplied.  Left out, the argument keeps every
position's ``[N, T, V]``: what a speculative verify and the chip benchmark's
references read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.entries.serve import LogitSpy
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import lfm2, llama, qwen3_next
from deepspeed_tpu.models.transformer import flat_slots
from tests.unit.inference.test_serving_fastpath import (_COMPACT_PROMPTS, _COUNTS, _FAMILIES,
                                                        _family_forward, _ragged_chunk,
                                                        _shared_compacting_engine)

# the families of test_compacted_mixed_wave_matches_the_padded_reference, and
# the two whose layers without attention carry a state a sequence beside the pool
FAMILIES = {name: _FAMILIES[name] for name in ("llama", "mistral", "falcon", "bloom", "opt")}
FAMILIES["lfm2-conv-state"] = lambda: (lfm2, lfm2.Lfm2Config.tiny(vocab=128))
FAMILIES["qwen3-next-gdn-state"] = lambda: (
    qwen3_next, qwen3_next.Qwen3NextConfig.tiny(vocab=128, experts=8, local_experts=4))
NUM_BLOCKS, BLOCK, T, BOUND, WIDTH = 33, 8, 16, 16, 8


def _chunk(module, cfg, counts):
    """``(tokens, counts, start, tables, cache)``: a ragged ``[4, 16]`` chunk
    over a pool (and, for a stateful family, slots) of random content."""
    rng = np.random.default_rng(sum(c * 17**i for i, c in enumerate(counts)))
    tokens, counts, start, tables = _ragged_chunk(rng, counts, T, BLOCK, NUM_BLOCKS, WIDTH)
    stateful = hasattr(module, "state_bytes_per_seq")
    extra = {"state_slots": len(counts)} if stateful else {}
    cache = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        module.init_paged_cache(cfg, NUM_BLOCKS, BLOCK, dtype=jnp.float32, **extra))
    if stateful:  # a row's slot rides as its table's last column; a dead row's is the trash slot
        slot = np.where(counts > 0, np.arange(len(counts)), len(counts)).astype(np.int32)
        tables = np.concatenate([tables, slot[:, None]], axis=1)
    return tokens, counts, start, tables, cache


@pytest.mark.parametrize("layout", ["compacted", "padded"])
@pytest.mark.parametrize("shape", list(_COUNTS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_last_rows_equal_the_all_positions_forwards_last_live_row(family, shape, layout):
    module, cfg, params, forward = _family_forward(FAMILIES[family])
    tokens, counts, start, tables, cache = _chunk(module, cfg, _COUNTS[shape])
    bound = BOUND if layout == "compacted" else None
    assert (flat_slots(len(counts), T, bound) is not None) == (layout == "compacted")
    every, cache_every = forward(params, tokens, counts, start, tables, cache, bound, False)
    last, cache_last = forward(params, tokens, counts, start, tables, cache, bound, True)
    assert every.shape == (len(counts), T, cfg.vocab_size)
    assert last.shape == (len(counts), 1, cfg.vocab_size)
    live = counts > 0
    want = np.asarray(every)[np.arange(len(counts)), np.maximum(counts - 1, 0)]
    np.testing.assert_allclose(np.asarray(last)[live, 0], want[live], atol=1e-5, rtol=0)
    assert np.isfinite(np.asarray(last)).all()  # a row with no token: some finite row
    # the pool and the state are written as they were: every block but the trash
    # block, every slot but the trash slot (each a leaf's last along its axis 1)
    for a, b in zip(jax.tree_util.tree_leaves(cache_every), jax.tree_util.tree_leaves(cache_last)):
        np.testing.assert_array_equal(np.asarray(a)[:, :-1], np.asarray(b)[:, :-1])


def _vocab_wide_rows(jaxpr, vocab):
    """The most rows any value ``[.., vocab]`` of the traced program holds,
    sub-programs and all (a Pallas kernel's own body apart: its values are tiles)."""
    most = 0
    for eqn in jaxpr.eqns:
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if shape and shape[-1] == vocab:
                most = max(most, int(np.prod(shape[:-1])))
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    most = max(most, _vocab_wide_rows(inner, vocab))
    return most


@pytest.mark.parametrize("bound,name", [(256, "compacted"), (None, "padded")], ids=lambda v: str(v))
@pytest.mark.parametrize("family", ["llama", "qwen3-next-gdn-state"])
def test_a_last_rows_program_holds_no_logits_but_a_row_a_sequence(family, bound, name):
    """A chunk program at ``[8, 256]``, traced: with ``last_rows`` no value as wide
    as the vocabulary has more than N rows (no ``[N, T, V]``, no head over the S
    flat slots, no zero-fill); without it the ``[N, T, V]`` is there, as ever.
    The shapes are static, so the padded logits cannot come back unseen."""
    module, cfg = {  # a vocabulary no other width of the model equals
        "llama": lambda: (llama, llama.LlamaConfig.tiny(vocab=200, hidden=64, layers=2, heads=4,
                                                        kv_heads=4, seq=256)),
        "qwen3-next-gdn-state": lambda: (qwen3_next, qwen3_next.Qwen3NextConfig.tiny(
            vocab=200, experts=8, local_experts=4))}[family]()
    n, t, maxb = 8, 256, 8
    stateful = hasattr(module, "state_bytes_per_seq")
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, 65, 32, dtype=jnp.float32, **({"state_slots": n} if stateful else {})))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32) for s in ((n, t), (n, ), (n, ), (n, maxb + stateful))]

    def rows(last_rows):
        return _vocab_wide_rows(jax.make_jaxpr(
            lambda p, kv, tokens, n_tokens, start_pos, tables: module.forward_paged(
                cfg, p, tokens, n_tokens, start_pos, tables, kv, block_size=32,
                live_token_bound=bound, last_rows=last_rows))(params, kv, *ints).jaxpr,
            cfg.vocab_size)
    assert rows(True) == n
    assert rows(False) == n * t


def _all_positions_twin(eng, seen=None):
    """Stands in for ``eng._compiled_fwd``: the parent's step forward, every
    position's ``[n, t, V]`` from the family's forward without ``last_rows``,
    then the parent's ``pick`` gather of ``[row, n_tokens - 1]``, handed on as
    ``[n, 1, V]``.  ``seen`` collects ``(tokens, n_tokens, start_pos, [n, t, V])``."""
    every_position = jax.jit(lambda params, kv, tokens, n_tokens, start_pos, tables:
                             eng.model.forward_paged(
                                 eng.model_config, params, tokens, n_tokens, start_pos, tables, kv,
                                 block_size=eng.block_size, live_token_bound=eng._live_token_bound))

    def compiled_fwd(n, t, b):
        def fwd(params, kv, tokens, n_tokens, start_pos, tables):
            every, kv = every_position(params, kv, tokens, n_tokens, start_pos, tables)
            if seen is not None:
                seen.append(tuple(np.asarray(a) for a in (tokens, n_tokens, start_pos, every)))
            last = jnp.maximum(n_tokens - 1, 0)
            return jnp.take_along_axis(every, last[:, None, None], axis=1), kv
        return fwd
    return compiled_fwd


@pytest.mark.parametrize("sampling", [{}, {"temperature": 0.8, "top_k": 20, "top_p": 0.9}],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("fastpath", [True, False], ids=["fastpath", "reference-step"])
def test_pick_returns_the_tokens_of_the_all_positions_forward(monkeypatch, fastpath, sampling):
    """``pick`` over ``logits[:, 0]`` of the last-rows forward against the parent's
    pair: the all-positions forward and the gather of each row's last live row."""
    def engine():
        module, cfg = _FAMILIES["llama"]()
        return InferenceEngineV2(
            module, cfg, module.init_params(cfg, jax.random.PRNGKey(1)),
            config={"dtype": "float32", "serving_fastpath": {"enabled": fastpath}, **sampling},
            num_blocks=64, block_size=8, max_blocks_per_seq=8, token_budget=16,
            max_seqs_per_step=4)
    if sampling:  # a draw moves an engine's rng: a pair of its own, each from the seed
        eng, parent = engine(), engine()
    else:  # the shared engine's configuration: the one engine serves the wave both ways
        eng = parent = _shared_compacting_engine("llama", fastpath)
    tokens = eng.generate(_COMPACT_PROMPTS, max_new_tokens=8, greedy=not sampling)
    monkeypatch.setattr(parent, "_compiled_fwd", _all_positions_twin(parent))
    assert tokens == parent.generate(_COMPACT_PROMPTS, max_new_tokens=8, greedy=not sampling)
    assert all(len(t) == len(p) + 8 for t, p in zip(tokens, _COMPACT_PROMPTS))
    picks = {e["name"] for e in eng.ledger.events if e["site"] == "pick"}
    assert picks == ({"pick_n4_sampled"} if sampling else {"pick_n4"})
    if sampling:  # and the draw really sampled: a greedy engine picks other tokens
        greedy = _shared_compacting_engine("llama", fastpath).generate(_COMPACT_PROMPTS,
                                                                       max_new_tokens=8)
        assert tokens != greedy


def test_logit_spy_reads_row_zero_of_a_last_rows_program_through_a_clamped_index(monkeypatch):
    """The reliance ISSUE 44 names: the chip benchmark's ``LogitSpy`` (not edited)
    indexes the step forward's result at ``[row, n_tokens[row] - 1]``; of a
    ``[n, 1, V]`` ``jax.Array`` an index past the end clamps, so it reads
    ``[row, 0]``, the row the all-positions forward puts at
    ``[row, len(prompt) - start - 1]``.  The day the index stops clamping, or
    the result is no ``jax.Array``, this fails before a chip run does."""
    eng = _shared_compacting_engine("llama", True)
    shapes = []
    compiled_fwd = eng._compiled_fwd

    def watched(n, t, b):  # under the spy: the shapes the spy was handed
        fwd = compiled_fwd(n, t, b)

        def call(*args):
            logits, kv = fwd(*args)
            shapes.append((t, logits.shape))
            return logits, kv
        return call
    monkeypatch.setattr(eng, "_compiled_fwd", watched)
    with LogitSpy(eng, _COMPACT_PROMPTS) as spy:
        served = eng.generate(_COMPACT_PROMPTS, max_new_tokens=4)
        rows = spy.rows
    assert sorted(rows) == [0, 1, 2, 3]  # a row for every prompt of the wave
    assert max(t for t, _ in shapes) > 1 and all(shape[1] == 1 for _, shape in shapes)
    # the same wave through the all-positions forward, every call's [n, t, V] kept
    seen = []
    monkeypatch.setattr(eng, "_compiled_fwd", _all_positions_twin(eng, seen))
    assert eng.generate(_COMPACT_PROMPTS, max_new_tokens=4) == served
    for i, prompt in enumerate(_COMPACT_PROMPTS):
        found = [every[row, len(prompt) - starts[row] - 1]
                 for tokens, counts, starts, every in seen
                 for row in np.nonzero((counts > 0) & (starts + counts == len(prompt)))[0]
                 if np.array_equal(tokens[row, :counts[row]], prompt[starts[row]:])]
        assert found, f"prompt {i} ended in no forward"
        np.testing.assert_allclose(rows[i], found[0], atol=1e-5, rtol=0)


@pytest.mark.parametrize("fastpath", [True, False], ids=["fastpath", "reference-step"])
def test_head_rows_are_n_a_forward_and_n_times_k_a_burst(monkeypatch, fastpath):
    """``head_rows`` against the sum of ``n x passes`` over the launched step and
    burst programs, in ``snapshot()`` and so in ``health()``."""
    eng = _shared_compacting_engine("llama", fastpath)
    launched = []
    compiled_fwd, compiled_burst = eng._compiled_fwd, eng._compiled_burst

    def fwd(n, t, b):
        launched.append(n)
        return compiled_fwd(n, t, b)

    def burst(n, k, *args, **kwargs):
        launched.append(n * k)
        return compiled_burst(n, k, *args, **kwargs)
    monkeypatch.setattr(eng, "_compiled_fwd", fwd)
    monkeypatch.setattr(eng, "_compiled_burst", burst)
    before = eng.counters.snapshot()
    eng.generate(_COMPACT_PROMPTS, max_new_tokens=12)
    c = eng.counters.delta_since(before)
    assert c["head_rows"] == sum(launched) > 0
    assert eng.health()["fastpath"]["head_rows"] == eng.counters.snapshot()["head_rows"] \
        == eng.counters.head_rows
    assert c["head_rows"] < c["token_slots"]  # the head ran over every slot before
    if fastpath:
        assert c["burst_tokens"] > 0 and c["compact_passes"] > 0  # both kinds of program ran
