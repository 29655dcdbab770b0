"""What a family must show to be served, stated once.

A family (``deepspeed_tpu/models/<name>.py`` behind ``transformer.paged_forward``
and ``InferenceEngineV2``) serves what its plain reference
(``chipbench/references/<name>.py``: whole sequences, no cache, no state)
computes.  ``tests/unit/inference/test_<family>.py`` says so in three parts:

* a ``Family`` value: plain data, read by ``test_family_contract.py`` too;
* ``class Test<Family>(ServingContract)`` (``StatefulContract`` where a sequence
  keeps a fixed state in a slot beside the pool), which takes every shared case
  from here: the layout, prefill in chunks then decode steps, a compacted mixed
  step, a wave through chunks and the fused burst, the fast path against the
  padded oracle, a preempted sequence, speculation and tensor parallelism served
  or refused; with a state, a sequence that begins in a slot another left and a
  slot handed out again;
* the cases that are the family's own, as further methods of that class.

THE RULE for a new family's file: state a ``Family``, subclass the contract, add
only what is its own.  The file builds TWO engines, once a class, through the
harness ``h``: ``h.served`` (the family's default geometry, fast path on, every
default: it stands for what the benchmark runs) and ``h.oracle``
(``serving_fastpath.enabled=False``, the padded twin).  A drained engine serves
the next wave as a fresh one does, so a case serves through them and reads
tokens, and counters as deltas.  A case that truly needs a third configuration
takes ``h.engine(...)`` and says why in one line (it passes ``prewarm_buckets:
0``: under speculation a prewarm builds eight programs of which a one-prompt
wave launches three); the file's docstring counts the configurations it builds.  Spell no ``params`` draw,
``ids_of``, ``want``, ``close``, ``fresh_cache``, jitted forward, ``step``,
``engine`` or ``greedy`` again: where a family's differs in a value, the value
is a field of ``Family``.  The contract branches on what the value holds, never
on a family's name.  The file's junit sum under the driver's command stays under
150 s: a step program of a tiny model is 2-5 s of compiling, and a wave's
(rows, tokens, table width) buckets are what a case costs.
"""

import dataclasses
import functools
from types import MappingProxyType, SimpleNamespace
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models.transformer import STATE
from tests.unit.inference.scenario import launches_of


@dataclasses.dataclass(frozen=True)
class Pool:
    """The pool ``step`` pads to: blocks (the last is the trash block), tokens a
    block, columns of a row's table, state slots (none: the family keeps no state)."""
    blocks: int = 72
    block_size: int = 4
    table: int = 48
    slots: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Family:
    """What a family states.  Every field below ``tolerance_reason`` has the
    value most families share; a family states the ones in which it differs."""
    module: Any      # deepspeed_tpu.models.<name>
    reference: Any   # chipbench.references.<name>: imported, never edited
    sizes: Mapping   # the reference's tiny sizes (an HF config's keys)
    config: Any      # the program's tiny config of the same model
    tolerance: float
    tolerance_reason: str  # why this number: what sound runs read, what a fault reads
    relative: bool = True  # the tolerance as a share of the largest value compared
    # (names on a leaf's path, leaf, noise(shape)) -> leaf: norms pushed off neutral
    off_neutral: Optional[Callable] = None
    noise_keys: int = 128  # the split that ``noise`` draws from
    pool: Pool = Pool()
    engine: Mapping = MappingProxyType(dict(num_blocks=96, block_size=8, max_blocks_per_seq=24,
                                            token_budget=32, max_seqs_per_step=4))
    state_leaves: tuple = ()  # names under ``cache[STATE]``; ``(STATE, )``: the state is one array
    segments: Optional[list] = None  # ``layer_segments`` of the tiny config, where the family scans runs
    # prefill in chunks: the cuts of one prompt, decode steps after it, a chunk's padded
    # length (one for every chunk: one program; None: the chunk's own power of two)
    chunkings: tuple = ((150, ), (64, 64, 22), (1, 70, 79), (5, 131, 1, 2, 11))
    decode_steps: int = 3
    chunk_slots: Optional[int] = 256
    # a compacted mixed step: (tokens, already cached, served up to) a sequence, the slot
    # of each, the rows' padded length, the pass's ``live_token_bound``
    mixed: tuple = ((160, 70, 160), (80, 5, 80), (9, 8, 9))
    mixed_slots: tuple = (0, 3, 1)
    mixed_slots_a_row: int = 256
    mixed_bound: int = 176
    mixed_interpreted: bool = False  # that step traced with the Pallas kernels interpreted
    # through the engine: prompt lengths a wave (an entry may be pieces ``((seed, n), ...)``),
    # new tokens, the prompts held against the reference's greedy continuation
    waves: tuple = ((5, 90, 140, 9, 70, 3), )
    new_tokens: int = 6
    compared: tuple = (0, 1, 2)
    oracle_new_tokens: int = 5
    preempt_prompt: int = 100
    # hooks: ``layout(h, own, cache)`` the family's own shapes; ``wave(h, seen)`` what its
    # counters must read after a wave (``seen``: engine, counters (the wave's delta), prompts, got, launched)
    layout: Optional[Callable] = None
    wave: Optional[Callable] = None
    # misreadings of the published layer (``WrongReadings``): name -> what the REFERENCE is given
    # in their place (``sizes``: keys changed; ``params``: a function of the weights; any other
    # key: a function of the reference module replaced), and how far over the tolerance each reads
    wrong_readings: Mapping = MappingProxyType({})
    wrong_margin: float = 1.0
    reference_slots: int = 160  # the one length the reference is traced at: the longest sequence served


class Harness:
    """What every case of a family's file works with, made when first asked for."""

    def __init__(self, family):
        self.family, self.pool = family, family.pool
        self._greedy, self._prefilled = {}, {}

    @functools.cached_property
    def params(self):
        """The reference's draw, norms off neutral by the family's rule."""
        f = self.family
        drawn = jax.jit(lambda key: f.reference.init_params(f.sizes, key, jnp.float32))(jax.random.PRNGKey(7))
        if f.off_neutral is None:
            return drawn
        keys = iter(jax.random.split(jax.random.PRNGKey(8), f.noise_keys))
        noise = lambda shape: jax.random.normal(next(keys), shape)
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: f.off_neutral([getattr(p, "key", None) for p in path], leaf, noise), drawn)

    def ids_of(self, seed, n):
        return np.random.default_rng(seed).integers(0, self.family.sizes["vocab_size"], n).tolist()

    def want(self, ids, rows, params=None, sizes=None):
        """The reference's logits of ``ids`` at ``rows``.  It is causal, so ``ids`` are padded
        to the ONE length it is traced at (a longer sequence in 16s)."""
        ids = list(ids) + [0] * (max(self.family.reference_slots - len(ids), 0) or -len(ids) % 16)
        return np.asarray(self.family.reference.logits_rows(
            sizes or self.family.sizes, self.params if params is None else params, ids, rows))

    def error(self, got, wanted):
        scale = np.abs(wanted).max() if self.family.relative else 1.0
        return float(np.abs(np.asarray(got, np.float32) - wanted).max() / scale)

    def close(self, got, wanted):
        """Within the family's tolerance: ``Family.tolerance_reason`` says why that number."""
        assert self.error(got, wanted) <= self.family.tolerance

    def fresh_cache(self, dtype=jnp.float32, slots=None):
        state = {} if self.pool.slots is None else {"state_slots": slots or self.pool.slots}
        return self.family.module.init_paged_cache(self.family.config, self.pool.blocks, self.pool.block_size,
                                                   dtype=dtype, **state)

    def state(self, cache):
        """``{name: leaf}`` of a cache's state; nothing where the family keeps none."""
        kept = cache.get(STATE, {})
        return kept if isinstance(kept, dict) else {STATE: kept}

    def jitted(self):
        """A forward traced when first called: a planted fault, or kernels interpreted, is
        what the trace finds."""
        return jax.jit(functools.partial(self.family.module.forward_paged, self.family.config),
                       static_argnames=("block_size", "live_token_bound"))

    @functools.cached_property
    def forward(self):
        """The family's ONE jitted ``forward_paged``, compiled once a shape."""
        return self.jitted()

    def step(self, cache, rows, t, bound=None, forward=None, params=None):
        """One forward over ``rows`` = [(tokens, start_pos, blocks[, slot])]; returns
        (logits at each row's last token, cache).  Rows are padded to a power of two;
        a dead row's table holds the trash block and, with a state, the trash slot."""
        n = 1 << (len(rows) - 1).bit_length()
        kept = self.state(cache)
        tokens, counts, starts = np.zeros((n, t), np.int32), np.zeros(n, np.int32), np.zeros(n, np.int32)
        tables = np.full((n, self.pool.table + bool(kept)), self.pool.blocks - 1, np.int32)
        if kept:
            tables[:, -1] = next(iter(kept.values())).shape[1] - 1
        for i, (toks, start, blocks, *slot) in enumerate(rows):
            tokens[i, :len(toks)], counts[i], starts[i] = toks, len(toks), start
            tables[i, :len(blocks)] = blocks
            if kept:
                tables[i, -1], = slot
        logits, cache = (forward or self.forward)(
            self.params if params is None else params, jnp.asarray(tokens), jnp.asarray(counts),
            jnp.asarray(starts), jnp.asarray(tables), cache, block_size=self.pool.block_size,
            live_token_bound=bound)
        return [np.asarray(logits[i, max(len(r[0]) - 1, 0)], np.float32) for i, r in enumerate(rows)], cache

    def blocks_for(self, tokens):
        """Blocks for a sequence of ``tokens``, scattered over the pool, never the trash block."""
        need, live = -(-tokens // self.pool.block_size), self.pool.blocks - 1
        assert need <= min(live, self.pool.table)
        return [(3 + 5 * i) % live for i in range(need)]

    def chunks_then_decode(self, ids, chunks, decode=0, slot=2, forward=None, params=None, cache=None):
        """``[(position, logits)]`` at the end of each chunk and decode step, and the cache."""
        f = self.family
        blocks, cache, at, got = self.blocks_for(len(ids)), cache or self.fresh_cache(), 0, []
        row = lambda toks: [(toks, at, blocks) + (() if self.pool.slots is None else (slot, ))]
        for size in list(chunks) + [1] * decode:
            t = 1 if size == 1 else f.chunk_slots or 1 << (size - 1).bit_length()
            (logits, ), cache = self.step(cache, row(ids[at:at + size]), t, forward=forward, params=params)
            at += size
            got.append((at - 1, logits))
        return got, cache

    def prefilled(self, chunks):
        """``(ids, got, cache)`` of the contract's prompt served in ``chunks`` and the
        family's decode steps by the sound program: worked out once a chunking."""
        if chunks not in self._prefilled:
            ids = self.ids_of(1, sum(chunks) + self.family.decode_steps)
            self._prefilled[chunks] = (ids, *self.chunks_then_decode(ids, chunks, self.family.decode_steps))
        return self._prefilled[chunks]

    @functools.cached_property
    def mixed(self):
        """The mixed step's sequences, the cache with each one's head in it (by a step of
        its own), the rows that continue them, and the forward they are traced by."""
        f, at, seqs = self.family, 0, []
        for i, (n, _, _) in enumerate(f.mixed):
            need = -(-n // self.pool.block_size)
            seqs.append((self.ids_of(2 + i, n), list(range(at, at + need)), f.mixed_slots[i:i + 1]))
            at += need
        assert at < self.pool.blocks
        forward = self.interpreted() if f.mixed_interpreted else None
        cache = self.fresh_cache()
        for (ids, blocks, slot), (_, head, _) in zip(seqs, f.mixed):
            if head:
                _, cache = self.step(cache, [(ids[:head], 0, blocks, *slot)], f.mixed_slots_a_row,
                                     forward=forward)
        rows = [(ids[head:upto], head, blocks, *slot)
                for (ids, blocks, slot), (_, head, upto) in zip(seqs, f.mixed)]
        return SimpleNamespace(seqs=seqs, cache=cache, rows=rows, forward=forward)

    def interpreted(self):
        """A forward traced with the Pallas kernels interpreted (the flag is read as the
        program is traced, so every shape it will see is traced inside ``kernels``)."""
        from deepspeed_tpu.ops import _pallas
        inner = self.jitted()

        def forward(*args, **kw):
            was, _pallas.INTERPRET = _pallas.INTERPRET, True
            try:
                return inner(*args, **kw)
            finally:
                _pallas.INTERPRET = was
        return forward

    # ------------------------------------------------------- through the engine
    def engine(self, fast=True, sections=None, **geometry):
        conf = {"dtype": "float32", **(sections or {})}
        if not fast:
            conf["serving_fastpath"] = {"enabled": False}
        return InferenceEngineV2(self.family.module, self.family.config, self.params, config=conf,
                                 **{**self.family.engine, **geometry})

    @functools.cached_property
    def served(self):
        """The family's default geometry, fast path on, every default."""
        return self.engine()

    @functools.cached_property
    def oracle(self):
        """The padded twin: no compaction, no bursts, no prewarm."""
        return self.engine(fast=False)

    @functools.cached_property
    def twins(self):
        """One wave through ``served`` and through ``oracle``: each one's tokens and
        the delta of its counters, worked out once."""
        prompts, seen = self.prompts((33, 7, 81), seed=50), SimpleNamespace()
        for name, eng in (("fast", self.served), ("slow", self.oracle)):
            before = eng.counters.snapshot()
            tokens = [list(g) for g in eng.generate(prompts, max_new_tokens=self.family.oracle_new_tokens)]
            setattr(seen, name + "_tokens", tokens)
            setattr(seen, name, eng.counters.delta_since(before))
        return seen

    def prompts(self, wave, seed=10):
        return [self.ids_of(seed + i, p) if isinstance(p, int)
                else sum((self.ids_of(s, n) for s, n in p), []) for i, p in enumerate(wave)]

    def greedy(self, prompt, new):
        """The reference's greedy continuation, worked out once a prompt."""
        if (tuple(prompt), new) not in self._greedy:
            ids = list(prompt)
            for _ in range(new):
                ids.append(int(np.argmax(self.want(ids, [len(ids) - 1])[0])))
            self._greedy[tuple(prompt), new] = ids
        return list(self._greedy[tuple(prompt), new])


class ServingContract:
    """The cases every family's file takes.  ``family`` is the file's ``Family``."""
    family = None

    @pytest.fixture(scope="class")
    def h(self, request):
        return Harness(request.cls.family)

    def pytest_generate_tests(self, metafunc):
        family = metafunc.cls.family
        if "chunks" in metafunc.fixturenames:
            metafunc.parametrize("chunks", family.chunkings, ids=lambda c: "x".join(map(str, c)))
        if "reading" in metafunc.fixturenames:
            metafunc.parametrize("reading", sorted(family.wrong_readings))
        if "wave" in metafunc.fixturenames:
            lengths = lambda p: p if isinstance(p, int) else sum(n for _, n in p)
            metafunc.parametrize("wave", family.waves, ids=lambda w: "x".join(str(lengths(p)) for p in w))

    def test_the_layout_is_the_references_and_what_the_family_states(self, h):
        f = self.family
        if f.segments is not None:
            assert f.module.layer_segments(f.config) == f.reference.segments(f.sizes) == f.segments
        own = jax.eval_shape(lambda: f.module.init_params(f.config, jax.random.PRNGKey(0)))
        assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(h.params)
        assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(own)] == \
            [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(h.params)]
        cache = h.fresh_cache()
        assert tuple(h.state(cache)) == f.state_leaves and bool(f.state_leaves) == (h.pool.slots is not None)
        for leaf in h.state(cache).values():
            assert leaf.shape[1] == h.pool.slots + 1  # the trash slot behind the sequences'
        if f.layout is not None:
            f.layout(h, own, cache)

    def test_prefill_in_chunks_then_decode_steps_equal_the_reference(self, h, chunks):
        """A later chunk reads what an earlier one left, in the pool's blocks and in
        the sequence's slot; its end writes both back; a decode step is a chunk of one."""
        ids, got, _ = h.prefilled(chunks)
        wanted = h.want(ids, [at for at, _ in got])
        for (_, row), w in zip(got, wanted):
            h.close(row, w)

    def test_a_compacted_mixed_step_gives_each_sequence_what_it_gets_alone(self, h):
        """Chunks and decode rows of several sequences on the flat [1, S] axis: nothing
        crosses a sequence boundary, in the pool, the scan, the shift or the slots."""
        f, m = self.family, h.mixed
        assert len(m.rows) * f.mixed_slots_a_row > f.mixed_bound >= sum(len(r[0]) for r in m.rows)
        mixed, after = h.step(m.cache, m.rows, f.mixed_slots_a_row, bound=f.mixed_bound, forward=m.forward)
        for i, r in enumerate(m.rows):
            (alone, ), single = h.step(m.cache, [r], f.mixed_slots_a_row, forward=m.forward)
            h.close(mixed[i], alone)
            h.close(mixed[i], h.want(m.seqs[i][0], [r[1] + len(r[0]) - 1])[0])
            for name, leaf in h.state(after).items():
                h.close(np.asarray(leaf[:, r[3]]), np.asarray(h.state(single)[name][:, r[3]]))
        for name, leaf in h.state(after).items():
            assert np.isfinite(np.asarray(leaf[:, :-1])).all()
            for slot in set(range(h.pool.slots)) - set(f.mixed_slots[:len(m.rows)]):
                # the slot no row named is untouched
                np.testing.assert_array_equal(np.asarray(leaf[:, slot]), np.asarray(h.state(m.cache)[name][:, slot]))

    def test_generate_through_chunks_and_the_fused_burst_is_the_references_greedy(self, h, wave, monkeypatch):
        """Chunked prefill under the budget, compacted mixed passes, decode in fused
        bursts: the reference's greedy continuation, and the family's counters."""
        f, eng, prompts = self.family, h.served, h.prompts(wave)
        before, was, launched = eng.counters.snapshot(), eng.health().get("state"), launches_of(eng, monkeypatch)
        got = [list(g) for g in eng.generate(prompts, max_new_tokens=f.new_tokens)]
        c = eng.counters.delta_since(before)
        assert c["burst_tokens"] > 0 and c["compact_passes"] > 0
        for i in f.compared:
            assert got[i] == h.greedy(prompts[i], f.new_tokens)
        if f.state_leaves:
            # more sequences than slots: every hand-out starts a sequence from zero; a prompt that
            # shares whole leading blocks with an earlier one is declined by the prefix tree, counted
            state, slots = eng.health()["state"], f.engine["max_seqs_per_step"]
            bs = f.engine["block_size"]
            sharers = sum(any(p[:bs] == q[:bs] for q in prompts[:i]) for i, p in enumerate(prompts))
            tree = eng.manager.prefix_cache
            assert tree.hit_blocks_total == 0 and tree.tokens_saved_total == 0
            assert len(prompts) > slots == state["state_slots"] == eng.manager.trash_slot
            assert {k: state[k] - was[k] for k in (
                "state_slots_in_use", "state_slots_zeroed", "prefix_declined_stateful")} == {
                    "state_slots_in_use": 0, "state_slots_zeroed": len(prompts),
                    "prefix_declined_stateful": sharers}
            assert state["enabled"] and state["state_slots_in_use"] == 0
            assert state["state_bytes_per_seq"] == f.module.state_bytes_per_seq(f.config)
            assert all(leaf.shape[1] == slots + 1 for leaf in h.state(eng.kv).values())
        if f.wave is not None:
            f.wave(h, SimpleNamespace(engine=eng, counters=c, prompts=prompts, got=got, launched=launched))
        eng.check_kv_invariant()

    def test_the_fast_path_and_the_padded_oracle_serve_the_same_tokens(self, h):
        twins = h.twins
        assert twins.fast_tokens == twins.slow_tokens
        assert twins.slow["compact_passes"] == 0 < twins.fast["compact_passes"]

    def test_a_preempted_sequence_reaches_the_undisturbed_tokens(self, h):
        """With a state nothing is kept (a state keeps no block boundary) and the
        sequence starts over in a slot handed out anew; without, the kept blocks'
        tokens (every leaf of them) are not computed again."""
        f, eng = self.family, h.served
        prompt, budget, bs = h.ids_of(30, f.preempt_prompt), f.engine["token_budget"], f.engine["block_size"]
        undisturbed, zeroed = h.greedy(prompt, 5), eng.manager.state_slots_zeroed
        eng.put([7], [prompt])
        for _ in range(2):
            eng.step()
        seq = eng.manager.seqs[7]
        assert seq.seen_tokens == 2 * budget < len(prompt) and (seq.state_slot is None) == (not f.state_leaves)
        eng.manager.preempt(seq, keep_blocks=4)
        if f.state_leaves:
            assert (seq.seen_tokens, seq.blocks, seq.state_slot) == (0, [], None)
        else:
            assert seq.seen_tokens == 4 * bs < 2 * budget and len(seq.blocks) == 4
        out = []
        while len(out) < 5:
            out.extend(eng.step().values())
        eng.flush(7)
        assert prompt + out == undisturbed
        if f.state_leaves:
            assert eng.manager.state_slots_zeroed == zeroed + 2 and eng.manager.state_slots_in_use == 0

    def test_speculation_and_tensor_parallelism_are_served_or_refused(self, h):
        f = self.family
        if not f.state_leaves:
            # a third engine: speculation is a section of the configuration an engine is built with; a
            # rejected draft is rolled back by blocks, so whatever lives in the pool's blocks follows
            prompt = h.ids_of(40, 60)
            spec = h.engine(sections={"serving_spec_decode": {"enabled": True, "k": 3},
                                      "serving_fastpath": {"prewarm_buckets": 0}})
            assert list(spec.generate([prompt], max_new_tokens=6)[0]) == h.greedy(prompt, 6)
            assert spec.counters.spec_rounds > 0
        else:
            with pytest.raises(ValueError, match="per-sequence state"):  # a state keeps no block boundary
                h.engine(sections={"serving_spec_decode": {"enabled": True}})
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            f.module.forward_paged(f.config, h.params, None, None, None, None, h.fresh_cache(),
                                   block_size=h.pool.block_size, tp_axis="tensor")


class StatefulContract(ServingContract):
    """And where a sequence keeps a fixed state in a slot beside the pool."""

    def test_a_sequence_that_begins_reads_nothing_its_slot_was_left_with(self, h):
        self.begins_over_what_a_slot_was_left_with(h)

    def begins_over_what_a_slot_was_left_with(self, h, forward=None):
        """A slot is never zeroed: the sequence that takes it over begins
        (``start_pos == 0``) over what the last one left, here the last one's state
        and then NaNs, and is served as over a fresh cache, in a chunk pass and in
        the decode steps that follow."""
        f = self.family
        n = sum(f.chunkings[0])  # the contract's prompt again: every shape is compiled
        first, second = h.ids_of(22, n), h.ids_of(23, n + f.decode_steps)
        _, used = h.chunks_then_decode(first, (n, ))
        assert all(np.abs(np.asarray(leaf[:, 2])).max() > 0 for leaf in h.state(used).values())
        spoiled = dict(used)
        spoiled[STATE] = jax.tree_util.tree_map(lambda rows: rows.at[:, 2].set(jnp.nan), used[STATE])
        for cache in (used, spoiled):
            got, _ = h.chunks_then_decode(second, (n, ), f.decode_steps, forward=forward, cache=cache)
            for (_, row), w in zip(got, h.want(second, [at for at, _ in got])):
                h.close(row, w)

    def test_a_slot_reused_after_retire_starts_from_zero(self, h):
        """A wave of more sequences than slots (the lengths of the first wave, other
        draws: the programs are compiled): the last ones wait for a slot, take over
        what a retired sequence left in it, and are served as from zero."""
        f, eng = self.family, h.served
        slots, zeroed = f.engine["max_seqs_per_step"], eng.manager.state_slots_zeroed
        prompts = h.prompts(f.waves[0], seed=70)
        got = [list(g) for g in eng.generate(prompts, max_new_tokens=f.new_tokens)]
        for waited in range(slots, len(prompts)):
            assert got[waited] == h.greedy(prompts[waited], f.new_tokens)
        for leaf in h.state(eng.kv).values():  # no slot was zeroed: each holds what its last sequence left
            assert (np.abs(np.asarray(leaf[:, :slots])).reshape(leaf.shape[0], slots, -1).max(-1).max(0) > 0).all()
        assert len(prompts) > slots and eng.manager.state_slots_zeroed == zeroed + len(prompts)
        assert eng.manager.state_slots_in_use == 0


class WrongReadings:
    """Beside a contract, for a family that states ``wrong_readings``: each misreading
    of the published layer, stated on the reference, must NOT pass for the program."""

    @pytest.fixture(scope="class")
    def held(self, h):
        """A 150-token prompt and the program's logits at its end, served in three
        chunks: what every reading is held against."""
        ids = h.ids_of(5, 150)
        return ids, h.chunks_then_decode(ids, (64, 64, 22))[0][-1][1]

    def test_the_right_reading_passes_where_the_wrong_ones_are_held(self, h, held):
        ids, got = held
        h.close(got, h.want(ids, [len(ids) - 1])[0])

    def test_a_wrong_reading_of_the_published_layer_does_not_pass(self, h, held, monkeypatch, reading):
        (ids, got), how = held, dict(self.family.wrong_readings[reading])
        # a key of its own in the sizes: no cached trace of the right reading
        sizes = {**self.family.sizes, **how.pop("sizes", {}), "reading": reading}
        params = how.pop("params", lambda p: p)(h.params)
        for name, fn in how.items():
            monkeypatch.setattr(self.family.reference, name, fn)
        wrong = h.want(ids, [len(ids) - 1], params=params, sizes=sizes)[0]
        assert h.error(got, wrong) > self.family.wrong_margin * self.family.tolerance, reading

    def test_bfloat16_in_float32s_place_does_not_pass(self, h, held):
        ids = held[0]
        half = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1 else a, h.params)
        ((_, got), ), _ = h.chunks_then_decode(ids, (len(ids), ), params=half, cache=h.fresh_cache(jnp.bfloat16))
        assert np.isfinite(got).all() and h.error(got, h.want(ids, [len(ids) - 1])[0]) > self.family.tolerance
