"""Speculative decoding suite (ISSUE 20): drafter units, the on-device
rejection sampler's distribution guarantees, and the ragged seams the
draft/verify round shares with the paged-pool serving stack — sample identity
against the spec-off engine (fastpath and reference loops, strict and
non-strict), journal replay of a crash mid-stream (accepted-prefix frames
only, never draft tokens), and census/allocator invariants when a rejected
draft's block allocation crosses a block boundary."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import _filter_logits
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.fastpath import DeferredRuns, ServeCounters
from deepspeed_tpu.inference.v2.journal import replay_journal
from deepspeed_tpu.inference.v2.spec_decode import (AdaptiveKController,
                                                    ModelDrafter, NgramDrafter,
                                                    SpecDecodeStats,
                                                    rejection_select,
                                                    spec_k_ladder)
from deepspeed_tpu.models import llama
from tests.unit.fault_injection_serving import FakeClock


def _cfg(seq=256):
    return llama.LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                                  kv_heads=2, seq=seq)


_PARAMS = {}


def _engine(config=None, *, seq=256, **kw):
    cfg = _cfg(seq)
    if seq not in _PARAMS:
        _PARAMS[seq] = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(config=config if config is not None else {"dtype": "float32"},
                    num_blocks=64, block_size=8, max_blocks_per_seq=8,
                    token_budget=32, max_seqs_per_step=8)
    defaults.update(kw)
    return InferenceEngineV2(llama, cfg, _PARAMS[seq], **defaults)


def _spec_conf(extra=None, **spec):
    conf = {"dtype": "float32",
            "serving_spec_decode": {"enabled": True, **spec}}
    conf.update(extra or {})
    return conf


@functools.lru_cache(maxsize=None)
def _plain_engine():
    """The default engine, one for every case that wants the spec-off greedy
    tokens of a wave (drained, it replays a wave step for step).  A spec engine
    is never shared: its controller walks ``k`` down on what earlier waves
    accepted, to where a later case would run no round at all."""
    return _engine()


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17], [20, 21]]


# ========================================================== kernel-level units
def test_spec_k_ladder_bounded_and_anchored():
    assert spec_k_ladder(1) == (1,)
    assert spec_k_ladder(4) == (1, 3, 4)
    assert spec_k_ladder(8) == (1, 3, 7, 8)
    assert spec_k_ladder(63) == (1, 3, 7, 15, 31, 63)
    for k in (1, 2, 5, 16, 63):
        ladder = spec_k_ladder(k)
        assert ladder[0] == 1 and ladder[-1] == k
        assert all(r <= k for r in ladder)


def test_rejection_select_greedy_packs_agree_prefix_plus_argmax():
    """Greedy verify: accept while draft matches the target argmax, then one
    corrected token — the packed row's emitted tokens are the argmax at EVERY
    position, so the emitted run equals plain greedy decode exactly."""
    n, k, v = 3, 3, 16
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(n, k + 1, v)), jnp.float32)
    tgt = np.argmax(np.asarray(logits, np.float64), axis=-1)
    draft = np.stack([tgt[0, :k],                       # all accepted
                      [tgt[1, 0], (tgt[1, 1] + 1) % v, tgt[1, 2]],  # reject @1
                      [(tgt[2, 0] + 1) % v, tgt[2, 1], tgt[2, 2]]])  # reject @0
    packed, _ = rejection_select(logits, jnp.asarray(draft, jnp.int32),
                                 jax.random.PRNGKey(0), sample_cfg=None)
    packed = np.asarray(packed)
    assert list(packed[:, 0]) == [k + 1, 2, 1]
    np.testing.assert_array_equal(packed[:, 1:], tgt.astype(np.int32))


def test_rejection_select_sampled_marginal_matches_filtered_target():
    """The Leviathan guarantee, measured: over many rng draws the FIRST
    emitted token's empirical distribution matches direct sampling from the
    filtered target — total variation within the sampling-noise band."""
    v, k, draws = 24, 3, 4000
    sample_cfg = (0.8, 8, 0.95)
    rng = np.random.default_rng(5)
    base = jnp.asarray(rng.normal(0.0, 1.5, size=(1, k + 1, v)), jnp.float32)
    logits = jnp.tile(base, (draws, 1, 1))
    draft = jnp.tile(jnp.asarray([[3, 4, 5]], jnp.int32), (draws, 1))
    packed, _ = rejection_select(logits, draft, jax.random.PRNGKey(1),
                                 sample_cfg=sample_cfg)
    first = np.asarray(packed)[:, 1]
    freq = np.bincount(first, minlength=v) / draws
    filt = _filter_logits(base[0, :1], temperature=sample_cfg[0],
                          top_k=sample_cfg[1], top_p=sample_cfg[2])
    target_p = np.asarray(jax.nn.softmax(filt[0]))
    tv = 0.5 * float(np.abs(freq - target_p).sum())
    assert tv < 0.08, f"TV distance {tv:.4f} — the sampler is biased"
    # masked-out tokens must never be emitted
    assert float(freq[target_p < 1e-12].sum()) == 0.0


def test_rejection_select_residual_never_reemits_rejected_token():
    """On rejection at position a the resample draws from the residual (the
    rejected draft token masked out) — emitting it again would double-count
    its probability mass."""
    v, k, draws = 16, 2, 512
    rng = np.random.default_rng(2)
    base = jnp.asarray(rng.normal(size=(1, k + 1, v)), jnp.float32)
    logits = jnp.tile(base, (draws, 1, 1))
    # draft position 0: a LOW-probability token under the target, so most
    # rows reject at 0 and resample there
    filt = _filter_logits(base[0, :1], temperature=1.0, top_k=0, top_p=1.0)
    worst = int(np.argmin(np.asarray(filt[0])))
    draft = jnp.tile(jnp.asarray([[worst, 1]], jnp.int32), (draws, 1))
    packed, _ = rejection_select(logits, draft, jax.random.PRNGKey(3),
                                 sample_cfg=(1.0, 0, 1.0))
    packed = np.asarray(packed)
    rejected_at_0 = packed[:, 0] == 1
    assert rejected_at_0.sum() > draws // 2
    assert not np.any(packed[rejected_at_0, 1] == worst)


def test_ngram_drafter_proposes_from_history_match():
    d = NgramDrafter(3, 1)
    # history with a cycle: the longest-suffix match continues it
    hist = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    assert d.propose(hist, 4) == [7, 8, 5, 6]
    # rightmost match wins when several exist
    hist2 = [1, 2, 9, 9, 1, 2, 3, 3, 1, 2]
    assert d.propose(hist2, 2) == [3, 3]
    # no match anywhere: pad by repeating the last token
    assert d.propose([1, 2, 3], 3) == [3, 3, 3]

    class Seq:
        def __init__(self, toks):
            self.tokens = list(toks)
            self.seen_tokens = len(toks) - 1

    batch = d.propose_batch([Seq(hist), Seq([1, 2, 3])], 4, pad_to=4)
    assert isinstance(batch, np.ndarray) and batch.shape == (4, 4)
    assert batch.dtype == np.int32
    assert list(batch[0]) == [7, 8, 5, 6]
    assert list(batch[1]) == [3, 3, 3, 3]
    assert not batch[2:].any()  # padded rows stay zero


def test_adaptive_k_controller_ladder_walk_and_floor_probe():
    from deepspeed_tpu.runtime.config import ServingSpecDecodeConfig
    cfg = ServingSpecDecodeConfig(enabled=True, k=8, ewma_alpha=1.0,
                                  raise_threshold=0.7, lower_threshold=0.3,
                                  probe_every=3)
    c = AdaptiveKController(cfg)
    assert c.ladder == (1, 3, 7, 8)
    assert c.k == 8  # starts at the top rung
    c.note_round(8, 1)  # acceptance 0.125 < lower: step down
    assert c.k == 7
    c.note_round(7, 0)
    assert c.k == 3
    c.note_round(3, 0)
    assert c.k == 1  # the floor: plain burst territory
    # at the floor, next_k() returns 1 until the probe counter trips
    assert [c.next_k() for _ in range(cfg.probe_every)][:-1] == [1, 1]
    assert c.k == 3  # probed back up one rung
    c.note_round(3, 3)  # perfect acceptance: climb
    assert c.k == 7
    c.note_round(7, 7)
    assert c.k == 8
    c.note_round(8, 8)
    assert c.k == 8  # capped at the top

    fixed = AdaptiveKController(ServingSpecDecodeConfig(
        enabled=True, k=4, adaptive_k=False))
    fixed.note_round(4, 0)
    assert fixed.next_k() == 4  # adaptive off: k is pinned


def test_spec_stats_snapshot_and_acceptance():
    s = SpecDecodeStats()
    assert s.acceptance_rate() == 0.0
    s.note_round(8, 6, [4, 3])
    s.note_round(8, 2, [2, 1])
    snap = s.snapshot()
    assert snap["rounds_total"] == 2
    assert snap["proposed_total"] == 16 and snap["accepted_total"] == 8
    assert snap["emitted_total"] == 10
    assert snap["acceptance_rate"] == 0.5
    assert snap["tokens_per_verify"] == {"1": 1, "2": 1, "3": 1, "4": 1}


# ==================================================== engine sample identity
def test_spec_greedy_identity_fastpath_strict_and_nonstrict():
    ref = _plain_engine().generate(PROMPTS, max_new_tokens=9)
    spec = _engine(_spec_conf()).generate(PROMPTS, max_new_tokens=9)
    assert spec == ref
    spec_ns = _engine(_spec_conf()).generate(PROMPTS, max_new_tokens=9,
                                             strict=False)
    assert [r.tokens for r in spec_ns] == ref
    assert all(r.status == "ok" for r in spec_ns)


def test_spec_greedy_identity_reference_loop():
    """Spec decode rides the fused path; with the fastpath reference loop
    (``serving_fastpath.enabled=False``) the spec section must be inert and
    the output identical to the plain reference."""
    ref = _engine({"dtype": "float32",
                   "serving_fastpath": {"enabled": False}}).generate(
        PROMPTS, max_new_tokens=9)
    spec = _engine(_spec_conf({"serving_fastpath": {"enabled": False}})
                   ).generate(PROMPTS, max_new_tokens=9)
    assert spec == ref


def test_spec_greedy_identity_with_eos():
    ref = _plain_engine().generate(PROMPTS, max_new_tokens=9)
    eos = ref[0][len(PROMPTS[0]) + 4]
    a = _engine(_spec_conf()).generate(PROMPTS, max_new_tokens=9,
                                       eos_token_id=eos)
    b = _plain_engine().generate(PROMPTS, max_new_tokens=9, eos_token_id=eos)
    assert a == b


def test_spec_model_drafter_identity_and_full_acceptance():
    """The target model attached as its own drafter: every greedy proposal
    matches the verify argmax, so acceptance is exactly 1.0 and the stream
    is still byte-identical (the all-accept bonus path)."""
    eng = _engine(_spec_conf(drafter="model"))
    eng.attach_draft_model(llama, _cfg(), _PARAMS[256])
    got = eng.generate(PROMPTS, max_new_tokens=12)
    ref = _plain_engine().generate(PROMPTS, max_new_tokens=12)
    assert got == ref
    spec = eng.health()["spec_decode"]
    assert spec["rounds_total"] > 0
    assert spec["acceptance_rate"] == 1.0


def test_spec_attach_draft_model_guards():
    with pytest.raises(ValueError):
        _engine().attach_draft_model(llama, _cfg(), _PARAMS[256])
    with pytest.raises(ValueError):
        _engine(_spec_conf(drafter="ngram")).attach_draft_model(
            llama, _cfg(), _PARAMS[256])


def test_spec_sampled_run_valid_and_seeded_deterministic():
    """T>0 spec serving: tokens are valid vocab entries and a fixed seed is
    reproducible run-to-run (the rng advances on-device, one split per verify
    program)."""
    conf = _spec_conf({"temperature": 0.7, "top_k": 20, "top_p": 0.9})
    a = _engine(conf).generate(PROMPTS, max_new_tokens=8)
    b = _engine(conf).generate(PROMPTS, max_new_tokens=8)
    assert a == b
    assert all(0 <= t < 128 for r in a for t in r)


def test_spec_prewarm_covers_ladder_zero_warm_recompiles():
    eng = _engine(_spec_conf())
    eng.generate(PROMPTS, max_new_tokens=9)
    assert eng.ledger.warm_total == 0, \
        "spec serving recompiled a warm bucket — the prewarm key must " \
        "include the verify width"
    eng.generate(PROMPTS, max_new_tokens=9)
    assert eng.ledger.warm_total == 0


def test_spec_declines_when_deadline_armed():
    """Deadline-armed sequences take the conservative path: TTL eviction
    timing must stay byte-identical to the spec-off stack, so no draft/verify
    round may change the loop's iteration structure."""
    clock = FakeClock(tick=0.05)
    eng = _engine(_spec_conf(), clock=clock)
    res = eng.generate([[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=64,
                       strict=False, ttl_s=0.4)
    assert eng.counters.spec_rounds == 0
    clock2 = FakeClock(tick=0.05)
    ref = _engine(config={"dtype": "float32"}, clock=clock2).generate(
        [[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=64, strict=False,
        ttl_s=0.4)
    assert [(r.uid, r.status, r.tokens) for r in res] == \
        [(r.uid, r.status, r.tokens) for r in ref]


# ====================================================== spec OFF byte-identity
def test_spec_off_is_default_and_inert():
    eng = _plain_engine()
    assert not eng.spec_cfg.enabled
    assert eng.spec_stats is None and eng._drafter is None
    out = eng.generate(PROMPTS, max_new_tokens=9)
    assert eng.counters.spec_rounds == 0
    assert eng.counters.spec_proposed == 0
    assert eng.counters.spec_accepted == 0
    assert eng.health()["spec_decode"] == {"enabled": False}
    assert out == eng.generate(PROMPTS, max_new_tokens=9)


def test_spec_off_exposition_has_no_spec_families():
    from deepspeed_tpu.monitor.metrics import MetricsRegistry, populate_from_engine
    eng = _plain_engine()
    eng.generate(PROMPTS, max_new_tokens=6)
    reg = MetricsRegistry()
    populate_from_engine(reg, eng)
    assert not any("spec" in name for name in reg.families)
    # the counter exposition list is pinned: new ServeCounters fields must
    # never leak into a spec-off scrape
    fastpath_counters = sorted(n for n in reg.families
                               if n.startswith("dstpu_fastpath_"))
    assert fastpath_counters == [
        "dstpu_fastpath_burst_tokens_total", "dstpu_fastpath_compact_passes_total",
        "dstpu_fastpath_compiles_total", "dstpu_fastpath_dispatches_total", "dstpu_fastpath_flushes_total",
        "dstpu_fastpath_host_syncs_total",
        "dstpu_fastpath_live_blocks_total", "dstpu_fastpath_live_tokens_total",
        "dstpu_fastpath_loop_iterations_total",
        "dstpu_fastpath_step_tokens_total", "dstpu_fastpath_table_slots_total",
        "dstpu_fastpath_token_slots_total", "dstpu_fastpath_upload_ints_total",
        "dstpu_fastpath_uploads_total"]


def test_serve_counters_fields_spec_tail():
    """The spec counters ride BEHIND the pre-spec fields, and the slot
    counters (ISSUE 24, 25, 27, 35, 40, 43, 44) behind them, so every positional consumer of an
    older field order still reads the same values."""
    assert ServeCounters.FIELDS[-1] == "head_rows"
    assert ServeCounters.FIELDS[-4:-1] == ("scan_chunks", "scan_positions", "scan_live_positions")
    assert ServeCounters.FIELDS[-5] == "attn_token_slots"
    assert ServeCounters.FIELDS[-16:-5] == ("spec_rounds", "spec_proposed", "spec_accepted",
                                          "token_slots", "live_tokens", "table_slots",
                                          "live_blocks", "compact_passes",
                                          "moe_routed_rows", "moe_expert_rows", "kernel_steps")
    c = ServeCounters()
    assert c.spec_rounds == 0 and c.spec_proposed == 0 and c.spec_accepted == 0


# ========================================================== ragged-seam tests
def test_journal_replay_crash_mid_stream_accepted_prefixes_only(tmp_path):
    """Drive a journal-armed spec engine through draft/verify rounds, then
    crash it (no terminal frames, no close).  Replay must recover EXACTLY a
    prefix of the true greedy stream for every request: the WAL frames carry
    accepted runs only — one unverified draft token in a frame would break
    the prefix property."""
    path = str(tmp_path / "spec.wal")
    eng = _engine(_spec_conf({"serving_fault_tolerance": {
        "enabled": True, "fsync_every": 1, "journal_path": path}}))
    prompts = PROMPTS[:2]
    eng.put([0, 1], [list(p) for p in prompts])
    emitted = {0: [], 1: []}
    spec_rounds = 0
    for _ in range(40):
        out = eng._fused_decode(6, greedy=True, eos_token_id=None)
        if out is None:
            out = {u: [t] for u, t in eng.step().items()}
        else:
            spec_rounds = eng.counters.spec_rounds
        for uid, toks in out.items():
            emitted[uid].extend(toks)
        if min(len(v) for v in emitted.values()) >= 10:
            break
    assert spec_rounds > 0, "no draft/verify round ran before the crash"
    # crash: abandon the engine mid-stream — the WAL holds flushed frames only
    ref = _plain_engine().generate([list(p) for p in prompts], max_new_tokens=24)
    state = replay_journal(path)
    for uid, p in enumerate(prompts):
        entry = state.entries[uid]
        assert entry.prompt == p and not entry.done
        cont = ref[uid][len(p):]
        assert len(entry.emitted) >= 10
        assert entry.emitted == cont[:len(entry.emitted)], \
            (f"journal stream for uid {uid} is not a prefix of the true "
             f"greedy stream:\n{entry.emitted}\nvs\n{cont}")
        # and the journal is not ahead of what the engine handed out
        assert entry.emitted == emitted[uid][:len(entry.emitted)]


def test_rejected_draft_across_block_boundary_rolls_back_clean():
    """A draft long enough to allocate past a block boundary, fully rejected:
    the overshoot blocks must come back to the allocator in the same round,
    the block table must shrink to exactly the accepted length, and the
    census/allocator partition invariant must hold."""
    eng = _engine(_spec_conf())
    prompt = list(range(1, 16))  # 15 tokens: 2 blocks of 8
    ref = _plain_engine().generate([list(prompt)], max_new_tokens=4)[0]
    eng.put([0], [list(prompt)])
    while len(eng.manager.seqs[0].tokens) < 16:
        eng.step()  # prefill + the first decode step
    seq = eng.manager.seqs[0]
    assert len(seq.tokens) == 16 and seq.seen_tokens == 15
    assert len(seq.blocks) == 2

    class RejectAllDrafter:
        def propose_batch(self, seqs, k, pad_to, counters=None):
            bad = np.zeros((pad_to, k), np.int32)
            # first proposal differs from the true continuation: guaranteed
            # rejection at position 0, so exactly ONE token is emitted
            bad[:, :] = (ref[16] + 1) % 128
            return bad

    eng._drafter = RejectAllDrafter()
    free_before = eng.manager.allocator.free_blocks
    # k=15 makes ensure_blocks cross into a 4th block (16+1+15 = 32 slots);
    # the accepted run of 1 needs only 3
    out = eng.decode_spec(15, greedy=True, eos_token_id=None)
    assert out is not None and out[0] == [ref[16]]
    assert len(seq.tokens) == 17 and seq.seen_tokens == 16
    assert len(seq.blocks) == 3, \
        f"draft-overshoot blocks survived the rollback: {len(seq.blocks)}"
    assert eng.manager.allocator.free_blocks == free_before - 1
    if eng.kv_obs is not None:
        eng.kv_obs.check_invariant(eng.manager.allocator, eng.manager.seqs)
    # the next plain burst continues the stream correctly over the kept KV
    nxt = eng.decode_burst(2, greedy=True)
    assert nxt is not None and nxt[0] == list(ref[17:19])


def test_deferred_runs_one_sync_and_ragged_unpack():
    packed = jnp.asarray([[3, 10, 11, 12, 0], [1, 20, 99, 99, 99]], jnp.int32)
    c = ServeCounters()
    h = DeferredRuns(packed_dev=packed, uids=[7, 9], counters=c)
    runs = h.runs()
    assert runs == {7: [10, 11, 12], 9: [20]}
    assert c.host_syncs == 1
    h.runs()
    assert c.host_syncs == 1  # cached: the wave pays exactly one sync


def test_spec_scheduler_fused_accounting():
    eng = _engine(_spec_conf())
    eng.generate(PROMPTS, max_new_tokens=9)
    assert eng.counters.spec_rounds > 0
    assert eng.scheduler.fused_tokens > 0
    assert eng.scheduler.fused_steps > 0
    # steps never advance inside a fused round: the sequential count and the
    # fused count partition the work
    assert eng.scheduler.fused_tokens >= eng.scheduler.fused_steps


def test_spec_health_and_metrics_agree():
    from deepspeed_tpu.monitor.metrics import MetricsRegistry, populate_from_engine
    eng = _engine(_spec_conf())
    eng.generate(PROMPTS, max_new_tokens=9)
    spec = eng.health()["spec_decode"]
    assert spec["enabled"] and spec["drafter"] == "ngram"
    assert spec["proposed_total"] == eng.counters.spec_proposed
    assert spec["accepted_total"] == eng.counters.spec_accepted
    assert 0.0 <= spec["acceptance_ewma"] <= 1.0
    assert spec["k"] in spec["ladder"]
    reg = MetricsRegistry()
    populate_from_engine(reg, eng)
    fam = reg.families["dstpu_serving_spec_proposed_total"]
    assert list(fam.samples.values()) == [float(eng.counters.spec_proposed)]
    hist = list(reg.families["dstpu_serving_spec_tokens_per_verify"]
                .samples.values())[0]
    assert hist.count == sum(spec["tokens_per_verify"].values())
