"""Speculative decoding for the v2 serving engine — draft, verify, accept.

The fused decode burst (fastpath.py / engine_v2.decode_burst) already
collapses host round-trips: k tokens per sync.  But every one of those k
tokens still costs a full target-model forward, and decode is
HBM-bandwidth-bound — the weights stream from HBM once PER TOKEN.
Speculative decoding (Leviathan et al., "Fast Inference from Transformers
via Speculative Decoding") amortizes that stream k-for-1: a cheap DRAFTER
proposes k tokens per sequence, the target model scores all k in ONE
batched forward over the paged KV pool (positions ride the existing block
tables), and on-device rejection sampling accepts the longest valid prefix
plus one corrected token — between 1 and k+1 tokens per verify, with the
output distribution provably the target's.

This module owns the pieces that are independent of the engine's dispatch
machinery:

- :func:`rejection_select` — the on-device accept/reject kernel.  For the
  deterministic drafters below the proposal distribution is a delta, so the
  exact residual-sampling rule simplifies: accept ``d_i`` with probability
  ``p_i(d_i)`` under the FILTERED target distribution (the same
  temperature/top-k/top-p masking ``_sample`` applies — shared via
  ``engine._filter_logits`` so spec and plain sampling can never diverge),
  and on the first rejection resample from ``p_i`` with ``d_i`` masked out
  (the normalized residual ``max(p - q, 0)`` of a delta proposal).  Greedy
  decode degenerates to "accept while argmax agrees, then emit argmax" —
  token-identical to spec-off greedy decode.  Everything stays on device;
  the packed ``[n, k+2]`` result (accept count + emitted run) rides the
  round's ONE wave-boundary materialize.
- :class:`NgramDrafter` — the zero-weight prompt-lookup fallback: propose
  the continuation of the longest recent n-gram matching the sequence's
  suffix (pure host python over token ids the host already owns; no second
  model, no device work).
- :class:`ModelDrafter` — a small draft model from the model zoo running
  greedily against its OWN paged pool (catch-up prefill + k-step draft scan
  in one compiled program; proposals never visit the host — the device
  array feeds the verify program directly).
- :class:`AdaptiveKController` — EWMA-of-acceptance k controller restricted
  to a small static ladder so every verify width is a prewarmable bucket;
  at the k=1 floor the engine degrades to the plain burst path and the
  controller re-probes periodically.
- :class:`SpecDecodeStats` — proposed/accepted/emitted counters and the
  tokens-per-verify histogram behind ``serving_spec_*`` metrics and
  ``health()["spec_decode"]``.

Zero-host-sync contract: accept/reject accumulation stays on device until
the engine's wave-boundary ``fastpath.materialize()`` — dslint's
``host-sync-in-hot-path`` rule scans this WHOLE file (module level
included) with the full explicit-fetch set, same as kv_metrics.py.
"""

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...monitor.compile_events import compile_later
from ...monitor.program_scopes import FirstCall


def spec_k_ladder(k_max: int) -> Tuple[int, ...]:
    """The static draft-length ladder: 1 (the degrade-to-burst floor) then
    pow2-1 rungs capped at the configured k, so verify widths k+1 stay powers
    of two as long as the cap itself is one.  A static ladder is what lets
    the prewarm enumerate every verify program ahead of serving — an
    unconstrained adaptive k would recompile on every drift."""
    rungs = {min(int(k_max), v) for v in (1, 3, 7, 15, 31, 63)}
    return tuple(sorted(rungs))


def rejection_select(logits, draft, rng, *, sample_cfg):
    """On-device accept/reject for one verify round (traced into the engine's
    fused verify program — never called eagerly).

    ``logits``: [n, k+1, V] target logits over (input token + k draft
    tokens); position i is conditioned on the draft prefix d_0..d_{i-1}.
    ``draft``: [n, k] proposed tokens.  ``sample_cfg``: None for greedy,
    else (temperature, top_k, top_p) — the engine's live sampling knobs.

    Returns ``(packed, rng)`` with packed [n, k+2] int32 rows
    ``[count, e_0, ..., e_k]``: the row emits ``e_0..e_{count-1}``
    (1 <= count <= k+1).  Accepted positions satisfy e_i == d_i; the final
    emitted token is the corrected/bonus sample and becomes the sequence's
    next pending input.

    Exactness (deterministic drafter => delta proposal q = δ(d_i)):
    accept d_i with prob p̃_i(d_i); the residual max(p̃ - q, 0)/Z is p̃ with
    d_i zeroed, so the correction resamples from p̃_i masked at d_i; if all
    k accept, the bonus samples p̃_k unmasked.  The marginal of each emitted
    token is exactly p̃ — the same filtered distribution ``_sample`` draws
    from, so spec on/off are distribution-identical (and token-identical
    under greedy, where acceptance is argmax agreement).
    """
    n, kp1, vocab = logits.shape
    k = kp1 - 1
    # sample_cfg is a static Python tuple bound before jit at the verify
    # compile seam, so this branch specializes the trace
    if sample_cfg is None or sample_cfg[0] == 0.0:
        tgt = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
        acc = (draft == tgt[:, :k]).astype(jnp.int32)
        count = 1 + jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
        packed = jnp.concatenate([count[:, None].astype(jnp.int32), tgt], axis=1)
        return packed, rng
    from ..engine import _filter_logits
    temperature, top_k, top_p = sample_cfg
    filt = _filter_logits(logits.reshape(n * kp1, vocab), temperature=temperature,
                          top_k=top_k, top_p=top_p).reshape(n, kp1, vocab)
    logp = jax.nn.log_softmax(filt, axis=-1)
    lp_draft = jnp.take_along_axis(logp[:, :k], draft[..., None], axis=-1)[..., 0]
    rng, ku, kr = jax.random.split(rng, 3)
    u = jax.random.uniform(ku, (n, k))
    # log-space compare; the 1e-38 floor keeps a u=0 draw (prob ~2^-23 per
    # element, NOT negligible over a serve) from accepting a top-k/top-p
    # MASKED draft token through log(0) = -inf < -1e30
    acc = (jnp.log(jnp.maximum(u, 1e-38)) < lp_draft).astype(jnp.int32)
    a = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)          # leading accepts, 0..k
    count = a + 1
    # correction/bonus sample at position a: residual of the delta proposal
    # (mask d_a) below k; the bonus position a == k samples p̃_k unmasked
    row = jnp.take_along_axis(filt, a[:, None, None], axis=1)[:, 0]  # [n, V]
    d_pad = jnp.concatenate([draft, draft[:, :1]], axis=1)  # [n, k+1]; col k unused
    d_at_a = jnp.take_along_axis(d_pad, a[:, None], axis=1)[:, 0]
    mask = (jnp.arange(vocab, dtype=jnp.int32)[None, :] == d_at_a[:, None]) \
        & (a < k)[:, None]
    row = jnp.where(mask, -jnp.inf, row)
    fix = jax.random.categorical(kr, row, axis=-1).astype(jnp.int32)
    pos = jnp.arange(kp1, dtype=jnp.int32)[None, :]
    emitted = jnp.where(pos == a[:, None], fix[:, None], d_pad)
    packed = jnp.concatenate([count[:, None].astype(jnp.int32), emitted], axis=1)
    return packed, rng


class NgramDrafter:
    """Zero-weight prompt-lookup drafter (the no-second-model fallback).

    Proposes the continuation of the rightmost earlier occurrence of the
    sequence's longest suffix n-gram — pure host python over token ids the
    host already owns (spec rounds run at wave boundaries, so every token is
    materialized), zero device work, proposals ride the verify upload.
    Effective exactly where cheap speculation should be: repetitive /
    templated continuations, copy spans, and the short cycles greedy decode
    falls into; elsewhere acceptance collapses and the adaptive-k controller
    degrades the engine back to the plain burst."""

    #: bound the suffix-match scan to the most recent history — proposal cost
    #: must stay O(window), not O(sequence length)
    WINDOW = 256

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1):
        self.ngram_max = max(int(ngram_max), int(ngram_min))
        self.ngram_min = max(1, int(ngram_min))

    def propose(self, tokens: List[int], k: int) -> List[int]:
        """Exactly k proposed tokens for one sequence's token history."""
        hist = tokens[-self.WINDOW:]
        m_len = len(hist)
        for m in range(self.ngram_max, self.ngram_min - 1, -1):
            if m_len <= m:
                continue
            suffix = hist[m_len - m:]
            for j in range(m_len - m - 1, -1, -1):
                if hist[j:j + m] == suffix:
                    cont = hist[j + m:j + m + k]
                    if cont:
                        out = list(cont)
                        while len(out) < k:
                            out.append(out[-1])
                        return out
        return [hist[-1]] * k  # no match: propose a repeat run

    def propose_batch(self, seqs, k: int, pad_to: int, counters=None):
        """[pad_to, k] int32 host proposals, row i for seqs[i] (padded rows
        zero — they decode into the trash block and are never read)."""
        out = np.zeros((pad_to, k), np.int32)
        for i, seq in enumerate(seqs):
            out[i, :] = self.propose(seq.tokens, k)
        return out


class ModelDrafter:
    """A small draft model from the model zoo proposing greedily against its
    OWN paged KV pool.

    The drafter mirrors the target's paged-attention contract
    (``forward_paged`` + block tables) over a private pool: each round it
    catches up on tokens the target accepted since its last draft (their
    positions simply overwrite whatever rejected-draft KV was left there —
    paged attention never reads past ``start_pos + n_tokens``, the same
    argument that makes the target's own rejected positions harmless), then
    drafts k tokens in one compiled catch-up-plus-scan program.  Proposals
    stay ON DEVICE — the [n, k] array feeds the engine's verify program
    directly, so drafting adds dispatches but zero host syncs.

    Under a TP mesh the drafter runs fully replicated (params, pool and
    batch all ``PartitionSpec()``): a draft model small enough to be worth
    drafting with is small enough to replicate, and replication keeps the
    proposal array consumable by the shard_mapped verify without resharding.
    """

    def __init__(self, model_module, model_config, params, *, num_blocks: int,
                 block_size: int, max_blocks_per_seq: int, dtype=jnp.float32,
                 mesh=None, ledger=None):
        self.model = model_module
        self.cfg = model_config
        self.block_size = int(block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self._ledger = ledger
        self._replicated = None
        # construction-time host->device upload of draft weights (not a fetch)
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, dtype), params)
        kv = model_module.init_paged_cache(model_config, num_blocks, block_size,
                                           dtype=dtype)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._replicated = NamedSharding(mesh, PartitionSpec())
            params = jax.device_put(params, self._replicated)
            kv = jax.device_put(kv, self._replicated)
        self.params = params
        self.kv = kv
        # trivial private allocator: the last block is the trash slot padded
        # rows decode into (same convention as the ragged manager's pool)
        self.trash_block = num_blocks - 1
        self._free: List[int] = list(range(num_blocks - 1))
        self._state: Dict[int, Dict] = {}  # uid -> {"blocks": [...], "seen": int}
        self._fns: Dict = {}

    # ------------------------------------------------------------ bookkeeping
    def _gc(self, live_uids) -> None:
        for uid in [u for u in self._state if u not in live_uids]:
            self._free.extend(self._state.pop(uid)["blocks"])

    def _ensure_blocks(self, st: Dict, upto_tokens: int) -> bool:
        need = min(-(-upto_tokens // self.block_size), self.max_blocks_per_seq)
        grow = need - len(st["blocks"])
        if grow > len(self._free):
            return False
        for _ in range(max(0, grow)):
            st["blocks"].append(self._free.pop())
        return True

    def _compiled_draft(self, n: int, t: int, b: int, k: int):
        key = (n, t, b, k)
        fn = self._fns.get(key)
        if fn is None:
            model, cfg, bs = self.model, self.cfg, self.block_size
            ones = jnp.ones((n,), jnp.int32)

            def draft(params, kv, tokens, nt, start, tables):
                logits, kv = model.forward_paged(cfg, params, tokens, nt, start,
                                                 tables, kv, block_size=bs)
                last = jnp.maximum(nt - 1, 0)
                row = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
                d0 = jnp.argmax(row, axis=-1).astype(jnp.int32)
                if k == 1:  # static Python int baked into the compile key
                    return kv, d0[:, None]

                def body(carry, _):
                    kv, tok, pos = carry
                    lg, kv = model.forward_paged(cfg, params, tok[:, None], ones,
                                                 pos, tables, kv, block_size=bs)
                    nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                    return (kv, nxt, pos + 1), nxt

                (kv, _, _), rest = jax.lax.scan(body, (kv, d0, start + nt), None,
                                                length=k - 1)
                return kv, jnp.concatenate([d0[:, None], rest.T], axis=1)

            draft.__name__ = f"draft_n{n}_t{t}_b{b}_k{k}"
            if self._replicated is not None:
                rep = self._replicated
                self._fns[key] = jax.jit(  # dslint: disable=donation-after-use  # call-site contract: propose_batch reassigns self.kv from the result in the same statement
                    draft, donate_argnums=(1,), out_shardings=(rep, rep))
            else:
                self._fns[key] = jax.jit(draft, donate_argnums=(1,))  # dslint: disable=donation-after-use  # call-site contract: propose_batch reassigns self.kv from the result in the same statement
            fn = self._fns[key]
            if self._ledger is not None:
                self._ledger.record("draft", key, name=draft.__name__)

                def seen(fn, args, name=draft.__name__):
                    # the first call's shapes, for program_scopes(); then the bare program
                    program = compile_later(fn, args)
                    if program is not None:
                        self._fns[key] = fn
                        self._ledger.built(name, program)
                self._fns[key] = fn = FirstCall(fn, seen)
        return fn

    # ---------------------------------------------------------------- propose
    def propose_batch(self, seqs, k: int, pad_to: int, counters=None):
        """Draft k tokens per sequence; returns a DEVICE [pad_to, k] int32
        array (row i for seqs[i]) or None when the private pool can't cover
        the round (the engine falls back to the plain burst)."""
        self._gc({s.uid for s in seqs})
        n = pad_to
        rows: List[Tuple[Dict, List[int]]] = []
        t_max = 1
        for s in seqs:
            st = self._state.setdefault(s.uid, {"blocks": [], "seen": 0})
            pending = s.tokens[st["seen"]:]
            if not pending:  # catch-up must feed >= 1 token; re-feed the last
                st["seen"] -= 1
                pending = s.tokens[-1:]
            if not self._ensure_blocks(st, len(s.tokens) + k):
                return None
            rows.append((st, pending))
            t_max = max(t_max, len(pending))
        t = 1
        while t < t_max:
            t *= 2
        b = 1
        while b < max(len(st["blocks"]) for st, _ in rows):
            b *= 2
        tokens = np.zeros((n, t), np.int32)
        nt = np.zeros((n,), np.int32)
        start = np.zeros((n,), np.int32)
        tables = np.full((n, b), self.trash_block, np.int32)
        for i, (st, pending) in enumerate(rows):
            tokens[i, :len(pending)] = pending
            nt[i] = len(pending)
            start[i] = st["seen"]
            tables[i, :len(st["blocks"])] = st["blocks"]
            # positions < len(tokens) now hold real-token KV; drafted
            # positions beyond are junk the NEXT catch-up overwrites
            st["seen"] = st["seen"] + len(pending)
        fn = self._compiled_draft(n, t, b, k)
        if counters is not None:
            counters.dispatches += 1
            counters.uploads += 4
            counters.upload_ints += int(tokens.size + nt.size + start.size
                                        + tables.size)
        up = (lambda a: jax.device_put(a, self._replicated)) \
            if self._replicated is not None else jnp.asarray
        self.kv, draft = fn(self.params, self.kv, up(tokens), up(nt), up(start),
                            up(tables))
        return draft


class AdaptiveKController:
    """EWMA-of-acceptance draft-length controller over the static ladder.

    ``note_round`` folds one verify round's acceptance fraction into the
    EWMA; the live k steps UP one rung when the EWMA clears
    ``raise_threshold`` and DOWN one rung below ``lower_threshold`` — never
    off-ladder, so every verify width the controller can pick is already a
    compiled bucket.  At the k=1 floor speculation isn't worth a drafter
    call: :meth:`next_k` returns 1 and the engine runs the plain burst
    (zero spec overhead, zero recompiles); every ``probe_every`` floored
    rounds the controller re-probes the lowest speculative rung so a
    regime change (e.g. the decode entering a repetitive span) can win k
    back."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.ladder = spec_k_ladder(cfg.k)
        self._idx = len(self.ladder) - 1  # start optimistic, at the cap
        self.ewma: Optional[float] = None
        self._floor_rounds = 0

    @property
    def k(self) -> int:
        return self.ladder[self._idx]

    def next_k(self) -> int:
        """The draft length to use for the NEXT fused round."""
        if not self.cfg.adaptive_k:
            return self.cfg.k
        if self.ladder[self._idx] <= 1:
            self._floor_rounds += 1
            if self._floor_rounds >= self.cfg.probe_every and len(self.ladder) > 1:
                self._floor_rounds = 0
                self._idx = 1  # re-probe the lowest speculative rung
        return self.ladder[self._idx]

    def note_round(self, proposed: int, accepted: int) -> None:
        if not self.cfg.adaptive_k or proposed <= 0:
            return
        rate = accepted / proposed
        a = self.cfg.ewma_alpha
        self.ewma = rate if self.ewma is None else a * rate + (1 - a) * self.ewma
        if self.ewma >= self.cfg.raise_threshold:
            self._idx = min(self._idx + 1, len(self.ladder) - 1)
        elif self.ewma <= self.cfg.lower_threshold:
            self._idx = max(self._idx - 1, 0)

    def snapshot(self) -> Dict[str, object]:
        return {"k": self.k, "ladder": list(self.ladder),
                "acceptance_ewma": (round(self.ewma, 4)
                                    if self.ewma is not None else None)}


class SpecDecodeStats:
    """Host-side spec-decode accounting behind ``serving_spec_*`` metrics
    and ``health()["spec_decode"]`` — proposed/accepted lifetime counters,
    emitted totals, and the tokens-per-verify histogram (bounded: a verify
    of k emits between 1 and k+1 tokens per sequence)."""

    def __init__(self):
        self.rounds_total = 0
        self.proposed_total = 0
        self.accepted_total = 0
        self.emitted_total = 0
        self.fallback_rounds_total = 0  # fused rounds that ran the plain burst
        self.tokens_per_verify: Dict[int, int] = {}

    def note_round(self, proposed: int, accepted: int,
                   run_lengths: List[int]) -> None:
        self.rounds_total += 1
        self.proposed_total += int(proposed)
        self.accepted_total += int(accepted)
        self.emitted_total += int(sum(run_lengths))
        for r in run_lengths:
            self.tokens_per_verify[int(r)] = self.tokens_per_verify.get(int(r), 0) + 1

    def acceptance_rate(self) -> float:
        return self.accepted_total / max(self.proposed_total, 1)

    def snapshot(self) -> Dict[str, object]:
        return {"rounds_total": self.rounds_total,
                "proposed_total": self.proposed_total,
                "accepted_total": self.accepted_total,
                "emitted_total": self.emitted_total,
                "fallback_rounds_total": self.fallback_rounds_total,
                "acceptance_rate": round(self.acceptance_rate(), 4),
                "tokens_per_verify": {str(c): n for c, n in
                                      sorted(self.tokens_per_verify.items())}}
