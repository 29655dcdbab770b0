"""Continuous-batching inference engine (FastGen analog).

Reference InferenceEngineV2 (inference/v2/engine_v2.py:30): ``put()`` enqueues
requests, each ``step()`` runs ONE ragged forward over a SplitFuse-scheduled
token batch against the paged KV pool, and sampled tokens stream back per uid.

TPU shape discipline: the ragged batch is padded to fixed (max_seqs, chunk)
buckets so jit compiles a small set of programs (one per bucket) instead of
one per ragged shape — the XLA analog of the reference's CUDA-graph-free
ragged kernels.
"""

import functools
import inspect
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ...compat import shard_map
from ...models.transformer import STATE, TALLY, flat_slots, paged_step_slots
from ...monitor import compile_events, program_scopes
from ...monitor.perf import PHASES, CompileLedger, StepPhaseProfiler
from ...monitor.tracing import RequestTracer
from ...parallel.mesh import TENSOR_AXIS, MeshTopology
from ...runtime.heartbeat import (HEARTBEAT_DIR_ENV, HEARTBEAT_INTERVAL_ENV,
                                  NULL_HEARTBEAT, OPS_DIR_ENV, SERVING_FSYNC_ENV,
                                  SERVING_GENERATION_ENV, SERVING_JOURNAL_ENV,
                                  HeartbeatWriter)
from ...utils.env import env_float, env_int
from ...utils.logging import log_dist
from ..config import DTYPES as _DTYPES, load_inference_config
from .admission import (DEADLINE_EXPIRED, FAILED, OK, PREEMPT_REQUEUED_EXHAUSTED, SHED,
                        AdmissionQueue, RecoveredRequest, RequestResult,
                        ServingStalledError)
from .blocked_allocator import KVAllocationError
from .fastpath import (FED_SENTINEL, PENDING_TOKEN, DeferredRuns, DeferredTokens,
                       DeviceBatchState, ServeCounters, materialize, round_up_pow2)
from .journal import RequestJournal, journal_bytes
from .kv_metrics import KVObservability
from .qos import QosPolicy
from .ragged_manager import PrefixCache, RaggedStateManager
from .scheduler import SplitFuseScheduler
from .spec_decode import (AdaptiveKController, ModelDrafter, NgramDrafter,
                          SpecDecodeStats, rejection_select)

def candidate_sample(row, rng, *, temperature, top_k, top_p, axis):
    """Candidate-set sampling over a vocab-sharded logits row (reference
    logits_gather ragged kernels): each shard contributes its local top-k'
    (logit, global index) pairs, k' = max(top_k, 64), and the full sampler
    runs on the gathered [N, k'*tp] candidate row — O(k'*tp) pairs on the
    wire per token instead of an O(V) full-vocab gather.  Exact whenever the
    candidates cover the top-k/nucleus set: always for top-k <= k'; for
    top-p the mass outside 64*tp candidates is negligible for real
    vocabularies (and zero when k'*tp >= V, where this is a permuted full
    row).  ``rng`` must be replicated so every shard samples the identical
    candidate index.  Returns (global token ids [N], rng)."""
    from ..engine import _sample
    vlocal = row.shape[-1]
    kc = min(vlocal, max(int(top_k) if top_k else 0, 64))
    vals, idx = jax.lax.top_k(row, kc)
    offset = jax.lax.axis_index(axis).astype(jnp.int32) * vlocal
    gidx = idx.astype(jnp.int32) + offset
    allv = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
    alli = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
    cand, rng = _sample(allv, rng, temperature=temperature, top_k=top_k, top_p=top_p)
    tok = jnp.take_along_axis(alli, cand[:, None], axis=1)[:, 0]
    return tok, rng


class InferenceEngineV2:

    # decode-burst length while any live request carries a deadline OR the
    # admission queue is non-empty: the deadline is only enforceable between
    # host round-trips, so this bounds eviction overshoot (tokens decoded past
    # expiry) and admission latency while keeping ~SLICE x fewer round-trips
    # than stepwise decode
    BURST_DEADLINE_SLICE = 8
    # table-width bucketing (serving fastpath satellite): widths grow in
    # block-table-slot steps of TABLE_STEP with sticky hysteresis — a shrink
    # only happens after TABLE_SHRINK_PATIENCE consecutive steps of slack, so
    # one long sequence entering/leaving the batch doesn't force a recompile
    # cascade across every (n, t) bucket it touches mid-serve
    TABLE_STEP = 4
    TABLE_SHRINK_PATIENCE = 16

    @compile_events.engine_init
    def __init__(self, model_module, model_config, params, config: Optional[Dict] = None,
                 num_blocks: int = 512, block_size: int = 16,
                 max_blocks_per_seq: int = 64, token_budget: int = 256,
                 max_seqs_per_step: int = 32,
                 topology: Optional[MeshTopology] = None,
                 telemetry=None, clock: Optional[Callable[[], float]] = None,
                 journal: Optional[RequestJournal] = None,
                 table_step: Optional[int] = None):
        self.config = load_inference_config(config)
        if table_step is not None:
            # the table's width grows in steps of this many slots (class default 4): every width
            # is a program of its own, so an engine whose sequences span a hundred blocks and
            # more (ISSUE 43: 16k-token prompts, 132 slots) takes coarser steps and compiles a
            # handful of widths, at the price of up to ``table_step - 1`` dead slots a row
            self.TABLE_STEP = int(table_step)
        self.model = model_module
        self.model_config = model_config
        self.dtype = _DTYPES[self.config.dtype]
        self.block_size = block_size
        # a family whose layers keep a FIXED state a sequence beside the paged
        # pool (ISSUE 33: short convolutions) says so by stating its bytes; it
        # gets one state slot a row of a step, which the manager hands out
        state_bytes = getattr(model_module, "state_bytes_per_seq", None)
        self.state_bytes_per_seq = int(state_bytes(model_config)) if state_bytes else 0
        self.manager = RaggedStateManager(
            num_blocks, block_size, max_blocks_per_seq,
            state_slots=max_seqs_per_step if state_bytes else 0)
        # copy-on-write prefix caching (ISSUE 13): requests whose leading full
        # prompt blocks match live computed blocks map them read-only
        # (allocator refcount) and prefill only their divergent tail — the
        # realized form of the counterfactual PR 12's PrefixObservatory
        # measures, keyed on the same chained token-block hashes.  The engine
        # contributes the ONE device action: the CoW block copy for prompts
        # cached to their last token.
        self.prefix_cfg = self.config.serving_prefix_cache
        if self.prefix_cfg.enabled:
            self.manager.prefix_cache = PrefixCache(
                block_size, cow=self.prefix_cfg.cow,
                defer_shared_prefill=self.prefix_cfg.defer_shared_prefill)
            self.manager.cow_copy = self._cow_copy_block
        # block-level KV-pool observability (ISSUE 12): census + prefix-
        # sharing opportunity + capacity forecast, all from host state the
        # manager/allocator already own — zero device syncs (the kv-obs smoke
        # proves ServeCounters byte-identical on vs off)
        self.kv_cfg = self.config.serving_kv_observability
        self.kv_obs: Optional[KVObservability] = None
        if self.kv_cfg.enabled:
            self.kv_obs = KVObservability(
                block_size, num_blocks, self.manager.trash_block,
                ewma_alpha=self.kv_cfg.ewma_alpha,
                pressure_steps=self.kv_cfg.pressure_steps,
                age_buckets_per_decade=self.kv_cfg.age_buckets_per_decade)
            self.manager.census = self.kv_obs.census
        # serve-step clock for kv observability: stepwise dispatches count 1,
        # a fused decode burst of k counts k — so block ages and the
        # forecaster's per-step rates mean the same thing on every decode
        # path (the scheduler's step counter never advances inside a burst)
        self._kv_steps = 0
        # telemetry: a monitor.TelemetryCollector; the scheduler emits its
        # gauges through it and step() adds serving rates (ISSUE 1 tentpole)
        self.telemetry = telemetry
        # serving resilience (ISSUE 4): admission control + load shedding in
        # front of the manager, deadlines on an injectable clock (fault tests
        # drive a fake one), preemption policy shared with the scheduler
        self.resilience = self.config.serving_resilience
        self._clock = clock if clock is not None else time.monotonic
        # an injected clock makes gauge timestamps deterministic too (ISSUE 11
        # satellite): record_gauges stamps the engine clock's last donated
        # read instead of wall time, so FakeClock tests assert exact stamps
        self._clock_injected = clock is not None
        # request-lifecycle tracing (ISSUE 6): span chains per uid, SLO
        # latency histograms (TTFT/TBT/e2e/queue-wait), and the always-on
        # flight recorder — consumes ONLY the injectable clock, at points
        # the host already touches, so tracing adds zero device syncs
        self.tracer = RequestTracer(self.config.serving_tracing,
                                    clock=self._clock, telemetry=telemetry)
        # multi-tenant QoS (ISSUE 19): per-tenant quotas + weighted-fair
        # dequeue + victim steering.  Constructed only when the section is
        # armed — self.qos is None otherwise and every downstream seam
        # (admission, scheduler, metrics) keeps its pre-QoS behavior
        self.qos = None
        if self.config.serving_qos.enabled:
            self.qos = QosPolicy(self.config.serving_qos, clock=self._clock)
            self.qos.kv_blocks_of = self.manager.tenant_blocks
        self.admission = AdmissionQueue(self.resilience, clock=self._clock,
                                        tracer=self.tracer, qos=self.qos)
        self._deadline_expired_total = 0
        self._stall_streak = 0
        self.stalls_total = 0  # lifetime watchdog trips (streaks are transient)
        self._queue_wait_s = 0.0
        self.scheduler = SplitFuseScheduler(token_budget, max_seqs_per_step,
                                            telemetry=telemetry,
                                            resilience=self.resilience,
                                            tracer=self.tracer,
                                            gauge_timestamp=self._gauge_timestamp)
        self.scheduler.qos = self.qos
        # serving fault tolerance (ISSUE 8): durable request journal + serve-
        # iteration liveness heartbeat.  Both arm from config OR the
        # ServingSupervisor's env exports (DSTPU_SERVING_JOURNAL +
        # DSTPU_HEARTBEAT_DIR), so a supervised worker needs no config
        # changes — the same contract the elastic training agent uses.  The
        # env heartbeat dir is honored ONLY under a serving supervisor (the
        # journal env marks that); a serving engine inside a supervised
        # TRAINING worker must not clobber the trainer's rank stamps.
        self.ft = self.config.serving_fault_tolerance
        generation = int(os.environ.get(SERVING_GENERATION_ENV, "0") or 0)
        if journal is None:
            jp = os.environ.get(SERVING_JOURNAL_ENV) or \
                (self.ft.journal_path if self.ft.enabled else None)
            if jp:
                # the supervisor exports its fsync policy alongside the
                # journal path — without this, a supervised worker's default
                # config would silently pin strict mode and the operator's
                # fsync_every choice would be dead in subprocess deployments
                journal = RequestJournal(
                    jp, fsync_every=env_int(SERVING_FSYNC_ENV, self.ft.fsync_every),
                    seed=self.config.seed)
        self.journal = journal
        if self.journal is not None:
            self.journal.open_generation(generation)
        self._heartbeat = NULL_HEARTBEAT
        under_supervisor = bool(os.environ.get(SERVING_JOURNAL_ENV))
        hb_dir = (os.environ.get(HEARTBEAT_DIR_ENV) if under_supervisor else None) \
            or (self.ft.heartbeat_dir if self.ft.heartbeat else None)
        if hb_dir:
            self._heartbeat = HeartbeatWriter(
                hb_dir, rank=0,
                interval_s=env_float(HEARTBEAT_INTERVAL_ENV,
                                     self.ft.heartbeat_interval_s),
                generation=generation)
        # recovery counters surfaced by health()/state_snapshot(); the
        # supervisor stamps restarts_total/degraded onto each engine it builds
        self.ft_stats = {"restarts_total": 0, "recovered_requests_total": 0,
                         "degraded": False}
        # pull-based ops plane (ISSUE 11): a /metrics + /healthz + /statez
        # endpoint over host-side CACHED snapshots.  The serve loop refreshes
        # the cache (throttled on the injectable clock) at host-touch points
        # it already pays for; scrape handlers only read the cached strings,
        # so a scrape can never trigger a device sync or race a step.  The
        # supervisor-exported DSTPU_OPS_DIR additionally publishes per-rank
        # snapshot/textfile pairs for fleet-level merging — honored ONLY
        # under a serving supervisor (same gate as the heartbeat dir above:
        # a serving engine inside a supervised TRAINING worker must not
        # clobber the trainer's ops rank files).
        self.ops_cfg = self.config.ops_server
        ops_dir = (os.environ.get(OPS_DIR_ENV) if under_supervisor else None) \
            or self.ops_cfg.textfile_dir
        self._ops = None
        if self.ops_cfg.enabled or ops_dir:
            from ...monitor.ops_server import OpsPublisher
            self._ops = OpsPublisher(self.ops_cfg, generation=generation,
                                     ops_dir=ops_dir,
                                     rank=int(os.environ.get("RANK", "0") or 0),
                                     owner="serving engine")
        self.ops = self._ops.server if self._ops is not None else None
        self.topology = topology
        self.tp = topology.axis_size(TENSOR_AXIS) if topology is not None else 1
        self._warn_truncated_nucleus()
        params = jax.tree_util.tree_map(lambda x: jnp.asarray(x, self.dtype), params)
        if self.manager.state_slots and self.config.serving_spec_decode.enabled:
            # a rejected draft is rolled back by blocks (rollback_blocks); a
            # state that has absorbed the rejected tokens cannot follow
            raise ValueError(
                f"serving_spec_decode is not supported for {model_module.__name__}: its "
                f"layers keep a per-sequence state that speculative decoding's rollback "
                f"of rejected tokens cannot restore")
        stateful = {"state_slots": self.manager.state_slots} if self.manager.state_slots else {}
        kv = model_module.init_paged_cache(model_config, num_blocks, block_size, dtype=self.dtype,
                                           **stateful)
        if self.tp > 1:
            # TP-sharded serving (reference engine_v2.py:81 builds on a TP group;
            # sharding helpers inference/v2/model_implementations/sharding/)
            from . import tp as _tp
            _tp.validate_model(model_config, self.tp, model_module=model_module)
            self._param_specs = _tp.param_specs(model_module, params, self.tp,
                                                model_config=model_config)
            self._kv_specs = _tp.kv_pool_spec(kv, self.tp)
            params = _tp.place(topology, params, self._param_specs)
            kv = _tp.place(topology, kv, self._kv_specs)
        self.params = params
        self.kv = kv
        self._fwd_cache: Dict = {}
        self._rng = jax.random.PRNGKey(self.config.seed)
        self.max_blocks_per_seq = max_blocks_per_seq
        # serving fast path (ISSUE 5): persistent device-resident batch
        # buffers, deferred pick syncs, and host-link counters that make the
        # orchestration cost observable (fastpath.py).  Under TP the batch
        # state replicates over the engine's mesh (ISSUE 15) so the same
        # ≤1-sync loop drives the shard_mapped forward unchanged.
        self.fastpath = self.config.serving_fastpath
        # the step's live-token bound, handed to every family's forward
        # (models/transformer.py paged_forward states the contract) so a bucket
        # with more slots than the scheduler ever fills computes its live
        # tokens, not its padding (ISSUE 25).  The reference step stays padded:
        # it is the oracle the compacted program is compared with.
        self._live_token_bound: Optional[int] = (
            token_budget if self.fastpath.enabled else None)
        kernel_slots, attn_slots = paged_step_slots(model_module, model_config, kv, self.dtype,
                                                    self.tp)
        counted = dict(kernel_slots=kernel_slots, attn_slots=attn_slots)
        if hasattr(model_module, "moe_expert_rows"):  # a mixture of experts counts its rows
            counted.update(moe_picks=model_module.moe_picks_per_token(model_config),
                           moe_rows=functools.partial(model_module.moe_expert_rows, model_config))
        if hasattr(model_module, "state_scan"):  # a state scanned in chunks counts them
            counted.update(scan=model_module.state_scan(model_config))
        if hasattr(model_module, "selected_keys"):  # a learned selection of the cache counts its keys
            counted.update(selected=(*model_module.selected_keys(model_config), block_size))
        if hasattr(model_module, "pick_tallies"):  # picks whose kind only the device knows
            counted.update(tallied=model_module.pick_tallies(model_config))
        if hasattr(model_module, "attention_windows"):  # layers that differ in their window count blocks
            counted.update(windowed=(model_module.attention_windows(model_config), block_size))
        self.counters = ServeCounters(**counted)
        # serving performance observatory (ISSUE 16): the compile ledger is
        # always on (no clock reads, no device work) and is the single source
        # of truth behind counters.compiles; the slot counters (ISSUE 24) are
        # host integers bumped where a program is launched, always on too;
        # the phase profiler reads the injectable clock at
        # phase boundaries and is gated on serving_perf.enabled so the off
        # path performs zero extra clock reads (byte-identical FakeClock runs).
        # What each recorded program cost to trace, lower and load is the
        # process's set-up account's (ISSUE 36), joined by program name
        self.perf_cfg = self.config.serving_perf
        self.ledger = CompileLedger(self.counters, tracer=self.tracer,
                                    events=compile_events.ACCOUNT)
        self.phase_profiler = StepPhaseProfiler(self.perf_cfg, clock=self._clock,
                                                tracer=self.tracer)
        self.batch_state = DeviceBatchState(
            self.counters, mesh=self.topology.mesh if self.tp > 1 else None,
            ledger=self.ledger)
        # speculative decoding (ISSUE 20): drafter + adaptive-k controller +
        # accounting behind the fused draft/verify path (decode_spec).
        # Constructed only when the section is armed — with spec off (the
        # default) every seam below (tokens, counters, journal bytes,
        # Prometheus exposition) is byte-identical to the pre-spec stack.
        self.spec_cfg = self.config.serving_spec_decode
        self.spec_stats: Optional[SpecDecodeStats] = None
        self._spec_controller: Optional[AdaptiveKController] = None
        self._drafter = None
        if self.spec_cfg.enabled:
            self.spec_stats = SpecDecodeStats()
            self._spec_controller = AdaptiveKController(self.spec_cfg)
            if self.spec_cfg.drafter == "ngram":
                self._drafter = NgramDrafter(self.spec_cfg.ngram_max,
                                             self.spec_cfg.ngram_min)
            # drafter == "model": speculation stays dormant (plain burst)
            # until the caller provides weights via attach_draft_model()
        self._inflight: Optional[DeferredTokens] = None
        self._table_width = 0
        self._table_slack = 0
        # health() freshness stamp: advanced at state-change boundaries
        # (wave-boundary / serve-end _refresh_kv), NOT per health() call —
        # the cached /healthz snapshot must mirror health() verbatim
        self._health_generated_at = self._clock()
        log_dist(f"InferenceEngineV2: blocks={num_blocks}x{block_size} "
                 f"budget={token_budget} dtype={self.config.dtype} tp={self.tp} "
                 f"fastpath={'on' if self.fastpath.enabled else 'off'}", ranks=[0])
        # first ops snapshot at attach, so a scrape between construction and
        # the first serve sees real (zeroed) families instead of an empty body
        self.refresh_ops(force=True)

    def _warn_truncated_nucleus(self):
        """One-time runtime notice when TP candidate-set sampling approximates
        top-p (ADVICE r5): with ``top_p < 1`` each shard contributes k' =
        max(top_k, 64) candidates, so tail mass outside the k'*tp candidate
        set is redistributed unless k'*tp covers the vocabulary."""
        vocab = getattr(self.model_config, "vocab_size", None)
        if self.tp <= 1 or vocab is None or not self.config.top_p < 1.0:
            return
        kc = max(int(self.config.top_k) if self.config.top_k else 0, 64)
        if kc * self.tp < int(vocab):
            from ...utils.logging import warning_once
            warning_once(
                f"InferenceEngineV2: top_p={self.config.top_p} with tp={self.tp} uses the "
                f"truncated-nucleus approximation — sampling sees {kc}*{self.tp}="
                f"{kc * self.tp} candidates of V={int(vocab)}, so nucleus mass outside the "
                f"per-shard top-{kc} sets is redistributed; raise top_k to widen coverage "
                f"if exact top-p sampling matters")

    def _shard_mapped(self, inner, out_specs):
        """Wrap a (params, kv, *replicated) forward for TP: replicated
        activations in, sharded params/KV, psums inside via tp_axis."""
        n_rep = len(inspect.signature(inner).parameters) - 2
        rep = tuple(PartitionSpec() for _ in range(n_rep))
        return shard_map(inner, mesh=self.topology.mesh,
                         in_specs=(self._param_specs, self._kv_specs) + rep,
                         out_specs=out_specs, check_vma=False)

    # ------------------------------------------------------------------ intake
    def put(self, uids: Sequence[int], prompts: Sequence[Sequence[int]],
            ttl_s: Optional[float] = None, *, tenant: Optional[str] = None,
            service_class: Optional[str] = None) -> None:
        """Enqueue requests directly into the state manager (reference
        engine_v2.put:107), bypassing the admission queue — the step()-level
        API for callers running their own loop.  ``ttl_s`` stamps a deadline
        that step() enforces between forwards: an expired sequence is evicted
        (done, ``finish_reason: deadline_expired``, blocks reclaimed) before
        the next ragged batch is scheduled.

        ``tenant``/``service_class`` (ISSUE 19) stamp QoS identity on the
        whole batch: the prefix cache keys on the tenant and the per-tenant
        gauges attribute the load.  put() bypasses the admission queue, so
        quota SHEDDING does not apply here — callers running their own loop
        own their own backpressure — but identity and accounting do."""
        ttl = ttl_s if ttl_s is not None else self.resilience.default_ttl_s
        tenant = str(tenant) if tenant else "default"
        if self.qos is not None:
            service_class = self.qos.service_class(service_class)
        elif service_class is None:
            service_class = "interactive"
        now = None
        if ttl is not None or self.tracer.enabled:
            # one clock read covers the whole batch: the deadline stamp, the
            # flight-recorder tick, and the admit marks all share it
            now = self._clock()
            self.tracer.tick(now)
        deadline = now + ttl if ttl is not None else None
        self._reset_table_width_if_idle()
        for uid, prompt in zip(uids, prompts):
            seq = self.manager.add_sequence(int(uid), [int(t) for t in prompt],
                                            deadline=deadline, tenant=tenant,
                                            service_class=service_class)
            self._map_prefix(seq)
            if self.qos is not None:
                self.qos.note_admit(tenant, service_class, len(prompt))
            if self.journal is not None:
                # step()-level requests journal too (max_new_tokens=0: the
                # caller's own loop owns the budget) so a crash loses neither
                # path's requests; recovery re-admission targets the
                # generate()/serve_recovered contract
                self.journal.record_admit(int(uid), [int(t) for t in prompt],
                                          ttl_s=ttl, max_new_tokens=0,
                                          tenant=tenant,
                                          service_class=service_class)
            self.tracer.event("admit", uid=int(uid), direct=True)
            self.tracer.on_admit(int(uid), now, prompt_len=len(prompt),
                                 tenant=(tenant if self.qos is not None
                                         else None))
        # prefix-sharing opportunity over the post-intake live set (the put()
        # analog of _serve's per-pass observation; the new sequences are
        # already live, so no extras needed)
        self._observe_prefix({})

    def flush(self, uid: int) -> None:
        seq = self.manager.seqs.get(uid)
        finish_reason = seq.finish_reason if seq is not None else None
        failure = self.manager.failures.get(uid)
        self.manager.retire(uid)
        # step()-level callers end a request's life here: terminal status
        # mirrors retire()'s completion accounting (failures stay failed —
        # fail() marks the seq done with finish_reason None — evictions keep
        # their own status, everything else flushed-as-completed)
        if failure is not None:
            status = FAILED
        elif finish_reason in (DEADLINE_EXPIRED, PREEMPT_REQUEUED_EXHAUSTED):
            status = finish_reason
        else:
            status = OK
        if self.journal is not None and uid in self.journal.watched:
            self.journal.record_terminal(
                uid, status, finish_reason=finish_reason, reason=failure,
                n_tokens=seq.generated_tokens if seq is not None else 0)
        self.tracer.on_terminal(uid, status, finish_reason=finish_reason,
                                reason=failure, t=self.tracer.last_now)

    def _reset_table_width_if_idle(self) -> None:
        """Fresh serve (no tracked sequences): drop the sticky table width so
        a repeated scenario replays the same width trajectory — and therefore
        hits the same compiled programs — as its first run."""
        if not self.manager.seqs:
            self._table_width = 0
            self._table_slack = 0

    # ------------------------------------------------------------------- step
    def _build_fwd_jit(self, n: int, t: int, b: int):
        """The ragged forward for one bucket, jitted under the bucket's name
        (``fwd_n32_t256_b20``): the device trace's program line and the
        compile ledger then say which bucket ran.  A step samples one token a
        sequence, so the forward is asked for each row's last live logits alone
        (``last_rows``): ``[n, 1, V]`` whatever ``t``."""
        model, cfg, bs = self.model, self.model_config, self.block_size
        tp_axis, bound = TENSOR_AXIS if self.tp > 1 else None, self._live_token_bound

        def fwd(params, kv, tokens, n_tokens, start_pos, tables):
            return model.forward_paged(cfg, params, tokens, n_tokens, start_pos,
                                       tables, kv, block_size=bs, tp_axis=tp_axis,
                                       live_token_bound=bound, last_rows=True)
        if self.tp > 1:
            fwd = self._shard_mapped(fwd, (PartitionSpec(), self._kv_specs))
        fwd.__name__ = f"fwd_n{n}_t{t}_b{b}"
        return jax.jit(fwd, donate_argnums=(1, ))  # dslint: disable=donation-after-use  # call-site contract: step() reassigns self.kv from the result in the same statement (the KV pool is donated so decode updates alias in place)

    def _compiled_fwd(self, n: int, t: int, b: int):
        key = (n, t, b)
        if key not in self._fwd_cache:
            try:
                # compile ahead-of-time even for buckets the prewarm missed:
                # the ledger gets the real compile wall time (ISSUE 16)
                self._aot_compile_fwd(n, t, b, prewarmed=False)
            except Exception:
                # AOT lowering can fail where plain jit works (backend
                # quirks); serving must degrade to the lazy wrapper, not die
                fwd = self._build_fwd_jit(n, t, b)
                self._fwd_cache[key] = self._until_first_call(key, fwd.__name__, fwd)
                self.ledger.record("fwd", key, name=fwd.__name__)
        return self._fwd_cache[key]

    def _until_first_call(self, key, name: str, fn):
        """A lazily jitted program for ``_fwd_cache[key]``: its first call hands
        the ledger a way to compile it again at that call's shapes (for
        ``program_scopes()``) and puts the bare program in the slot, so no later
        call passes through here."""
        def seen(fn, args):
            program = compile_events.compile_later(fn, args)
            if program is not None:
                self._fwd_cache[key] = fn
                self.ledger.built(name, program)
        return program_scopes.FirstCall(fn, seen)

    def _aot_compile_fwd(self, n: int, t: int, b: int, *,
                         prewarmed: bool = True) -> None:
        """Prewarm one (n_seqs, chunk, table_width) bucket ahead of the serve
        loop: lower + compile the ragged forward against abstract shapes and
        cache the executable, so the first mid-wave step that lands in the
        bucket dispatches instead of stalling p95 on a compile.

        Under TP the avals carry the engine's mesh shardings (params/KV
        sharded per their specs, batch buffers replicated — exactly what
        DeviceBatchState commits at dispatch): an unsharded lowering would
        build an executable the first sharded dispatch could never hit, so
        the "prewarm" would silently recompile mid-wave anyway."""
        key = (n, t, b)
        if key in self._fwd_cache:
            return
        if self.tp > 1:
            rep = self.topology.replicated()
            ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
            abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                      sharding=x.sharding)
        else:
            ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
            abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        # time.perf_counter, not the injectable clock: this is a host-side
        # duration (XLA compiles synchronously here), and reading the engine
        # clock would shift FakeClock-driven deadline semantics with the
        # observatory on — the ledger must never perturb what it measures
        t0 = time.perf_counter()  # dslint: disable=raw-clock-in-serving  # genuinely wall-clock-only: measuring the synchronous XLA compile itself; reading the injectable clock here would shift FakeClock-driven deadline semantics with the observatory on
        fwd = self._build_fwd_jit(n, t, b)
        self._fwd_cache[key] = fwd.lower(
            jax.tree_util.tree_map(abstract, self.params),
            jax.tree_util.tree_map(abstract, self.kv),
            ints((n, t)), ints((n, )), ints((n, )), ints((n, self._table_columns(b)))).compile()
        self.ledger.record("fwd", key, wall_s=time.perf_counter() - t0,  # dslint: disable=raw-clock-in-serving  # same stopwatch as t0 above — host compile duration, never the engine clock
                           prewarmed=prewarmed, name=fwd.__name__, program=self._fwd_cache[key])

    def _selected_spans(self, spans):
        """Each launched row's ``(start_pos, n_tokens)`` for the ``dsa_*``, ``scan_*`` and
        ``kv_blocks_behind_window`` counters: a list only where the family's counters read it (nothing is built for any other)."""
        return list(spans) if self.counters.reads_spans else None

    def _cow_copy_block(self, src: int, dst: int) -> None:
        """Copy-on-write block duplication (ISSUE 13): copy one KV block's
        contents device-side so a fully-prefix-cached prompt's single
        recomputed position writes a PRIVATE block, never a shared one.  One
        compiled program serves every copy (src/dst ride as a traced [2]
        array); every paged cache in the model zoo lays blocks on axis 1
        ([L, num_blocks, ...] — models/transformer.py), which this relies on."""
        fn = self._fwd_cache.get("cow_copy")
        if fn is None:
            pools = [name for name in self.kv if name != TALLY]  # running tallies hold no block

            def cow_copy(kv, pair):
                return {**kv, **jax.tree_util.tree_map(
                    lambda leaf: leaf.at[:, pair[1]].set(leaf[:, pair[0]]),
                    {name: kv[name] for name in pools})}
            if self.tp > 1:
                # the pool's head-sharding must survive the copy: pin
                # out_shardings to the live pool's NamedShardings so the
                # donated sharded pool aliases in place instead of degrading
                # to a gather + single-device copy
                kv_sh = jax.tree_util.tree_map(lambda leaf: leaf.sharding, self.kv)
                fn = jax.jit(cow_copy, donate_argnums=(0, ), out_shardings=kv_sh)
            else:
                fn = jax.jit(cow_copy, donate_argnums=(0, ))
            self._fwd_cache["cow_copy"] = fn = self._until_first_call("cow_copy", "cow_copy", fn)
            self.ledger.record("cow_copy", "cow_copy")
        self.counters.dispatches += 1
        self.counters.uploads += 1
        self.counters.upload_ints += 2
        self.kv = fn(self.kv, jnp.asarray([src, dst], jnp.int32))

    # batch-shape bucketing shares the ONE pow2 primitive with the scatter-row
    # padding in fastpath.DeviceBatchState (divergence would multiply shapes)
    _bucket = staticmethod(round_up_pow2)

    def _table_columns(self, b: int) -> int:
        """Columns of a step's table array for ``b`` table slots: one more for
        a model with a per-sequence state, each row's state slot."""
        return b + (1 if self.manager.state_slots else 0)

    def _stepped_width(self, blocks: int) -> int:
        """Block-table width rounded up in TABLE_STEP-slot increments, capped
        at max_blocks_per_seq — shared by the live bucketing (hysteresis) and
        the prewarm's bucket prediction so the two can't drift apart."""
        return min(-(-blocks // self.TABLE_STEP) * self.TABLE_STEP,
                   self.max_blocks_per_seq)

    def _table_width_for(self, need: int) -> int:
        """Bucketed block-table width for this step's batch.

        Fast path: round ``need`` up in TABLE_STEP-slot increments with sticky
        hysteresis — the width never shrinks until TABLE_SHRINK_PATIENCE
        consecutive steps had at least a full step of slack.  The paged
        kernel's grid walks every table slot, so stepped widths waste at most
        TABLE_STEP-1 dead slots (pure doubling wastes up to 2x), and the
        stickiness keeps one long sequence joining/leaving the batch from
        recompiling every (n, t) bucket it touches.  Reference mode
        (``serving_fastpath.enabled=False``) keeps the original pure-doubling
        behavior as the equivalence oracle."""
        need = min(need, self.max_blocks_per_seq)
        if not self.fastpath.enabled:
            return min(self._bucket(need), self.max_blocks_per_seq)
        stepped = self._stepped_width(need)
        w = self._table_width
        if stepped > w:
            w = stepped
            self._table_slack = 0
        elif stepped <= w - self.TABLE_STEP:
            self._table_slack += 1
            if self._table_slack >= self.TABLE_SHRINK_PATIENCE:
                w = stepped
                self._table_slack = 0
        else:
            self._table_slack = 0
        self._table_width = w
        return w

    def step(self, greedy: bool = True) -> Dict[int, int]:
        """Run one SplitFuse step; returns {uid: sampled_token} for sequences
        that produced a next token (finished prefill or decoded).

        With the serving fast path enabled this is dispatch + immediate
        materialize over the persistent device batch buffers; the serve loop
        uses the split halves directly to defer the materialize by one step.
        TP-sharded engines ride the same path (ISSUE 15): DeviceBatchState
        replicates its buffers over the mesh, so the shard_mapped forward
        consumes them with zero resharding."""
        if not self.fastpath.enabled:
            return self._step_reference(greedy)
        deferred = self._dispatch_step(greedy)
        if deferred is None:
            return {}
        with self._phase_annotation("dispatch", "wait"):
            return deferred.patch(self.manager)

    def _dispatch_step(self, greedy: bool) -> Optional[DeferredTokens]:
        """Fast-path step dispatch: incrementally scatter this step's deltas
        into the bucket's persistent device buffers, launch forward + pick,
        and return a :class:`DeferredTokens` handle WITHOUT waiting on the
        sampled tokens.  Emitting sequences get a PENDING_TOKEN placeholder
        (count-accurate for scheduling) that ``patch()`` later overwrites; a
        decode row whose input token is still in flight is fed on-device from
        the previous step's sampled tokens and never visits the host."""
        self._expire_live()  # TTL enforcement between forwards, never mid-batch
        chunks = self.scheduler.schedule(self.manager)
        if not chunks:
            return None
        tokens_run = sum(c.n_tokens for c in chunks)
        self.tracer.event("dispatch", step=self.scheduler.steps, seqs=len(chunks),
                          tokens=tokens_run)
        if self.tracer.enabled:  # don't build the chunk list for an early-return
            self.tracer.on_chunks([(c.uid, c.n_tokens) for c in chunks],
                                  step=self.scheduler.steps)
        n = self._bucket(len(chunks))
        t = self._bucket(max(c.n_tokens for c in chunks))
        flat = flat_slots(n, t, self._live_token_bound)
        if flat is not None and tokens_run > flat:
            # the compacted program has no slot for them: they would vanish
            raise RuntimeError(
                f"step of {tokens_run} live tokens over the {flat} token slots "
                f"fwd_n{n}_t{t} computes: the scheduler's token_budget "
                f"({self.scheduler.token_budget}) passed the bound the engine "
                f"was built with ({self._live_token_bound})")
        # bucket the table width to the live maximum: the paged kernel's grid
        # walks every table slot, so dead trailing slots are pure waste
        b = self._table_width_for(max(len(self.manager.seqs[c.uid].blocks)
                                      for c in chunks))
        held = (n, t, self._table_columns(b))  # the buffers' key: the table array's width
        rows = []
        feeds = []
        live_blocks = 0
        with self._phase_annotation("scatter_upload"):
            for i, c in enumerate(chunks):
                seq = self.manager.seqs[c.uid]
                sl = seq.tokens[seq.seen_tokens:seq.seen_tokens + c.n_tokens]
                packed = np.zeros(3 + t + held[2], np.int32)
                packed[0] = i
                if c.n_tokens == 1 and sl[0] == PENDING_TOKEN:
                    # the input token is the previous step's sample, still on
                    # device: feed it device-side instead of waiting for it
                    if self._inflight is None or c.uid not in self._inflight.row_of:
                        raise RuntimeError(f"uid {c.uid}: pending token scheduled with no "
                                           f"in-flight step to feed it from")
                    feeds.append((i, self._inflight.row_of[c.uid]))
                    packed[1] = FED_SENTINEL
                else:
                    packed[1:1 + len(sl)] = sl
                packed[1 + t] = c.n_tokens
                packed[2 + t] = seq.seen_tokens
                packed[3 + t:] = self.manager.block_table_row(seq, width=b)
                rows.append((i, packed))
                live_blocks += len(seq.blocks)
            spans = self._selected_spans((self.manager.seqs[c.uid].seen_tokens, c.n_tokens)
                                         for c in chunks)
            slot = self.batch_state.update(held, rows, n_active=len(chunks),
                                           trash_block=self.manager.trash_block)
            if feeds:
                self.batch_state.feed(held, self._inflight.toks_dev, feeds)
        self.phase_profiler.mark("scatter_upload")
        fwd = self._compiled_fwd(n, t, b)
        self.counters.dispatches += 1
        logits, self.kv = fwd(self.params, self.kv, slot.tokens, slot.n_tokens,
                              slot.start_pos, slot.tables)
        # token selection runs ON DEVICE (argmax or temperature/top-k/top-p
        # sampling) — only n ints cross the host link, not [n, V] logits
        # (reference: ragged sampling stays device-side, engine_v2.py:107)
        pick = self._compiled_step_pick(n, greedy)
        self.counters.dispatches += 1
        toks_dev, self._rng = pick(logits, self._rng)
        self.phase_profiler.mark("dispatch")
        self.counters.count_slots(n, t, b, tokens_run, live_blocks, flat=flat, spans=spans)
        emits = []
        row_of: Dict[int, int] = {}
        for i, c in enumerate(chunks):
            seq = self.manager.seqs[c.uid]
            seq.seen_tokens += c.n_tokens
            # prompt blocks this chunk just completed become shareable
            self.manager.register_prefix_blocks(seq)
            if seq.seen_tokens >= len(seq.tokens):
                # produced a next token (end of prompt, or a decode step)
                seq.tokens.append(PENDING_TOKEN)
                emits.append((c.uid, len(seq.tokens) - 1, i))
                row_of[c.uid] = i
        self.counters.step_tokens += len(emits)
        self._kv_steps += 1
        self._refresh_kv()
        self._emit_serving_gauges(tokens_run=tokens_run)
        return DeferredTokens(toks_dev=toks_dev, emits=emits, row_of=row_of,
                              counters=self.counters, tracer=self.tracer,
                              journal=self.journal)

    def _step_reference(self, greedy: bool) -> Dict[int, int]:
        """The pre-fastpath step: full host-side batch rebuild + four uploads
        + synchronous fetch per step.  Kept verbatim as the equivalence oracle
        (``serving_fastpath.enabled=False``) the fastpath tests diff against."""
        self._expire_live()
        chunks = self.scheduler.schedule(self.manager)
        if not chunks:
            return {}
        self.tracer.event("dispatch", step=self.scheduler.steps, seqs=len(chunks),
                          tokens=sum(c.n_tokens for c in chunks))
        if self.tracer.enabled:  # don't build the chunk list for an early-return
            self.tracer.on_chunks([(c.uid, c.n_tokens) for c in chunks],
                                  step=self.scheduler.steps)
        n = self._bucket(len(chunks))
        t = self._bucket(max(c.n_tokens for c in chunks))
        b = self._table_width_for(max(len(self.manager.seqs[c.uid].blocks)
                                      for c in chunks))
        tokens = np.zeros((n, t), np.int32)
        n_tokens = np.zeros((n, ), np.int32)
        start_pos = np.zeros((n, ), np.int32)
        tables = np.tile(self.manager.dead_table_row(b), (n, 1))
        live_blocks = 0
        for i, c in enumerate(chunks):
            seq = self.manager.seqs[c.uid]
            sl = seq.tokens[seq.seen_tokens:seq.seen_tokens + c.n_tokens]
            tokens[i, :len(sl)] = sl
            n_tokens[i] = c.n_tokens
            start_pos[i] = seq.seen_tokens
            tables[i] = self.manager.block_table_row(seq, width=b)
            live_blocks += len(seq.blocks)

        fwd = self._compiled_fwd(n, t, b)
        self.counters.dispatches += 2
        self.counters.uploads += 4  # the four batch arrays into the forward
        self.counters.upload_ints += int(tokens.size + n_tokens.size
                                         + start_pos.size + tables.size)
        logits, self.kv = fwd(self.params, self.kv, jnp.asarray(tokens), jnp.asarray(n_tokens),
                              jnp.asarray(start_pos), jnp.asarray(tables))
        pick = self._compiled_step_pick(n, greedy)
        toks_dev, self._rng = pick(logits, self._rng)
        tokens_run = int(n_tokens.sum())
        self.counters.count_slots(n, t, b, tokens_run, live_blocks, spans=self._selected_spans(
            (int(start_pos[i]), c.n_tokens) for i, c in enumerate(chunks)))
        with self._phase_annotation("dispatch", "wait"):
            toks = materialize(toks_dev, self.counters)  # one sync: n sampled ints

        out: Dict[int, int] = {}
        for i, c in enumerate(chunks):
            seq = self.manager.seqs[c.uid]
            seq.seen_tokens += c.n_tokens
            # prompt blocks this chunk just completed become shareable
            self.manager.register_prefix_blocks(seq)
            if seq.seen_tokens >= len(seq.tokens):
                tok = int(toks[i])
                seq.tokens.append(tok)
                out[c.uid] = tok
        self.counters.step_tokens += len(out)
        self.tracer.event("absorb", step=self.scheduler.steps, tokens=len(out))
        self.tracer.on_tokens_map(out)
        if self.journal is not None:
            self.journal.note_token_map(out)
        self._kv_steps += 1
        self._refresh_kv()
        self._emit_serving_gauges(tokens_run=tokens_run)
        return out

    def _gauge_timestamp(self) -> Optional[float]:
        """Deterministic gauge timestamp when the engine runs on an injected
        clock (FakeClock tests): the clock's last donated read.  None keeps
        record_gauges' wall-clock default — unchanged production behavior."""
        return self.tracer.last_now if self._clock_injected else None

    # ------------------------------------------------------ kv observability
    def _refresh_kv(self) -> None:
        """Wave-boundary census/forecast refresh (ISSUE 12): update per-block
        residency + last-touched stamps from ``seen_tokens``, sample the
        alloc/free rates into the capacity forecaster, land pressure-edge
        events in the flight recorder, and append a Chrome-trace counter-track
        sample when a trace export is configured.  Pure host arithmetic over
        ints the engine already owns — zero device syncs, and no effect on
        ``ServeCounters`` (the kv-obs smoke pins byte-identity on vs off)."""
        self._health_generated_at = self._clock()
        if self.kv_obs is None:
            return
        free = self.manager.allocator.free_blocks
        self.kv_obs.refresh(self.manager.seqs, self._kv_steps, free)
        crossing = self.kv_obs.pressure_crossing()
        if crossing is not None:
            edge, ste = crossing
            self.tracer.event(
                "kv_pressure", step=self.scheduler.steps, edge=edge,
                steps_to_exhaustion=None if ste == float("inf") else round(ste, 1),
                free_blocks=free)
        if self.tracer.config.chrome_trace_path:
            # only assemble the counter-track payload when an export will
            # actually consume it — fragmentation_tokens() walks the census
            census = self.kv_obs.census
            ste = self.kv_obs.forecaster.steps_to_exhaustion()
            self.tracer.counter_track("kv_pool", {
                "allocated_blocks": census.allocated_blocks,
                "free_blocks": free,
                "fragmentation_tokens": census.fragmentation_tokens(),
                **({} if ste is None else {"steps_to_exhaustion": round(ste, 1)}),
            })

    def _observe_prefix(self, extra_prompts: Dict[int, List[int]]) -> None:
        """One PrefixObservatory pass over live + admitted requests: every
        live sequence contributes its PROMPT portion (generated tokens are
        never shareable read-only), ``extra_prompts`` the not-yet-admitted
        prompts of the current intake (queued tickets / a put() batch)."""
        if self.kv_obs is None:
            return
        obs = self.kv_obs.prefix
        # cache-aware: a live uid whose hashes are already cached passes None
        # (no token-list slice built) — an intake over a large live set costs
        # dict lookups, not prompt copies
        prompts: Dict[int, Optional[List[int]]] = {
            uid: (None if obs.has(uid) else seq.tokens[:seq.prompt_len])
            for uid, seq in self.manager.seqs.items() if not seq.done}
        prompts.update(extra_prompts)
        obs.observe(prompts)

    def _map_prefix(self, seq) -> int:
        """Admit-time shared-prefix mapping with the hit landed in the flight
        recorder (the scheduler's per-chunk late-binding remap shares the
        manager seam but skips the event — per-step noise)."""
        mapped = self.manager.map_prefix(seq)
        if mapped:
            self.tracer.event("prefix_hit", uid=seq.uid, tokens=mapped,
                              blocks=len(seq.blocks))
        return mapped

    def _forget_prefix(self, uid: int) -> None:
        """Invalidate a uid's PrefixObservatory hash cache for a request that
        dies WITHOUT ever becoming a live sequence (queue expiry, stall
        drain, strict-abort drain) — live sequences invalidate through the
        census's retirement listener, but a queued-only ticket never reaches
        ``retire()``, and a stale entry would credit the uid's NEXT life with
        the dead prompt's hashes (phantom sharing)."""
        if self.kv_obs is not None:
            self.kv_obs.prefix.forget(uid)

    def check_kv_invariant(self) -> None:
        """Census-vs-allocator invariant: the census's owned-block set must
        exactly partition against the allocator free list (no block owned
        while free, none leaked).  Raises ``CensusInvariantError`` naming the
        offending uid/block.  Run automatically after every serve pass
        (``serving_kv_observability.invariant_check``); public so smokes and
        fault-injection tests can assert it at arbitrary points.  With prefix
        sharing the live sequences ride along, so the refcount-agreement and
        shared-content (no-request-observes-another's-KV) checks run too."""
        if self.kv_obs is not None:
            self.kv_obs.check_invariant(self.manager.allocator, self.manager.seqs)

    # ---------------------------------------------------------- ops endpoints
    def refresh_ops(self, force: bool = False) -> None:
        """Refresh the host-side ops snapshots the scrape handlers serve:
        re-populate the metrics registry from engine state (all python ints/
        floats the host already owns — zero device syncs, dslint-enforced on
        the whole ops plane), re-render the Prometheus text, re-dump
        ``health()``/``state_snapshot()`` JSON, and republish the per-rank
        exchange files when a supervisor exported ``DSTPU_OPS_DIR``.

        Called from the serve loop (throttled on the injectable clock to one
        refresh per ``ops_server.refresh_interval_s``) and force-called at
        attach and serve end.  A no-op when the ops plane is off — the
        byte-identical ServeCounters guarantee of the ops-smoke."""
        if self._ops is None:
            return
        from ...monitor.metrics import populate_from_engine
        self._ops.refresh(lambda reg: populate_from_engine(reg, self),
                          now=self.tracer.last_now, force=force,
                          healthz=lambda: json.dumps(self.health()),
                          statez=lambda: json.dumps(self.state_snapshot()))

    def close_ops(self) -> None:
        """Shut the ops HTTP listener down (tests / clean teardown)."""
        if self._ops is not None:
            self._ops.close()

    def _emit_serving_gauges(self, tokens_run: int) -> None:
        """Serving rates on top of the scheduler's per-step gauges: requests/s
        (retired-sequence rate) and tokens/s through the ragged forward."""
        record_gauges = getattr(self.telemetry, "record_gauges", None)
        if record_gauges is None:  # no sink, or one that takes no gauges
            return
        c = self.counters
        gauges = {"live_seqs": float(len(self.manager.live_uids())),
                  # resilience gauges (ISSUE 4): shed/preempt/deadline lifetime
                  # counters + last admission wait, next to the serving rates
                  "admission_queue_depth": float(len(self.admission)),
                  "shed_total": float(self.admission.shed_total),
                  "preempted_total": float(self.scheduler.preempted_total),
                  "deadline_expired_total": float(self._deadline_expired_total),
                  "queue_wait": float(self._queue_wait_s),
                  # fastpath gauges (ISSUE 5): the host-link cost of serving —
                  # device->host syncs, program dispatches, compiled buckets,
                  # ints uploaded, and the fraction of tokens emitted fused
                  "fastpath_host_syncs": float(c.host_syncs),
                  "fastpath_dispatches": float(c.dispatches),
                  "fastpath_compiled_programs": float(c.compiles),
                  "fastpath_upload_ints": float(c.upload_ints),
                  "fastpath_burst_fraction":
                      c.burst_tokens / max(c.burst_tokens + c.step_tokens, 1)}
        if self.kv_obs is not None:
            # KV-pool gauges (ISSUE 12) under the unified serving_kv_*
            # spelling — the same names the metrics registry exports, so the
            # telemetry stream and /metrics can't drift apart again
            census, fc = self.kv_obs.census, self.kv_obs.forecaster
            ste = fc.steps_to_exhaustion()
            gauges.update({
                "kv_free_blocks": float(self.manager.allocator.free_blocks),
                "kv_utilization": self.manager.kv_utilization(),
                "kv_fragmentation_tokens": float(census.fragmentation_tokens()),
                "kv_alloc_rate": fc.alloc_rate,
                "kv_free_rate": fc.free_rate,
                **({} if ste is None else {"kv_steps_to_exhaustion": float(ste)}),
            })
        pc = self.manager.prefix_cache
        if pc is not None:
            # realized prefix-cache savings (ISSUE 13) next to the
            # counterfactual the observatory reports — same spelling the
            # metrics registry exports
            gauges.update({
                "kv_prefix_hits": float(pc.hit_blocks_total),
                "kv_prefill_tokens_saved": float(pc.tokens_saved_total),
                "kv_prefix_realized_hit_rate": pc.realized_hit_rate(),
            })
        # SLO percentile gauges (ISSUE 6): ttft/tbt/e2e/queue_wait p50/p95/p99
        # from the tracer's streaming histograms ({} while tracing is off)
        gauges.update(self.tracer.gauge_fields())
        if self.perf_cfg.enabled:
            gauges["serving_warm_recompiles"] = float(self.ledger.warm_total)
        self._tokens_run_total = getattr(self, "_tokens_run_total", 0) + tokens_run
        rate = getattr(self.telemetry, "rate", None)
        if rate is not None:
            rps = rate("v2_completed_requests", float(self.manager.completed_requests))
            if rps is not None:
                gauges["requests_per_sec"] = rps
            tps = rate("v2_tokens_total", float(self._tokens_run_total))
            if tps is not None:
                gauges["tokens_per_sec"] = tps
        record_gauges(gauges, step=self.scheduler.steps, prefix="Inference/Serving",
                      timestamp=self._gauge_timestamp())

    def _tell(self, method: str, *args, **kwargs) -> None:
        """Call ``telemetry.<method>`` where the sink has it: a ``telemetry=``
        object implements only what it wants to hear (one that keeps request
        records is ``record_trace`` and nothing else)."""
        fn = getattr(self.telemetry, method, None)
        if fn is not None:
            fn(*args, **kwargs)

    def _phase_annotation(self, phase: str, part: Optional[str] = None):
        """``jax.profiler`` span for one serve phase (a name of
        ``monitor.perf.PHASES``, the list ``StepPhaseProfiler.mark`` takes
        too) or, nested inside it, for the part of it where the host blocks
        or does bulk work (``burst.wait``).  Always opened, whoever started
        the profiler: outside a profiler session a TraceMe is a flag check
        (PERF.md section 6, PR 24, has the measured cost)."""
        if phase not in PHASES:
            raise KeyError(f"{phase!r} is not a serve phase: {PHASES}")
        return jax.profiler.TraceAnnotation(phase if part is None else f"{phase}.{part}")

    def _perf_snapshot(self) -> Dict[str, Any]:
        """Host-side perf observatory snapshot (ISSUE 16): phase attribution
        and compile ledger — everything health()/statez surface."""
        snap = self.phase_profiler.snapshot()  # enabled/iterations/wall_s/phases
        snap["compile_ledger"] = self.ledger.snapshot()
        return snap

    def program_scopes(self, name: Optional[str] = None) -> Dict[str, Dict[str, tuple]]:
        """Which scope each operation of this engine's compiled programs
        belongs to: ``{program: {instruction: (scope, ...)}}`` for every program
        ``health()["perf"]["compile_ledger"]`` counts (``name``: that one),
        outermost scope first, the names those of
        ``monitor.program_scopes.SCOPES``.  A device trace of this server names
        an operation by its instruction (``%fusion.735``) inside a program
        event ``jit_<program>``: laid over it, the table says what the busy
        time was spent on (``chipbench/reduce/scopes.py`` does that).  It reads
        the optimized text of the executables the engine holds and compiles a
        lazily jitted program again at the shapes of its first call (a hit of
        JAX's caches), once a program: seconds, so an operator's call and never
        the serve loop's; ``health()`` does not include it."""
        return self.ledger.program_scopes(name)

    def _compiled_step_pick(self, n: int, greedy: bool):
        key = ("pick", n, greedy, self.config.temperature, self.config.top_k,
               self.config.top_p)
        if key not in self._fwd_cache:
            from ..engine import _sample
            temperature, top_k, top_p = (self.config.temperature, self.config.top_k,
                                         self.config.top_p)

            def pick(logits, rng):
                # the step's forward returned each row's last live logits alone
                # (_build_fwd_jit asks for last_rows): [n, 1, V], nothing to gather
                with jax.named_scope("pick"):
                    row = logits[:, 0]
                    if greedy:
                        return jnp.argmax(row, axis=-1).astype(jnp.int32), rng
                    return _sample(row, rng, temperature=temperature, top_k=top_k, top_p=top_p)

            pick.__name__ = f"pick_n{n}" + ("" if greedy else "_sampled")
            self._fwd_cache[key] = self._until_first_call(key, pick.__name__, jax.jit(pick))
            self.ledger.record("pick", key, name=pick.__name__)
        return self._fwd_cache[key]

    # ------------------------------------------------------------ decode burst
    def _compiled_burst(self, n: int, k: int, b: int, sample_cfg=None, eos: int = -1):
        """``sample_cfg``: None => greedy; (temperature, top_k, top_p) =>
        on-device sampling with the rng carried through the scan.  ``eos`` >= 0
        makes decode eos-aware: a finished row freezes (re-emits its token) and
        its done flag streams out alongside the tokens.  ``b``, the table's
        width, is a shape of the program and so part of its key and name, as a
        forward bucket's is: one name, one executable (``program_scopes()`` and
        the device trace tell programs apart by name)."""
        key = ("burst", n, k, b, sample_cfg, eos)
        if key not in self._fwd_cache:
            from ..engine import _sample
            model, cfg, bs = self.model, self.model_config, self.block_size
            ones = jnp.ones((n, ), jnp.int32)
            sampling = sample_cfg is not None
            if self.tp > 1:
                tp_kw = {"tp_axis": TENSOR_AXIS, "gather_logits": False}
                vocab = getattr(cfg, "vocab_size", None)

                if sampling:
                    # sampled TP decode stays in the same wire-cost class as
                    # greedy via candidate-set sampling (VERDICT r4 #4)
                    temperature, top_k, top_p = sample_cfg

                    def pick(row, rng):  # row [N, V_local]
                        if vocab is not None and row.shape[-1] == vocab:
                            return _sample(row, rng, temperature=temperature,
                                           top_k=top_k, top_p=top_p)
                        return candidate_sample(row, rng, temperature=temperature,
                                                top_k=top_k, top_p=top_p,
                                                axis=TENSOR_AXIS)
                else:
                    # vocab-parallel greedy: argmax the LOCAL logit shard and
                    # reduce (max value, then first-occurrence index) with O(1)
                    # scalars per token over ICI instead of O(V) gathers
                    def pick(row, rng):  # row [N, V_local]
                        if vocab is not None and row.shape[-1] == vocab:
                            return jnp.argmax(row, axis=-1).astype(jnp.int32), rng
                        vlocal = row.shape[-1]
                        local_idx = jnp.argmax(row, axis=-1).astype(jnp.int32)
                        local_val = jnp.max(row, axis=-1)
                        best = jax.lax.pmax(local_val, TENSOR_AXIS)
                        offset = jax.lax.axis_index(TENSOR_AXIS).astype(jnp.int32) * vlocal
                        cand = jnp.where(local_val == best, local_idx + offset,
                                         jnp.int32(2**31 - 1))
                        return jax.lax.pmin(cand, TENSOR_AXIS).astype(jnp.int32), rng
            else:
                tp_kw = {}
                if sampling:
                    temperature, top_k, top_p = sample_cfg

                    def pick(row, rng):
                        return _sample(row, rng, temperature=temperature,
                                       top_k=top_k, top_p=top_p)
                else:
                    pick = lambda row, rng: (jnp.argmax(row, axis=-1).astype(jnp.int32), rng)

            def burst(params, kv, tok0, start0, tables, rng0, done0):
                def body(carry, _):
                    kv, tok, start, rng, done = carry
                    logits, kv = model.forward_paged(cfg, params, tok[:, None], ones,
                                                     start, tables, kv, block_size=bs,
                                                     **tp_kw)
                    # one split key per fused step: the rng carried through the
                    # scan is the ENGINE rng, advanced by _sample exactly as the
                    # stepwise pick advances it — burst and per-step decode
                    # sample identical tokens for the same seed
                    with jax.named_scope("pick"):
                        nxt, rng = pick(logits[:, 0], rng)
                    # finished rows freeze: re-emit the last token (the pool
                    # keeps absorbing writes into pre-allocated slots; the host
                    # truncates at the first done flag)
                    nxt = jnp.where(done, tok, nxt)
                    done = jnp.logical_or(done, nxt == jnp.int32(eos))
                    return (kv, nxt, start + 1, rng, done), (nxt, done)

                (kv, _, _, rng, _), (toks, dones) = jax.lax.scan(
                    body, (kv, tok0, start0, rng0, done0), None, length=k)
                # toks/dones ride ONE fetch: pack [K, N] tokens over [K, N]
                # done flags into a single [2K, N] int32 array
                packed = jnp.concatenate([toks, dones.astype(jnp.int32)], axis=0)
                return kv, packed, rng

            if self.tp > 1:
                burst = self._shard_mapped(
                    burst, (self._kv_specs, PartitionSpec(), PartitionSpec()))
            # sampled and eos-aware bursts are other programs: other names
            burst.__name__ = (f"burst_n{n}_k{k}_b{b}" + ("_sampled" if sampling else "")
                              + (f"_eos{eos}" if eos >= 0 else ""))
            self._fwd_cache[key] = self._until_first_call(key, burst.__name__, jax.jit(burst, donate_argnums=(1, )))  # dslint: disable=donation-after-use  # call-site contract: decode_burst() reassigns self.kv from the result in the same statement
            self.ledger.record("burst", key, name=burst.__name__)
        return self._fwd_cache[key]

    def decode_burst(self, k: int, greedy: bool = True,
                     eos_token_id: Optional[int] = None) -> Optional[Dict[int, List[int]]]:
        """Run ``k`` decode steps INSIDE one compiled program — one host
        round-trip per k tokens instead of per token (the latency lever the
        reference gets from CUDA-graph decode loops).

        Greedy AND sampled (temperature/top-k/top-p from the engine config)
        decode both run device-side; with ``eos_token_id`` the scan carries a
        done-mask and finished rows freeze, so the returned per-uid lists stop
        at (and include) the first eos.  Applies only when every live sequence
        is in pure decode (one pending token) and the pool can pre-allocate k
        more slots per sequence; returns None when not applicable (caller
        falls back to step()).
        """
        with self._phase_annotation("burst", "prepare"):
            prepared = self._prepare_burst(k)
        if prepared is None:
            return None
        live, n, b, tok0, start0, tables = prepared
        sample_cfg = None if greedy else (self.config.temperature, self.config.top_k,
                                          self.config.top_p)
        eos = -1 if eos_token_id is None else int(eos_token_id)
        burst = self._compiled_burst(n, k, b, sample_cfg=sample_cfg, eos=eos)
        done0 = jnp.zeros((n, ), jnp.bool_)
        self.counters.dispatches += 1
        self.counters.uploads += 3
        self.counters.upload_ints += int(tok0.size + start0.size + tables.size)
        # the scan carries the ENGINE rng itself (no pre-split): each fused
        # step consumes exactly the key the stepwise pick would, so burst and
        # per-step decode are sample-for-sample identical
        self.kv, packed, self._rng = burst(self.params, self.kv, jnp.asarray(tok0),
                                           jnp.asarray(start0), jnp.asarray(tables),
                                           self._rng, done0)
        live_blocks = sum(len(seq.blocks) for seq in live)
        with self._phase_annotation("burst", "wait"):
            fetched = materialize(packed, self.counters)  # ONE sync per k steps
        with self._phase_annotation("burst", "absorb"):
            out = self._absorb_burst(live, k, eos, fetched)
        # k forward passes over [n, 1] token slots and [n, b] table slots each
        self.counters.count_slots(n, 1, b, sum(len(v) for v in out.values()),
                                  live_blocks, passes=k, spans=self._selected_spans(
                                      (int(start0[i]), 1) for i in range(len(live))))
        return out

    def _prepare_burst(self, k: int):
        """Host side of a burst before its dispatch: the applicability checks,
        the all-or-nothing block grab for ``k`` more positions per sequence,
        and the batch arrays.  None when the burst does not apply."""
        live, prefilling = self.scheduler.live_split(self.manager)
        if not live or prefilling:
            return None  # fuse only a pure-decode live set
        if len(live) > self.scheduler.max_seqs:
            return None
        if self._inflight is not None:
            # a deferred pick is still in flight: its placeholder would be
            # this burst's input token — patch it in first (idempotent; the
            # serve loop still absorbs the same handle afterwards)
            self._inflight.patch(self.manager)
        max_pos = getattr(self.model_config, "max_seq_len", None)
        total_new = 0
        for seq in live:
            upto = seq.seen_tokens + 1 + k
            if self.manager.over_cap(upto):
                return None
            if max_pos is not None and upto > max_pos:
                # positions past the rotary table would silently clamp — the
                # burst pre-commits k future positions, so bound them here
                return None
            total_new += self.manager.blocks_needed(seq, upto)
        if not self.manager.can_allocate(total_new):
            # check BEFORE allocating anything: a partial grab would strand
            # blocks on some sequences and starve the stepwise fallback
            return None
        grown: List = []
        try:
            for seq in live:
                prior = len(seq.blocks)
                self.manager.ensure_blocks(seq, seq.seen_tokens + 1 + k)
                grown.append((seq, prior))
        except KVAllocationError:
            # an injected/transient allocator failure mid-grab: roll every
            # sequence back to its prior table so nothing is stranded, and
            # decline — the stepwise fallback retries at finer grain.  The
            # rollback rides the manager's reclaim seam so the block census
            # stays exact through the fault path too.
            for seq, prior in grown:
                self.manager.rollback_blocks(seq, prior)
            return None

        n = self._bucket(len(live))
        b = self._table_width_for(max(len(s.blocks) for s in live))
        tok0 = np.zeros((n, ), np.int32)
        start0 = np.zeros((n, ), np.int32)
        # padded rows: decode into the trash block (and trash state slot) at position 0
        tables = np.tile(self.manager.dead_table_row(b), (n, 1))
        for i, seq in enumerate(live):
            tok0[i] = seq.tokens[seq.seen_tokens]
            start0[i] = seq.seen_tokens
            tables[i] = self.manager.block_table_row(seq, width=b)
        return live, n, b, tok0, start0, tables

    def _absorb_burst(self, live, k: int, eos: int, fetched) -> Dict[int, List[int]]:
        """Host side of a burst after its one fetch: the tokens into their
        sequences, the records, the WAL frame, the gauges."""
        toks, dones = fetched[:k], fetched[k:]        # [K, N] each
        out: Dict[int, List[int]] = {}
        for i, seq in enumerate(live):
            col = toks[:, i]
            n_real = k
            if eos >= 0 and dones[:, i].any():
                n_real = int(np.argmax(dones[:, i])) + 1  # first done step, inclusive
            produced = [int(t) for t in col[:n_real]]
            seq.tokens.extend(produced)
            seq.seen_tokens += n_real
            # a burst's first position can complete the FINAL prompt block
            # (a budget split at prompt_len - 1, or the CoW copy's recompute)
            self.manager.register_prefix_blocks(seq)
            self.counters.burst_tokens += n_real
            out[seq.uid] = produced
        # fused work accounting (ISSUE 20): a k-step burst is k sequential
        # steps' worth of decode work, without ever advancing scheduler.steps
        self.scheduler.note_fused_work(k, sum(len(v) for v in out.values()))
        self.tracer.event("burst", step=self.scheduler.steps, k=k, seqs=len(live))
        self.tracer.on_burst_tokens({uid: len(toks_) for uid, toks_ in out.items()})
        if self.journal is not None:
            # a burst IS a wave boundary: the host just materialized k tokens
            # per sequence in one sync, so the WAL appends one delta frame
            # here at zero extra device cost
            self.journal.note_token_map(out)
            self.journal.flush()
        # the burst is the dominant emission path: emit the serving gauges
        # here too, so burst-heavy serves surface fresh SLO percentiles and
        # burst-fraction instead of only dispatch-time snapshots
        self._kv_steps += k
        self._refresh_kv()
        self._emit_serving_gauges(tokens_run=sum(len(v) for v in out.values()))
        return out

    # ----------------------------------------------------- speculative decode
    def attach_draft_model(self, model_module, model_config, params, *,
                           num_blocks: Optional[int] = None,
                           block_size: Optional[int] = None) -> None:
        """Arm ``drafter: "model"`` spec decode with a small draft model from
        the model zoo (ISSUE 20): the drafter proposes greedily against its
        own private paged pool (catch-up + k-token scan in one compiled
        program per bucket) and its proposals feed the verify program without
        ever visiting the host.  Under TP the draft model runs fully
        replicated over the engine's mesh.  ``num_blocks``/``block_size``
        size the private pool (defaults: mirror the target pool)."""
        if not self.spec_cfg.enabled:
            raise ValueError("serving_spec_decode.enabled is off — arm the "
                             "section before attaching a draft model")
        if self.spec_cfg.drafter != "model":
            raise ValueError(f"serving_spec_decode.drafter is "
                             f"'{self.spec_cfg.drafter}', not 'model'")
        self._drafter = ModelDrafter(
            model_module, model_config, params,
            num_blocks=(num_blocks if num_blocks is not None
                        else self.manager.allocator.num_blocks),
            block_size=(block_size if block_size is not None
                        else self.block_size),
            max_blocks_per_seq=self.max_blocks_per_seq, dtype=self.dtype,
            mesh=self.topology.mesh if self.tp > 1 else None,
            ledger=self.ledger)

    def _build_spec_verify_jit(self, n: int, k: int, b: int, sample_cfg=None):
        """The fused verify program: ONE batched target forward over the
        paged pool scoring (input token + k draft tokens) per sequence, then
        the on-device rejection sampler — accept count and emitted run packed
        into one [n, k+2] int32 array so the whole round rides one fetch.
        Jitted under its bucket's name (``spec_verify_n8_k4_b12``)."""
        model, cfg, bs = self.model, self.model_config, self.block_size
        width = jnp.full((n, ), k + 1, jnp.int32)
        tp_axis = TENSOR_AXIS if self.tp > 1 else None

        def verify(params, kv, tok0, draft, start0, tables, rng):
            tokens = jnp.concatenate([tok0[:, None], draft], axis=1)
            logits, kv = model.forward_paged(cfg, params, tokens, width, start0, tables, kv,
                                             block_size=bs, tp_axis=tp_axis)
            packed, rng = rejection_select(logits, draft, rng, sample_cfg=sample_cfg)
            return kv, packed, rng
        if self.tp > 1:
            verify = self._shard_mapped(
                verify, (self._kv_specs, PartitionSpec(), PartitionSpec()))
        verify.__name__ = (f"spec_verify_n{n}_k{k}_b{b}"
                           + ("" if sample_cfg is None else "_sampled"))
        return jax.jit(verify, donate_argnums=(1, ))  # dslint: disable=donation-after-use  # call-site contract: decode_spec() reassigns self.kv from the result in the same statement

    def _compiled_spec_verify(self, n: int, k: int, b: int, sample_cfg=None):
        key = ("spec_verify", n, k, b, sample_cfg)
        if key not in self._fwd_cache:
            try:
                self._aot_compile_spec_verify(n, k, b, sample_cfg,
                                              prewarmed=False)
            except Exception:
                # same degrade as _compiled_fwd: lazy jit when AOT lowering
                # fails — serving must not die on a backend quirk
                verify = self._build_spec_verify_jit(n, k, b, sample_cfg)
                self._fwd_cache[key] = self._until_first_call(key, verify.__name__, verify)
                self.ledger.record("spec_verify", key, name=verify.__name__)
        return self._fwd_cache[key]

    def _aot_compile_spec_verify(self, n: int, k: int, b: int, sample_cfg=None,
                                 *, prewarmed: bool = True) -> None:
        """Prewarm one (n_seqs, draft_k, table_width) verify bucket: the AOT
        bucket key includes the VERIFY WIDTH (k), so every rung of the
        adaptive-k ladder is a compiled executable before the serve loop can
        dispatch it — a mid-serve k drift re-uses a prewarmed program instead
        of stalling p95 on a compile (the fwd-bucket contract extended to
        spec mode).  Sharded avals under TP, same as _aot_compile_fwd."""
        key = ("spec_verify", n, k, b, sample_cfg)
        if key in self._fwd_cache:
            return
        if self.tp > 1:
            rep = self.topology.replicated()
            ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
            rng_aval = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype,
                                            sharding=rep)
            abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                      sharding=x.sharding)
        else:
            ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
            rng_aval = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype)
            abstract = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        t0 = time.perf_counter()  # dslint: disable=raw-clock-in-serving  # same contract as _aot_compile_fwd: measuring the synchronous XLA compile itself, never the engine clock
        verify = self._build_spec_verify_jit(n, k, b, sample_cfg)
        self._fwd_cache[key] = verify.lower(
            jax.tree_util.tree_map(abstract, self.params),
            jax.tree_util.tree_map(abstract, self.kv),
            ints((n, )), ints((n, k)), ints((n, )), ints((n, b)),
            rng_aval).compile()
        self.ledger.record("spec_verify", key, wall_s=time.perf_counter() - t0,  # dslint: disable=raw-clock-in-serving  # same stopwatch as t0 above — host compile duration, never the engine clock
                           prewarmed=prewarmed, name=verify.__name__,
                           program=self._fwd_cache[key])

    def decode_spec(self, k: int, greedy: bool = True,
                    eos_token_id: Optional[int] = None
                    ) -> Optional[Dict[int, List[int]]]:
        """One speculative draft/verify round over the pure-decode live set
        (ISSUE 20): the drafter proposes ``k`` tokens per sequence, ONE
        batched target forward scores all of them against the paged pool, and
        the on-device rejection sampler emits the accepted prefix plus one
        corrected/bonus token — 1..k+1 tokens per sequence for a single
        target-weight HBM stream, distribution-exact vs plain decode (token-
        identical under greedy).

        Bookkeeping mirrors decode_burst: all-or-nothing block grab up front
        (rolled back on an injected allocator fault), ONE host sync for the
        packed accept runs, per-sequence seen-token advance by the ACCEPTED
        length with trailing draft-overshoot blocks rolled back before they
        can pollute shared prefix-cache state, WAL frames of verified tokens
        only.  Returns None when not applicable (caller falls back to the
        plain burst / stepwise paths)."""
        drafter = self._drafter
        if drafter is None:
            return None
        live, prefilling = self.scheduler.live_split(self.manager)
        if not live or prefilling:
            return None  # speculate only over a pure-decode live set
        if len(live) > self.scheduler.max_seqs:
            return None
        if any(seq.deadline is not None for seq in live):
            # deadline-armed sequences take the conservative path (the same
            # disengage rule the async pipeline follows): a spec round emits
            # a variable-length run per loop iteration, which would shift
            # eviction timing relative to the plain engine — TTL partials
            # must stay byte-identical to the spec-off stack
            return None
        if self._inflight is not None:
            # the drafter reads token HISTORY: a deferred pick still in
            # flight would leave PENDING_TOKEN placeholders in it
            self._inflight.patch(self.manager)
        max_pos = getattr(self.model_config, "max_seq_len", None)
        total_new = 0
        for seq in live:
            upto = seq.seen_tokens + 1 + k
            if self.manager.over_cap(upto):
                return None
            if max_pos is not None and upto > max_pos:
                return None
            total_new += self.manager.blocks_needed(seq, upto)
        if not self.manager.can_allocate(total_new):
            return None
        grown: List = []
        try:
            for seq in live:
                prior = len(seq.blocks)
                self.manager.ensure_blocks(seq, seq.seen_tokens + 1 + k)
                grown.append((seq, prior))
        except KVAllocationError:
            # injected/transient allocator fault mid-grab: full rollback so
            # nothing is stranded, then decline — the burst/stepwise
            # fallbacks retry at coarser/finer grain (census stays exact)
            for seq, prior in grown:
                self.manager.rollback_blocks(seq, prior)
            return None

        n = self._bucket(len(live))
        b = self._table_width_for(max(len(s.blocks) for s in live))
        tok0 = np.zeros((n, ), np.int32)
        start0 = np.zeros((n, ), np.int32)
        tables = np.full((n, b), self.manager.trash_block, np.int32)
        for i, seq in enumerate(live):
            tok0[i] = seq.tokens[seq.seen_tokens]
            start0[i] = seq.seen_tokens
            tables[i] = self.manager.block_table_row(seq, width=b)
        draft = drafter.propose_batch(live, k, n, counters=self.counters)
        if draft is None:
            # the drafter's private pool couldn't cover the round: undo the
            # target-pool grab and let the plain burst run instead
            for seq, prior in grown:
                self.manager.rollback_blocks(seq, prior)
            return None
        sample_cfg = None if greedy else (self.config.temperature,
                                          self.config.top_k, self.config.top_p)
        verify = self._compiled_spec_verify(n, k, b, sample_cfg=sample_cfg)
        self.counters.dispatches += 1
        if isinstance(draft, np.ndarray):
            self.counters.uploads += 4
            self.counters.upload_ints += int(tok0.size + start0.size
                                             + tables.size + draft.size)
            draft_dev = jnp.asarray(draft)
        else:
            # ModelDrafter proposals are already device-resident
            self.counters.uploads += 3
            self.counters.upload_ints += int(tok0.size + start0.size
                                             + tables.size)
            draft_dev = draft
        self.kv, packed, self._rng = verify(self.params, self.kv,
                                            jnp.asarray(tok0), draft_dev,
                                            jnp.asarray(start0),
                                            jnp.asarray(tables), self._rng)
        # one forward pass over [n, k + 1] token slots; the tables are read
        # before the accepted runs roll their draft-overshoot blocks back
        live_blocks = sum(len(seq.blocks) for seq in live)
        handle = DeferredRuns(packed_dev=packed, uids=[s.uid for s in live],
                              counters=self.counters)
        with self._phase_annotation("burst", "wait"):
            raw = handle.runs()  # ONE sync absorbs the whole ragged round
        bs = self.manager.block_size
        out: Dict[int, List[int]] = {}
        accepted_total = 0
        max_run = 1
        for seq in live:
            run = raw[seq.uid]
            if eos_token_id is not None:
                for j, tok in enumerate(run):
                    if tok == int(eos_token_id):
                        run = run[:j + 1]
                        break
            accepted_total += max(0, len(run) - 1)
            seq.tokens.extend(run)
            seq.seen_tokens += len(run)
            # the verify wrote KV for every draft position; positions past
            # the accepted run are stale and their trailing blocks must not
            # outlive the round — roll the table back to exactly the blocks
            # covering the kept tokens (the census and prefix registration
            # watermarks follow), before the allocator could hand a
            # drafted-into block to another sequence as "free" later
            keep = -(-len(seq.tokens) // bs)
            if len(seq.blocks) > keep:
                self.manager.rollback_blocks(seq, keep)
            # a round's first position can complete the FINAL prompt block
            # (same seam as the burst path)
            self.manager.register_prefix_blocks(seq)
            self.counters.burst_tokens += len(run)
            max_run = max(max_run, len(run))
            out[seq.uid] = run
        self.counters.count_slots(n, k + 1, b, sum(len(r) for r in out.values()),
                                  live_blocks, every_position=True, spans=self._selected_spans(
                                      (int(start0[i]), k + 1) for i in range(len(live))))
        self.counters.spec_rounds += 1
        self.counters.spec_proposed += len(live) * k
        self.counters.spec_accepted += accepted_total
        self.spec_stats.note_round(len(live) * k, accepted_total,
                                   [len(r) for r in out.values()])
        self._spec_controller.note_round(len(live) * k, accepted_total)
        # the deepest accepted run is the round's sequential-step equivalent
        self.scheduler.note_fused_work(max_run,
                                       sum(len(r) for r in out.values()))
        self.tracer.event("spec_verify", step=self.scheduler.steps, k=k,
                          seqs=len(live), accepted=accepted_total)
        self.tracer.on_burst_tokens({uid: len(r) for uid, r in out.items()})
        if self.journal is not None:
            # VERIFIED tokens only ever reach the WAL: the accepted prefix +
            # corrected token just materialized is the frame — an unverified
            # draft token can never be journaled, so replay of a crash
            # mid-verify regenerates byte-identical streams
            self.journal.note_token_map(out)
            self.journal.flush()
        self._kv_steps += max_run
        self._refresh_kv()
        self._emit_serving_gauges(tokens_run=sum(len(r) for r in out.values()))
        return out

    def _fused_decode(self, window: int, *, greedy: bool,
                      eos_token_id: Optional[int]
                      ) -> Optional[Dict[int, List[int]]]:
        """Dispatch one fused decode round: speculative draft/verify when the
        section is armed and the adaptive-k controller is off its floor,
        plain burst otherwise.  The draft length is snapped DOWN to the
        largest ladder rung fitting both the controller's pick and the
        remaining-budget window (emitting at most window tokens per
        sequence), so every dispatched verify width is a prewarmable bucket
        — never an off-ladder shape that would compile mid-serve."""
        if self._drafter is not None and self._spec_controller is not None:
            nk = self._spec_controller.next_k()
            if nk > 1:
                cap = min(nk, window - 1)
                k_d = max((r for r in self._spec_controller.ladder if r <= cap),
                          default=0)
                if k_d >= 1:
                    out = self.decode_spec(k_d, greedy=greedy,
                                           eos_token_id=eos_token_id)
                    if out is not None:
                        return out
                    if self.spec_stats is not None:
                        self.spec_stats.fallback_rounds_total += 1
        return self.decode_burst(window, greedy=greedy,
                                 eos_token_id=eos_token_id)

    def _spec_snapshot(self) -> Dict[str, Any]:
        """``health()["spec_decode"]``: {"enabled": False} with the section
        off (one shape for probes, same contract as qos), else controller
        state (live k, acceptance EWMA, ladder) + lifetime counters + the
        tokens-per-verify histogram."""
        if self.spec_stats is None or self._spec_controller is None:
            return {"enabled": False}
        return {"enabled": True,
                "drafter": (self.spec_cfg.drafter if self._drafter is not None
                            else "none"),
                **self._spec_controller.snapshot(),
                **self.spec_stats.snapshot()}

    # ----------------------------------------------------------- convenience
    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None, greedy: bool = True, *,
                 strict: bool = True, priorities: Optional[Sequence[int]] = None,
                 ttl_s: Optional[float] = None,
                 tenants: Optional[Sequence[str]] = None,
                 service_classes: Optional[Sequence[str]] = None
                 ) -> Union[List[List[int]], List[RequestResult]]:
        """Serve a batch to completion through the continuous-batching loop.

        Requests flow through the admission queue (bounded, priority-aware,
        load-shed under pressure — admission.py), are evicted between steps
        once past their deadline (``ttl_s`` or the config default), and a
        progress watchdog bounds live-but-unschedulable loops.

        ``strict=True`` (default, the pre-resilience contract): returns
        ``List[List[int]]`` of prompt+generated tokens and raises on the first
        shed/failure/stall (:class:`ServingStalledError` carries a full state
        snapshot).  ``strict=False``: every request runs to a terminal status
        and the call returns per-request :class:`RequestResult` objects
        (status in {ok, shed, deadline_expired, preempt_requeued_exhausted,
        failed}) — one bad request no longer costs the rest of the batch.

        ``greedy=False`` samples with the engine config's temperature/top-k/
        top-p — still through the device-side burst (the scan carries the rng
        and an eos done-mask), so sampled serving runs at burst throughput
        rather than one host round-trip per token."""
        uids = list(range(len(prompts)))
        results = self._serve(uids, prompts, max_new_tokens=max_new_tokens,
                              eos_token_id=eos_token_id, greedy=greedy, strict=strict,
                              priorities=priorities, ttl_s=ttl_s,
                              tenants=tenants, service_classes=service_classes)
        if strict:
            return [results[u].tokens for u in uids]
        return [results[u] for u in uids]

    def serve_recovered(self, requests: Sequence[RecoveredRequest], *,
                        max_new_tokens: int, eos_token_id: Optional[int] = None,
                        greedy: bool = True, strict: bool = False
                        ) -> Dict[int, RequestResult]:
        """Serve a batch where some requests resume a previous engine life
        (ISSUE 8): each :class:`RecoveredRequest` carries the token prefix it
        already emitted (replayed from the durable journal) and its REMAINING
        TTL.  Re-admitted sequences prefill ``prompt + prefix`` in one pass —
        the KV rebuild — and then continue decoding from where they died; the
        prefix counts against ``max_new_tokens`` so a recovered request never
        overruns its original budget.  Entries with an empty prefix are
        ordinary admissions riding the same call (the supervisor routes new
        work through here too, so one serve covers a mixed recovery)."""
        uids = [int(r.uid) for r in requests]
        prompts = [list(r.prompt) for r in requests]
        prefixes = {int(r.uid): [int(t) for t in r.prefix]
                    for r in requests if r.prefix}
        ttls = {int(r.uid): r.ttl_s for r in requests if r.pin_ttl}
        priorities = [int(r.priority) for r in requests]
        # QoS identity rides recovery AS JOURNALED (ISSUE 19): the planner
        # copied tenant/class from the journal entry, so a crash can never
        # launder a best-effort request into interactive
        tenants = [r.tenant for r in requests]
        service_classes = [r.service_class for r in requests]
        self.ft_stats["recovered_requests_total"] += len(prefixes)
        for r in requests:
            if r.prefix:
                self.tracer.event("recovered", uid=int(r.uid),
                                  prefix=len(r.prefix))
                self._record_resilience("serving_recovered", uid=int(r.uid),
                                        prefix_tokens=len(r.prefix))
        return self._serve(uids, prompts, max_new_tokens=max_new_tokens,
                           eos_token_id=eos_token_id, greedy=greedy,
                           strict=strict, priorities=priorities, ttl_s=None,
                           prefixes=prefixes, ttls=ttls, tenants=tenants,
                           service_classes=service_classes)

    def _serve(self, uids: List[int], prompts: Sequence[Sequence[int]], *,
               max_new_tokens: int, eos_token_id: Optional[int], greedy: bool,
               strict: bool, priorities: Optional[Sequence[int]],
               ttl_s: Optional[float],
               prefixes: Optional[Dict[int, List[int]]] = None,
               ttls: Optional[Dict[int, Optional[float]]] = None,
               tenants: Optional[Sequence[str]] = None,
               service_classes: Optional[Sequence[str]] = None
               ) -> Dict[int, RequestResult]:
        my = set(uids)
        self._reset_table_width_if_idle()
        conflict = sorted(my & set(self.manager.seqs))
        if conflict:
            # fail fast BEFORE any queue/manager mutation: finalization and
            # cleanup key on uid, so a collision with a put()-registered
            # sequence would otherwise let this call evict foreign work
            raise ValueError(f"generate() uids {conflict} are already tracked (direct "
                             f"put() requests coexist with generate() only with "
                             f"disjoint uids); flush them first")
        for uid in uids:
            # reusing a retired/flushed uid is legitimate; a failure entry left
            # over from its previous life must not poison the fresh request
            self.manager.failures.pop(uid, None)
        results: Dict[int, RequestResult] = {}
        # a recovered prefix pre-spends its share of the max_new_tokens
        # budget: the request finishes after (budget - prefix) NEW tokens
        produced = {u: len(prefixes[u]) if prefixes and u in prefixes else 0
                    for u in uids}
        token_cap = self.manager.max_blocks_per_seq * self.manager.block_size
        try:
            # ---- admission: shed-or-queue BEFORE any KV allocation
            for i, (uid, prompt) in enumerate(zip(uids, prompts)):
                prefix = prefixes.get(uid, []) if prefixes else []
                if ttls is not None and uid in ttls:
                    t, apply_default = ttls[uid], False  # recovery pins the TTL
                else:
                    t, apply_default = ttl_s, True
                tenant = tenants[i] if tenants is not None else None
                service_class = service_classes[i] if service_classes is not None else None
                if self.qos is not None:
                    # normalize HERE (not just inside submit) so the journal
                    # admit record carries the class the policy resolved —
                    # replay must reconstruct identity, not re-default it
                    tenant = str(tenant) if tenant else "default"
                    service_class = self.qos.service_class(service_class)
                shed = self.admission.submit(
                    uid, [int(tok) for tok in prompt],
                    priority=priorities[i] if priorities is not None else 0,
                    ttl_s=t, apply_default_ttl=apply_default,
                    kv_utilization=self.manager.kv_utilization(),
                    token_cap=token_cap, prefix=prefix or None,
                    recovered=bool(prefix), tenant=tenant,
                    service_class=service_class)
                if shed is not None:
                    self._record_resilience("serving_shed", uid=uid, code=shed.code,
                                            retryable=shed.retryable, detail=shed.detail)
                    if self.journal is not None:
                        # direct write, NOT _journal_terminal: a shed request
                        # was never admitted so it isn't in `watched` (and a
                        # recovered request re-shed at re-admission is only in
                        # a PREVIOUS generation's watched set) — but its
                        # terminal must still be durable, or replay re-serves
                        # it forever / reports it unresolved
                        self.journal.record_terminal(
                            uid, SHED, reason=str(shed),
                            retryable=shed.retryable,
                            # gate on qos: a QoS-off journal stays byte-
                            # identical to the pre-QoS record format
                            shed_code=(shed.code if self.qos is not None
                                       else None))
                    if strict:
                        raise RuntimeError(f"request {uid} shed: {shed}")
                    results[uid] = RequestResult(uid=uid, status=SHED, reason=str(shed),
                                                 retryable=shed.retryable,
                                                 retry_after_s=shed.retry_after_s,
                                                 shed_code=shed.code)
                elif self.journal is not None:
                    # the effective TTL (what admission just stamped) rides
                    # the admit record, with a wall-clock stamp so recovery
                    # can keep the ORIGINAL deadline clock across processes
                    effective = t if t is not None else \
                        (self.resilience.default_ttl_s if apply_default else None)
                    self.journal.record_admit(
                        uid, [int(tok) for tok in prompt],
                        priority=priorities[i] if priorities is not None else 0,
                        ttl_s=effective, max_new_tokens=max_new_tokens,
                        eos_token_id=eos_token_id, greedy=greedy,
                        prefix_len=len(prefix),
                        tenant=(tenant if tenant is not None else "default"),
                        service_class=(service_class if service_class is not None
                                       else "interactive"))
            # counterfactual prefix-cache report for THIS pass: the queued
            # (non-shed) prompts joining whatever is already live
            self._observe_prefix({uid: [int(t) for t in prompt]
                                  for uid, prompt in zip(uids, prompts)
                                  if uid not in results})
            self._prewarm(max_new_tokens, greedy=greedy)
            # re-arm the serve-loop jax.profiler window for THIS
            # generate() (ISSUE 16 satellite — one window per call)
            self._tell("serve_profile_begin")
            self._serve_loop(uids, my, results, produced, max_new_tokens=max_new_tokens,
                             eos_token_id=eos_token_id, greedy=greedy, strict=strict)
            if self.counters.tallied is not None:
                # the wave is over and its tokens are on the host: one fetch of the
                # device's running pick tallies, never one a step
                self.counters.absorb_tallies(materialize(self.kv[TALLY], self.counters))
            # post-pass pool state: final census/forecast refresh, then the
            # census-vs-allocator partition invariant (the PR-4 double-free
            # guard, continuously checked)
            self._refresh_kv()
            if self.kv_cfg.invariant_check:
                self.check_kv_invariant()
        except Exception:
            # a strict-mode raise must not leak this call's queued tickets or
            # live sequences into the next call (they would decode unbounded
            # with nobody tracking their budget)
            self._abandon(my, results)
            raise
        finally:
            # a serve capture window must never leak across generate()
            # calls — close it even on a strict raise
            self._tell("serve_profile_end")
            # flush the Chrome-trace export (if configured) even on a strict
            # raise — the partial trace is exactly what the postmortem wants
            self.tracer.write_chrome_trace()
            if self.journal is not None:
                # buffered token deltas must not outlive the call that
                # materialized them (a strict raise included)
                self.journal.flush()
            # final ops snapshot: a post-serve scrape must see the completed
            # state (lifetime counters, emptied queue), not a mid-wave cache
            self.refresh_ops(force=True)
        return results

    def _serve_loop(self, uids: List[int], my: set, results: Dict[int, RequestResult],
                    produced: Dict[int, int], *, max_new_tokens: int,
                    eos_token_id: Optional[int], greedy: bool, strict: bool) -> None:
        cfg = self.resilience
        fp = self.fastpath
        fusion_min = max(2, fp.fusion_min_steps) if fp.enabled else 2
        # an externally wrapped step() (fault injectors, tracing shims) must
        # keep intercepting every step, so the split dispatch/materialize
        # pipeline only engages on an unwrapped engine
        can_pipeline = (fp.enabled and fp.pipeline_depth > 0
                        and "step" not in self.__dict__)
        stall_streak = 0
        last_sig = None
        prof = self.phase_profiler
        serve_iter = 0  # per-generate index driving the serve profiler window

        def absorb(stepped):
            self._absorb_step(stepped, my, results, produced,
                              max_new_tokens=max_new_tokens,
                              eos_token_id=eos_token_id, strict=strict)

        while any(u not in results for u in uids):
            self.counters.loop_iterations += 1
            # serve-loop jax.profiler capture window (ISSUE 16 satellite):
            # [start, stop) in per-generate iterations, one window per
            # generate() — a no-op unless the window knobs are set
            self._tell("profile_serve_boundary", serve_iter)
            serve_iter += 1
            prof.begin_iteration()
            # serve-iteration liveness stamp (ISSUE 8): phase "serving" on
            # host-owned ints only — the supervisor reads staleness as a hang.
            # Throttled inside the writer; NULL writer when supervision is off
            self._heartbeat.stamp(self.counters.loop_iterations, phase="serving")
            # ops-plane cache refresh (ISSUE 11): host-only snapshot rebuild,
            # throttled on the injectable clock; a no-op with the plane off
            self.refresh_ops()
            prof.mark("other")  # liveness/ops bookkeeping, not a serve phase
            if self._inflight is not None and (len(self.admission)
                                               or self._any_live_deadline()):
                # wave boundary: admission/deadline handling below may evict
                # or finalize sequences — catch host state up to the device
                # first so PR-4 semantics match the synchronous loop exactly
                self.counters.flushes += 1
                self.tracer.event("flush", step=self.scheduler.steps, cause="wave")
                with self._phase_annotation("flush"):
                    absorb(self._settle_inflight())
                prof.mark("flush")
            self._expire_live()
            with self._phase_annotation("admission_pump"):
                self._pump_admissions(my, results, strict)
            prof.mark("admission_pump")

            # pure-decode fast path: burst k steps on device (greedy or
            # sampled; eos-aware via the carried done-mask).  The pump just
            # ran, so anything still queued could NOT be admitted this
            # iteration — bursting doesn't delay fusion, provided the burst
            # is SLICED so admission latency (and deadline-eviction
            # overshoot) stays bounded to a few tokens instead of paying the
            # per-token host round-trip for a whole backpressure window.
            k = self._fusion_window(uids, results, produced, max_new_tokens)
            fusible = False
            if k >= fusion_min:
                # cheap host-side applicability check BEFORE paying a pipeline
                # flush: the burst needs a pure-decode live set that fits one
                # ragged batch (decode_burst re-verifies pool capacity itself)
                decoding, prefilling = self.scheduler.live_split(self.manager)
                fusible = (bool(decoding) and not prefilling
                           and len(decoding) <= self.scheduler.max_seqs)
            if fusible and self._inflight is not None:
                # the burst's bookkeeping finalizes sequences host-side:
                # absorb the in-flight step first, then re-measure the window
                self.counters.flushes += 1
                self.tracer.event("flush", step=self.scheduler.steps, cause="fuse")
                with self._phase_annotation("flush"):
                    absorb(self._settle_inflight())
                prof.mark("flush")
                k = self._fusion_window(uids, results, produced, max_new_tokens)
            if fusible and k >= fusion_min:
                with self._phase_annotation("burst"):
                    burst = self._fused_decode(k, greedy=greedy,
                                               eos_token_id=eos_token_id)
                if burst:
                    for uid, toks in burst.items():
                        if uid not in my or uid in results:
                            continue
                        produced[uid] += len(toks)
                        hit_eos = (eos_token_id is not None and toks
                                   and toks[-1] == eos_token_id)
                        if hit_eos or produced[uid] >= max_new_tokens:
                            self._finish_ok(uid, results,
                                            "eos" if hit_eos else "max_new_tokens")
                    prof.mark("burst")
                    prof.end_iteration()
                    continue
                prof.mark("burst")  # a declined burst attempt still costs time

            if can_pipeline and not (len(self.admission) or self._any_live_deadline()):
                # async step pipelining: dispatch step N, then absorb step
                # N-1's tokens while the device executes N — host scheduling
                # of step N+1 overlaps device execution of N
                if (self._inflight is not None
                        and all(produced[u] + (1 if u in self._inflight.row_of else 0)
                                >= max_new_tokens
                                for u in uids if u not in results)):
                    # every unresolved request finishes the moment the
                    # in-flight step lands — absorb it instead of dispatching
                    # a guaranteed-overshoot step
                    absorb(self._settle_inflight())
                    prof.mark("absorb_patch")
                else:
                    with self._phase_annotation("dispatch"):
                        deferred = self._dispatch_step(greedy)
                    prev, self._inflight = self._inflight, deferred
                    with self._phase_annotation("absorb_patch"):
                        absorb(prev.patch(self.manager) if prev is not None else {})
                    prof.mark("absorb_patch")
            else:
                if self._inflight is not None:
                    self.counters.flushes += 1
                    self.tracer.event("flush", step=self.scheduler.steps,
                                      cause="sync")
                    with self._phase_annotation("flush"):
                        absorb(self._settle_inflight())
                    prof.mark("flush")
                with self._phase_annotation("dispatch"):
                    stepped = self.step(greedy=greedy)
                    with self._phase_annotation("absorb_patch"):
                        absorb(stepped)
                prof.mark("absorb_patch")

            # ---- progress watchdog: a live-but-unschedulable engine must trip,
            # not spin.  The signature covers every observable scheduling input;
            # identical signatures for the watchdog window = stall.
            sig = self._progress_signature()
            stall_streak = stall_streak + 1 if sig == last_sig else 0
            last_sig = sig
            self._stall_streak = stall_streak
            if stall_streak >= cfg.stall_watchdog_steps:
                if self._inflight is not None:
                    absorb(self._settle_inflight())
                self._handle_stall(my, results, strict)
                stall_streak, last_sig = 0, None
                self._stall_streak = 0

            if self.journal is not None:
                # wave-boundary WAL flush: every token this iteration
                # materialized is already host-side, so the delta frame costs
                # one buffered file append (fsync amortized per fsync_every)
                self.journal.flush()
            prof.end_iteration()  # residual (watchdog, WAL) lands in "other"

        if self._inflight is not None:
            # the final absorb resolved every request with a step still in
            # flight (e.g. a coexisting put() sequence rode it): patch its
            # placeholders so no PENDING_TOKEN ever escapes the loop
            self._inflight.patch(self.manager)
            self._inflight = None

    def _fusion_window(self, uids: List[int], results: Dict[int, RequestResult],
                       produced: Dict[int, int], max_new_tokens: int) -> int:
        """Tokens worth fusing into one decode burst right now: the smallest
        remaining budget across this call's live requests, sliced to
        BURST_DEADLINE_SLICE while anything is queued or deadlined (ALL live
        sequences, not just this call's — a coexisting direct put(ttl_s=...)
        sequence rides the burst too and its deadline deserves the same
        bounded overshoot)."""
        live = [u for u in uids if u not in results]
        k = min((max_new_tokens - produced[u] for u in live), default=0)
        if len(self.admission) or self._any_live_deadline():
            k = min(k, self.BURST_DEADLINE_SLICE)
        return k

    def _absorb_step(self, stepped: Dict[int, int], my: set,
                     results: Dict[int, RequestResult], produced: Dict[int, int], *,
                     max_new_tokens: int, eos_token_id: Optional[int],
                     strict: bool) -> None:
        """Fold one step's outcomes into per-request results: sampled-token
        finishes (eos / max_new_tokens), failures, and evictions — exactly the
        bookkeeping the synchronous loop ran inline after step().  The
        pipelined loop feeds it the PREVIOUS step's materialized tokens."""
        for uid, tok in stepped.items():
            if uid not in my or uid in results:
                continue
            produced[uid] += 1
            hit_eos = eos_token_id is not None and tok == eos_token_id
            if produced[uid] >= max_new_tokens or hit_eos:
                self._truncate_overshoot(uid)
                self._finish_ok(uid, results, "eos" if hit_eos else "max_new_tokens")

        for uid, reason in list(self.manager.failures.items()):
            if uid in my and uid not in results:
                if strict:
                    raise RuntimeError(f"request {uid} failed: {reason}")
                self._record_resilience("serving_request_failed", uid=uid,
                                        reason=reason)
                self._journal_terminal(uid, FAILED, reason=reason)
                self.tracer.event("failed", step=self.scheduler.steps, uid=uid)
                self.tracer.on_terminal(uid, FAILED, reason=reason)
                seq = self.manager.seqs.get(uid)
                results[uid] = RequestResult(
                    uid=uid, status=FAILED, reason=reason,
                    tokens=list(seq.tokens) if seq is not None else [])
                if seq is not None:
                    self.manager.retire(uid, completed=False)
                # consume the entry: uids are reused across generate()
                # calls and a stale failure must not taint a fresh request
                self.manager.failures.pop(uid, None)

        # sequences finished WITHOUT emitting this step: a decode capped at
        # max_blocks_per_seq completes gracefully (length_capped — all its
        # generated tokens are valid), an expired request was evicted by
        # _expire_live, an exhausted preemption victim ends
        for uid in list(self.manager.seqs):
            if uid not in my or uid in results:
                continue
            seq = self.manager.seqs[uid]
            if not (seq.done and seq.finish_reason):
                continue
            if seq.finish_reason == DEADLINE_EXPIRED:
                if strict:
                    raise RuntimeError(f"request {uid} deadline_expired after "
                                       f"producing {seq.generated_tokens} tokens")
                results[uid] = RequestResult(uid=uid, status=DEADLINE_EXPIRED,
                                             tokens=list(seq.tokens), retryable=True,
                                             reason="deadline expired while running",
                                             queue_wait_s=seq.queue_wait_s,
                                             preemptions=seq.preemptions)
                self._journal_terminal(uid, DEADLINE_EXPIRED, retryable=True,
                                       reason="deadline expired while running")
                self.tracer.on_terminal(uid, DEADLINE_EXPIRED,
                                        reason="deadline expired while running")
                self.manager.retire(uid, completed=False)
            elif seq.finish_reason == PREEMPT_REQUEUED_EXHAUSTED:
                self._record_resilience("serving_preempt_requeued_exhausted",
                                        uid=uid, preemptions=seq.preemptions)
                if strict:
                    raise RuntimeError(
                        f"request {uid} preempted {seq.preemptions}x and evicted "
                        f"(KV pool pressure); enlarge num_blocks or lower concurrency")
                results[uid] = RequestResult(
                    uid=uid, status=PREEMPT_REQUEUED_EXHAUSTED,
                    tokens=list(seq.tokens), retryable=True,
                    reason=f"preempted {seq.preemptions}x under KV pressure",
                    preemptions=seq.preemptions, queue_wait_s=seq.queue_wait_s)
                self._journal_terminal(
                    uid, PREEMPT_REQUEUED_EXHAUSTED, retryable=True,
                    reason=f"preempted {seq.preemptions}x under KV pressure")
                self.tracer.on_terminal(
                    uid, PREEMPT_REQUEUED_EXHAUSTED,
                    reason=f"preempted {seq.preemptions}x under KV pressure")
                self.manager.retire(uid, completed=False)
            else:  # length_capped: a graceful completion
                self._finish_ok(uid, results, seq.finish_reason)

    def _truncate_overshoot(self, uid: int) -> None:
        """A request finishing on its step-N token may already have step N+1
        in flight (pipelined dispatch): drop the in-flight placeholder so the
        finished token list is exactly the synchronous loop's.  The stray
        device-side KV write lands in blocks this retirement frees; any later
        owner's prefill rewrites them before its lengths let them be read."""
        d = self._inflight
        if d is None or uid not in d.row_of:
            return
        seq = self.manager.seqs.get(uid)
        if seq is not None and seq.tokens and seq.tokens[-1] == PENDING_TOKEN:
            seq.tokens.pop()
            seq.seen_tokens = min(seq.seen_tokens, len(seq.tokens))
        d.drop_emit(uid)

    def _settle_inflight(self) -> Dict[int, int]:
        """Materialize and clear the in-flight step (no-op when none)."""
        d, self._inflight = self._inflight, None
        return d.patch(self.manager) if d is not None else {}

    def _any_live_deadline(self) -> bool:
        return any(s.deadline is not None and not s.done
                   for s in self.manager.seqs.values())

    def _abandon(self, my: set, results: Dict[int, RequestResult]) -> None:
        """Strict-mode raise cleanup: reclaim every trace of this call so the
        engine is immediately reusable (blocks freed, queue drained, stale
        failure entries consumed)."""
        if self._inflight is not None:
            try:
                # foreign (direct put()) sequences may hold placeholders from
                # the aborted step — patch them before this call's teardown
                self._inflight.patch(self.manager)
            finally:
                self._inflight = None
        for uid in list(self.manager.seqs):
            if uid in my:
                self.manager.retire(uid, completed=False)
        for uid in my:
            self.manager.failures.pop(uid, None)
        for ticket in self.admission.drain():
            self._forget_prefix(ticket.uid)  # died queued: retire never fires
        # close any still-open traces of this call so the live-trace map and
        # the strict caller's postmortem both see a terminal event
        self.tracer.abort_all(my, reason="strict-mode abort")
        self._stall_streak = 0  # the wedge was evicted with everything else

    # ------------------------------------------------- serving-loop internals
    def _prewarm(self, max_new_tokens: int, greedy: bool = True) -> None:
        """Serve-time compile-cache prewarm: AOT-compile the forward buckets
        this call's queued + live requests are about to hit (bounded by
        ``serving_fastpath.prewarm_buckets``) so the first wave doesn't pay
        mid-serve compile stalls.  With spec decode armed, ALSO prewarm the
        verify bucket for every adaptive-k ladder rung — the AOT key includes
        the verify width, so a k drift mid-serve lands on a compiled
        executable (zero warm recompiles in spec mode).  Best-effort — any
        lowering failure falls back to compile-on-first-step."""
        fp = self.fastpath
        if not fp.enabled or fp.prewarm_buckets <= 0:
            return
        depth, max_prompt = self.admission.queued_stats()
        live = self.manager.live_uids()
        for uid in live:
            max_prompt = max(max_prompt, len(self.manager.seqs[uid].tokens))
        n_total = min(depth + len(live), self.scheduler.max_seqs)
        if n_total <= 0 or max_prompt <= 0:
            return
        bs = self.manager.block_size
        w_prefill = self._stepped_width(-(-(max_prompt + 1) // bs))
        w_decode = self._stepped_width(-(-(max_prompt + 1 + max_new_tokens) // bs))
        n_b = self._bucket(n_total)
        t_pf = self._bucket(max(1, min(self.scheduler.token_budget, max_prompt)))
        candidates = [(n_b, 1, w_prefill), (n_b, 1, w_decode),
                      (n_b, t_pf, w_prefill), (n_b, t_pf, w_decode)]
        warmed = 0
        for n, t, b in candidates:
            if warmed >= fp.prewarm_buckets:
                break
            if (n, t, b) in self._fwd_cache:
                continue
            try:
                self._aot_compile_fwd(n, t, b)
            except Exception as e:
                from ...utils.logging import warning_once
                warning_once(f"serving fastpath: prewarm of bucket {(n, t, b)} "
                             f"failed ({e}); falling back to on-demand compile")
                return
            warmed += 1
        if self._drafter is None or self._spec_controller is None:
            return
        sample_cfg = None if greedy else (self.config.temperature,
                                          self.config.top_k, self.config.top_p)
        ladder = self._spec_controller.ladder
        # deepest verify reach: prompt + per-round input token + run budget +
        # the largest rung of draft overshoot that the rollback then trims
        w_verify = self._stepped_width(
            -(-(max_prompt + 1 + max_new_tokens + max(ladder)) // bs))
        warmed_spec = 0
        for rung in ladder:
            for w in sorted({w_decode, w_verify}):
                if warmed_spec >= fp.prewarm_buckets:
                    return
                if ("spec_verify", n_b, rung, w, sample_cfg) in self._fwd_cache:
                    continue
                try:
                    self._aot_compile_spec_verify(n_b, rung, w, sample_cfg)
                except Exception as e:
                    from ...utils.logging import warning_once
                    warning_once(f"spec decode: prewarm of verify bucket "
                                 f"{(n_b, rung, w)} failed ({e}); falling "
                                 f"back to on-demand compile")
                    return
                warmed_spec += 1

    def _finish_ok(self, uid: int, results: Dict[int, RequestResult],
                   finish_reason: str) -> None:
        seq = self.manager.seqs[uid]
        seq.done = True
        seq.finish_reason = finish_reason
        results[uid] = RequestResult(uid=uid, status=OK, tokens=list(seq.tokens),
                                     finish_reason=finish_reason,
                                     queue_wait_s=seq.queue_wait_s,
                                     preemptions=seq.preemptions)
        self._journal_terminal(uid, OK, finish_reason=finish_reason)
        self.tracer.event("finish", step=self.scheduler.steps, uid=uid,
                          reason=finish_reason)
        self.tracer.on_terminal(uid, OK, finish_reason=finish_reason)
        self.manager.retire(uid)  # reclaim KV blocks immediately, not at batch end

    def _expire_live(self) -> None:
        """Engine-wide deadline enforcement between forwards: any live
        sequence past its deadline — however it was admitted (generate's
        admission pump or a direct put(ttl_s=...)) — is evicted in place:
        done, ``finish_reason: deadline_expired``, KV blocks reclaimed.  The
        serve loop converts evicted sequences into results; step()-level
        callers observe ``done`` + the finish reason."""
        now = self._clock()
        self.tracer.tick(now)  # donate the sweep's clock read to the recorder
        with self._phase_annotation("expire"):
            for seq in list(self.manager.seqs.values()):
                if seq.done or seq.deadline is None or now < seq.deadline:
                    continue
                self.manager.evict(seq, DEADLINE_EXPIRED)
                self._deadline_expired_total += 1
                self.tracer.event("expire", step=self.scheduler.steps, uid=seq.uid,
                                  produced=seq.generated_tokens)
                self._record_resilience("serving_deadline_expired", uid=seq.uid,
                                        produced=seq.generated_tokens,
                                        seen_tokens=seq.seen_tokens)
        # phase attribution (ISSUE 16): a no-op (and no clock read) unless
        # the profiler is enabled AND inside a serve-loop iteration
        self.phase_profiler.mark("expire")

    def _pump_admissions(self, my: set, results: Dict[int, RequestResult],
                         strict: bool) -> bool:
        """Move queued tickets into the state manager while the pool has
        headroom; tickets that expired waiting become deadline_expired results
        without ever owning a block.  Returns True when tickets remain queued
        because the pump has no headroom (live cap / pool pressure) — the
        serve loop may then burst, since nothing could fuse anyway."""
        cfg = self.resilience
        while len(self.admission):
            live = self.manager.live_uids()
            if cfg.max_live_seqs and len(live) >= cfg.max_live_seqs:
                return True
            if live and self.manager.kv_utilization() >= cfg.shed_kv_utilization:
                return True  # pool pressure: hold the queue (progress guaranteed
                # — something is live, and retiring it reopens the pump)
            ticket, expired = self.admission.pop_ready()
            for t in expired:
                self.tracer.event("queue_expired", step=self.scheduler.steps,
                                  uid=t.uid)
                self._forget_prefix(t.uid)  # died queued: retire never fires
                if t.uid in my and t.uid not in results:
                    self._deadline_expired_total += 1
                    self._record_resilience("serving_deadline_expired", uid=t.uid,
                                            produced=0, queued=True)
                    if strict:
                        raise RuntimeError(f"request {t.uid} deadline_expired while queued")
                    results[t.uid] = RequestResult(
                        uid=t.uid, status=DEADLINE_EXPIRED, retryable=True,
                        reason="deadline expired in the admission queue")
                    self._journal_terminal(
                        t.uid, DEADLINE_EXPIRED, retryable=True,
                        reason="deadline expired in the admission queue")
                    self.tracer.on_terminal(
                        t.uid, DEADLINE_EXPIRED, t=self.tracer.last_now,
                        reason="deadline expired in the admission queue")
            if ticket is None:
                break
            now = self._clock()
            self.tracer.tick(now)
            wait = max(0.0, now - ticket.enqueue_t)
            self._queue_wait_s = wait
            # queue-wait histogram feeds health() percentiles even with span
            # tracing off: the wait is already computed, pure host arithmetic
            self.tracer.observe_queue_wait(wait)
            # crash recovery: a re-admitted ticket's token history is
            # prompt + already-emitted prefix (prefilled in one pass — the KV
            # rebuild), with prompt_len pinned so the prefix keeps counting
            # as generated output, not prompt
            seq = self.manager.add_sequence(ticket.uid, ticket.prompt + ticket.prefix,
                                            priority=ticket.priority,
                                            deadline=ticket.deadline, queue_wait_s=wait,
                                            prompt_len=len(ticket.prompt),
                                            tenant=ticket.tenant,
                                            service_class=ticket.service_class)
            # admit-time prefix lookup (ISSUE 13): map whatever shared prompt
            # blocks are already computed — a journal-replayed request lands
            # back on the shared blocks its previous life rode — and the
            # scheduler re-checks per prefill chunk for late-arriving hits
            self._map_prefix(seq)
            self.tracer.event("admit", step=self.scheduler.steps, uid=ticket.uid,
                              **({"recovered": True} if ticket.recovered else {}))
            self.tracer.on_admit(ticket.uid, now, queue_wait_s=wait,
                                 prompt_len=len(ticket.prompt) + len(ticket.prefix),
                                 tenant=(ticket.tenant if self.qos is not None
                                         else None))
        return False

    def _handle_stall(self, my: set, results: Dict[int, RequestResult],
                      strict: bool) -> None:
        cfg = self.resilience
        self.stalls_total += 1
        self.tracer.event("stall", step=self.scheduler.steps,
                          live_seqs=len(self.manager.seqs),
                          free_blocks=self.manager.allocator.free_blocks)
        # snapshot AFTER the stall event so the dump's flight-recorder tail
        # includes the trip itself at the end of the history that led to it
        snapshot = self.state_snapshot()
        self._record_resilience("serving_stall",
                                live_seqs=len(snapshot["live_uids"]),
                                free_blocks=snapshot["free_blocks"],
                                queue_depth=snapshot["queue_depth"])
        if strict:
            raise ServingStalledError(
                f"serving made no progress for {cfg.stall_watchdog_steps} consecutive "
                f"steps with {len(snapshot['live_uids'])} live sequences and "
                f"{snapshot['free_blocks']} free KV blocks — see .snapshot for the "
                f"full engine state", snapshot)
        # non-strict: fail the stuck requests (live AND still-queued) with the
        # snapshot attached, reclaim their blocks, and keep serving the rest
        reason = (f"stalled: no scheduling progress for "
                  f"{cfg.stall_watchdog_steps} steps")
        for uid in list(self.manager.seqs):
            if uid in my and uid not in results:
                seq = self.manager.seqs[uid]
                results[uid] = RequestResult(uid=uid, status=FAILED, reason=reason,
                                             tokens=list(seq.tokens), retryable=True,
                                             preemptions=seq.preemptions,
                                             queue_wait_s=seq.queue_wait_s)
                self._journal_terminal(uid, FAILED, reason=reason, retryable=True)
                self.tracer.on_terminal(uid, FAILED, reason=reason,
                                        t=self.tracer.last_now)
                self.manager.retire(uid, completed=False)
        for ticket in self.admission.drain():
            self._forget_prefix(ticket.uid)  # died queued: retire never fires
            if ticket.uid in my and ticket.uid not in results:
                results[ticket.uid] = RequestResult(uid=ticket.uid, status=FAILED,
                                                    reason=reason + " (still queued)",
                                                    retryable=True)
                self._journal_terminal(ticket.uid, FAILED, retryable=True,
                                       reason=reason + " (still queued)")
                self.tracer.on_terminal(ticket.uid, FAILED, t=self.tracer.last_now,
                                        reason=reason + " (still queued)")

    def _progress_signature(self):
        return (tuple(sorted((uid, s.seen_tokens, len(s.tokens), s.done)
                             for uid, s in self.manager.seqs.items())),
                len(self.admission), self.manager.allocator.free_blocks)

    def _record_resilience(self, event: str, **fields) -> None:
        self._tell("record_resilience", event, step=self.scheduler.steps, **fields)

    def _journal_terminal(self, uid: int, status: str, *,
                          finish_reason: Optional[str] = None,
                          reason: Optional[str] = None,
                          retryable: bool = False) -> None:
        """Mirror a ``RequestResult`` construction into the durable journal
        (only for uids this journal admitted — foreign put() traffic keeps
        its own lifecycle).  Terminal records order after their buffered
        token deltas; strict mode writes + fsyncs them eagerly, throughput
        mode lands them at the next wave flush (a one-iteration window —
        a crash inside it re-serves the finished request from its
        journaled prefix)."""
        j = self.journal
        if j is None or uid not in j.watched:
            return
        seq = self.manager.seqs.get(uid)
        j.record_terminal(uid, status, finish_reason=finish_reason, reason=reason,
                          retryable=retryable,
                          n_tokens=seq.generated_tokens if seq is not None else 0)

    # ------------------------------------------------------------ introspection
    def state_snapshot(self) -> Dict[str, Any]:
        """Full serving state for stall diagnostics: live uids, per-sequence
        progress and block-table occupancy, allocator free count, queue depth."""
        alloc = self.manager.allocator
        return {
            "live_uids": sorted(self.manager.seqs),
            "sequences": {uid: {"seen_tokens": s.seen_tokens,
                                "pending_tokens": s.pending_tokens,
                                "blocks": list(s.blocks),
                                "done": s.done,
                                "preemptions": s.preemptions,
                                "deadline": s.deadline}
                          for uid, s in self.manager.seqs.items()},
            "free_blocks": alloc.free_blocks,
            "num_blocks": alloc.num_blocks,
            "state": self._state_snapshot(),
            "queue_depth": len(self.admission),
            "scheduler_steps": self.scheduler.steps,
            # block-level pool state (ISSUE 12): the full per-block census
            # table (owner/age/residency — bounded by the pool size) plus the
            # rollups/forecast health() carries, for stall postmortems that
            # need to see WHICH blocks are pinned where
            "kv": self._kv_snapshot(with_table=True),
            # realized prefix-sharing state (ISSUE 13)
            "prefix_cache": (self.manager.prefix_cache.snapshot()
                             if self.manager.prefix_cache is not None
                             else {"enabled": False}),
            # recovery state (ISSUE 8): restart/recovery counters + journal
            # size, so a crash postmortem's snapshot shows the durability side
            "fault_tolerance": self._fault_tolerance_snapshot(),
            # perf observatory (ISSUE 16): phase budget + compile provenance
            # ride the stall dump — a wedge preceded by warm recompiles or a
            # phase blowup is diagnosable from the snapshot alone
            "perf": self._perf_snapshot(),
            # the event history that LED here (ISSUE 6): the always-on flight
            # recorder's tail rides every stall dump for postmortems
            "flight_recorder": self.tracer.recorder.tail(),
        }

    def _state_snapshot(self) -> Dict[str, Any]:
        """The fixed per-sequence state beside the pool (ISSUE 33): slots and
        bytes, hand-outs (each starts a sequence from the zero state), prefix
        hits declined because mapped blocks would not restore it.  Where the
        state is a tree of named leaves (ISSUE 43), what a sequence holds in
        each, from the arrays themselves."""
        m = self.manager
        if not m.state_slots:
            return {"enabled": False}
        snap = {"enabled": True, "state_slots": m.state_slots,
                "state_slots_in_use": m.state_slots_in_use,
                "state_bytes_per_seq": self.state_bytes_per_seq,
                "state_slots_zeroed": m.state_slots_zeroed,
                "prefix_declined_stateful": (m.prefix_cache.declined_stateful_total
                                             if m.prefix_cache is not None else 0)}
        if isinstance(self.kv[STATE], dict):
            snap["state_bytes_by_leaf"] = {name: int(leaf.nbytes // leaf.shape[1])
                                           for name, leaf in self.kv[STATE].items()}
        return snap

    def _kv_snapshot(self, with_table: bool = False) -> Dict[str, Any]:
        """The ``health()["kv"]`` / ``state_snapshot()["kv"]`` payload:
        census rollups, prefix-opportunity report, capacity forecast —
        JSON-safe host values only."""
        if self.kv_obs is None:
            return {"enabled": False}
        snap = self.kv_obs.snapshot(self.manager.allocator.free_blocks)
        if with_table:
            snap["census_table"] = self.kv_obs.census.table()
        return snap

    def _fault_tolerance_snapshot(self) -> Dict[str, Any]:
        return {
            **{k: self.ft_stats[k] for k in ("restarts_total",
                                             "recovered_requests_total",
                                             "degraded")},
            "journal_bytes": journal_bytes(self.journal.path
                                           if self.journal is not None else None),
            "journaling": self.journal is not None and self.journal.enabled,
            "heartbeat": bool(getattr(self._heartbeat, "enabled", False)),
        }

    def health(self) -> Dict[str, Any]:
        """Liveness snapshot for external probes (the serving analog of the
        training engine's telemetry record): pool state, queue depth, and the
        lifetime resilience counters."""
        return {
            # freshness stamp (ISSUE 17) from the INJECTABLE clock, advanced
            # at serve/wave boundaries: a fleet router compares it against its
            # own reading of the same clock and treats a snapshot past its
            # staleness horizon as unhealthy — a frozen replica's last-good
            # gauges must not attract traffic.  Stamped at refresh (not per
            # call) so the cached /healthz snapshot mirrors health() exactly
            "generated_at": self._health_generated_at,
            "live_seqs": len(self.manager.live_uids()),
            "queue_depth": len(self.admission),
            "free_blocks": self.manager.allocator.free_blocks,
            "kv_utilization": self.manager.kv_utilization(),
            "state": self._state_snapshot(),
            # block-level pool observability (ISSUE 12): census rollups
            # (fragmentation, block-age, blocks-per-request), counterfactual
            # prefix-cache opportunity, and the steps-to-exhaustion forecast
            "kv": self._kv_snapshot(),
            # realized copy-on-write prefix sharing (ISSUE 13): hits, tokens
            # saved, CoW copies, realized hit-rate — read next to the
            # counterfactual under kv.prefix
            "prefix_cache": (self.manager.prefix_cache.snapshot()
                             if self.manager.prefix_cache is not None
                             else {"enabled": False}),
            "scheduler_steps": self.scheduler.steps,
            "completed_total": self.manager.completed_requests,
            "failed_total": self.manager.failed_requests,
            "shed_total": self.admission.shed_total,
            "preempted_total": self.scheduler.preempted_total,
            "deadline_expired_total": self._deadline_expired_total,
            # the streak is a live gauge; stalls_total is the observable stall
            # signal (the streak resets the moment the watchdog handles a trip,
            # so a momentary `stalled` boolean could never be caught True)
            "stall_streak": self._stall_streak,
            "stalls_total": self.stalls_total,
            # host-link counters (ISSUE 5): the serve loop's orchestration
            # cost, for probes that watch syncs-per-token drift — plus the
            # parallelism shape (ISSUE 15) so the ops plane can tell a
            # sharded serve apart from a single-chip one at a glance
            "fastpath": {**self.counters.snapshot(), "tp": self.tp,
                         "mesh_shape": ({a: int(s) for a, s in
                                         self.topology.mesh.shape.items()}
                                        if self.topology is not None else {})},
            # SLO latency percentiles (ISSUE 6): queue_wait histogram is fed
            # by the admission pump even with span tracing off; ttft/tbt/e2e
            # fill in once serving_tracing.enabled is set
            "queue_wait": self.tracer.queue_wait.snapshot(),
            "latency": self.tracer.latency_snapshot(),
            "tracing_enabled": self.tracer.enabled,
            # crash-durability counters (ISSUE 8): supervised restarts,
            # requests recovered with an emitted prefix, journal size on
            # disk, and the drain-only degradation flag
            "fault_tolerance": self._fault_tolerance_snapshot(),
            # serving performance observatory (ISSUE 16): per-phase wall-time
            # attribution and compile provenance — the ledger reports even
            # with the phase profiler off.  Which scope each operation of the
            # ledger's programs belongs to is program_scopes(): it reads
            # executables, so it is a call of its own and no part of this
            "perf": self._perf_snapshot(),
            # the recent engine-event history (always on, bounded ring)
            "flight_recorder": self.tracer.recorder.tail(32),
            # multi-tenant QoS (ISSUE 19): per-tenant admit/shed/token
            # counters, resident KV blocks, and the last quota retry hint —
            # {"enabled": False} when the policy layer is off so probes can
            # key on one shape
            "qos": (self.qos.snapshot() if self.qos is not None
                    else {"enabled": False}),
            # speculative decoding (ISSUE 20): adaptive-k controller state,
            # lifetime proposal/acceptance counters, tokens-per-verify
            # histogram — {"enabled": False} when the section is off
            "spec_decode": self._spec_snapshot(),
        }
