"""Serving fast path — device-resident batch state + deferred host syncs.

The v2 ragged engine's serve loop used to rebuild its whole padded batch on
the host every step (one ``np`` rebuild + four ``jnp.asarray`` uploads) and
then block on ``np.asarray(toks)`` before it could schedule the next step —
pure orchestration overhead that left a ~20x gap between the fused decode
burst and the continuous-batching loop (1907 vs 90.4 tok/s in the last chip
record before this module; that record is deleted, the figures are history).
This module holds the three host-link levers the engine composes:

- :class:`DeviceBatchState` — persistent donated device buffers per
  ``(n_seqs, chunk, table_width)`` bucket (tokens / n_tokens / start_pos /
  block tables), updated by ONE jitted scatter of the rows that actually
  changed since the previous step (admissions, retirements, new tokens), so
  steady-state steps move O(changed seqs) ints across the host link instead
  of re-uploading the full padded batch.
- :class:`DeferredTokens` — the sanctioned deferred-sync handle for sampled
  tokens.  The engine appends :data:`PENDING_TOKEN` placeholders at dispatch
  time and patches them when the handle is materialized — one step later in
  the pipelined serve loop, immediately in the synchronous ``step()`` API.
  :func:`materialize` is the ONE place v2 serving code converts a device
  value to host; dslint's ``host-sync-in-hot-path`` rule flags any direct
  ``np.asarray`` on step results elsewhere under ``inference/v2/``.
- :class:`ServeCounters` — host-sync / dispatch / upload / compile counters
  that make the win provable (the fastpath tests assert <=1 host sync per
  serve-loop iteration in steady-state decode and a bounded compile count
  across a mixed-arrival scenario).

Nothing here schedules or owns sequences — that stays in the scheduler and
the ragged manager; this is purely the host<->device traffic layer.

Sharded serving (ISSUE 15): given the engine's mesh, :class:`DeviceBatchState`
places its buffers REPLICATED over it (``NamedSharding(mesh,
PartitionSpec())``) and pins replicated ``out_shardings`` on the donated
scatter/feed programs, so the same ≤1-sync loop drives a shard_mapped
forward under TP×DP meshes — the delta is broadcast once, never gathered.
"""

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...monitor.compile_events import compile_later

# host-side placeholder for a sampled-but-not-yet-fetched token.  Negative so
# it can never collide with a real vocab id; it only ever appears as the LAST
# entry of a live sequence's token list between dispatch and materialize.
PENDING_TOKEN = -1

# device-side mirror sentinel for a token slot that is fed on-device from the
# previous step's sampled tokens (the host never knows the value, so the
# mirror records "fed" instead of a real id and the diff never tries to
# re-upload it)
FED_SENTINEL = np.int32(-(2**31) + 1)


class ServeCounters:
    """Lifetime counters for the serve loop's host-link behavior.

    ``host_syncs``   device->host materializations (the expensive round-trips)
    ``dispatches``   device program launches (forward / pick / burst / scatter)
    ``uploads``      host->device transfers issued
    ``upload_ints``  int32 elements moved host->device by those transfers
    ``compiles``     distinct compiled programs (bucket shapes) built so far
    ``loop_iterations`` serve-loop iterations observed
    ``step_tokens`` / ``burst_tokens``  tokens emitted via stepwise vs fused
    ``flushes``      pipeline flushes forced by wave boundaries
    ``spec_rounds``  speculative draft/verify rounds dispatched (ISSUE 20)
    ``spec_proposed`` / ``spec_accepted``  draft tokens proposed vs accepted
    by the target's rejection sampler — their ratio is the acceptance rate
    behind the adaptive-k controller and the ``serving_spec_*`` metric
    families.  All three stay zero with spec decode off (the default), so
    the pre-spec counter fields keep their exact pre-spec values.

    What the device was asked to compute against what was live (ISSUE 24),
    bumped by :meth:`count_slots` where a forward program is launched:
    ``token_slots``  token positions the per-token layers of the launched
    programs computed: n x t a forward pass over a padded bucket, and the S
    flat slots of a compacted one (ISSUE 25: a step of 31 decode rows and one
    225-token chunk is the bucket 32 x 256, computed over S = 256)
    ``live_tokens``  of those, the tokens that advanced a sequence
    ``attn_token_slots``  token positions the ATTENTION layout of the launched
    programs held (ISSUE 40): n x t a padded pass, as ``token_slots``; for a
    compacted pass ``attn_slots(n, S)``, the positions of the paged kernel's
    flat row axis, each sequence begun on a whole sublane tile of rows (n x t
    until the kernel took q from the flat axis); ``live_tokens /
    attn_token_slots`` is how full the kernel's q is
    ``table_slots``  block-table entries the paged kernel's grid walks (n x b
    a forward pass)
    ``live_blocks``  of those, the entries that name a sequence's own block
    ``kernel_steps``  steps of the kernel's grid along the table (ISSUE 35: a
    step takes ``kernel_slots(t)`` table slots at once, so n x ceil(b / slots) a
    forward pass; ``table_slots / kernel_steps`` is the slots a step walked)
    ``compact_passes``  forward passes that ran compacted: how often the
    bucket held more slots than the step's live-token bound
    ``head_rows``  rows the head (final norm and vocabulary product) of the
    launched programs multiplied (ISSUE 44): n a forward pass of a step or a
    burst, which take each row's last live token before the head (it was
    ``token_slots`` while the head ran over every slot); n x t a speculative
    verify, which scores every position

    A mixture-of-experts model routes each token to k experts in every layer
    (ISSUE 27; the model module states ``moe_picks`` = k x layers and
    ``moe_rows(slots)``; a dense model has neither and both stay zero):
    ``moe_expert_rows``  rows the expert FFNs' grouped matmuls ran over, from
    the launched programs' static shapes, in every layer: a pass's token slots
    x k, rounded up to whole row tiles, where the leaves hold every routed
    expert; on a share (ISSUE 51) the window the held picks are compacted into
    (``moe/serving.py expert_rows``: what uniform routing sends here, with
    headroom), which a pass that holds more runs again (counted only where the
    device tallies it: ``moe_overflow_windows``)
    ``moe_routed_rows``  the picks live tokens made, whatever kind each is: all
    of them rows where every expert is held, on a share the held ones alone

    A family whose state is a recurrence scanned over a step's tokens in chunks
    (a gated delta rule, a state-space scan: the model module states
    ``state_scan`` with its own chunk; zero for every other):
    ``scan_chunks``  chunks the scans of the launched programs walked, from their
    static shapes (every sequence of a compacted pass begins on a chunk's edge;
    a step of one token a row walks none), in every such layer
    ``scan_positions``  the token positions of those chunks
    ``scan_live_positions``  of those, the positions that held a live token
    A family whose rows of ONE token leave the walk for its update kernel
    (ISSUE 55; ``state_scan`` says so with a fourth entry, the trips a compacted
    walk takes: Granite's Mamba-2) counts what its scan was GIVEN, from each
    launched row's ``n_tokens`` (``spans``): ``scan_live_positions`` the tokens
    of the rows of more than one token, ``scan_chunks`` / ``scan_positions`` the
    layout of a compacted walk (``ceil(S / chunk) + window`` chunks a trip,
    whatever the bucket's rows) times its trips; so ``live_tokens -
    scan_live_positions / layers`` is the rows the update kernel served, those of
    chunk passes among them.  Such a family alone counts and reports
    (``WALK_FIELDS``):
    ``scan_overflow_windows``  trips its compacted walks ran beyond their first:
    a pass that held more rows of several tokens than a window lays out, in
    every such layer (how many rows a launch walks the host knows: no tally on
    the device, no fetch)

    A family that attends a learned selection of the cache (ISSUE 45; the model
    module states ``selected_keys`` = (top-k, attention layers); zero, and absent
    from a snapshot, for every other: ``SELECTED_FIELDS``), counted from each launched row's ``start_pos`` and ``n_tokens``
    (``spans``), summed over live query tokens and attention layers:
    ``dsa_causal_keys``  ``position + 1``: the keys plain causal attention sees
    ``dsa_selected_keys``  ``min(position + 1, top-k)``: the keys attended
    ``dsa_scored_keys``  keys the indexer's score kernel scored the token: its
    tile's steps of ``INDEX_BLOCKS`` blocks up to the tile's last position
    (``ops/attention/dsa.py``)
    ``dsa_attended_keys``  key rows the attention kernel multiplied the token's
    rows with: every step of ``kernel_slots(t)`` blocks up to the sequence's
    length, selected or not (``ops/attention/paged.py`` masks what was not)

    A family whose attention layers differ in their window (ISSUE 56; the model
    module states ``attention_windows`` = one window a layer, None for a layer
    that attends its whole past; absent from a snapshot for every other:
    ``WINDOWED_FIELDS``), counted from each launched row's ``start_pos``
    (``spans``) in whole blocks of the pool:
    ``kv_blocks_behind_window``  blocks a live sequence holds WHOLLY BEHIND the
    window of the step's first query token, and so of every later one, times the
    windowed layers: what an allocator that frees by layer kind would hold no
    longer (``live_blocks`` x the layers is what the one table a sequence holds).
    It says what the one table keeps, not what the kernel fetched: that the
    walk leaves these blocks alone the kernel's tests hold (poisoned blocks) and
    the chip's trace shows (the kernel's time against its roofline)

    A family whose router may pick experts that compute nothing (ISSUE 49; the
    model module states ``pick_tallies``; absent from a snapshot for every
    other: ``TALLIED_FIELDS``).  Which kind a pick is only the device knows: the
    family's forward adds each pass's counts to ``kv_cache[TALLY]`` on the
    device and the engine reads the running sums ONCE A WAVE (the end of a
    ``generate()``: :meth:`absorb_tallies`), so they are current wherever a
    window begins or ends and cost no synchronisation a step.  Summed over the
    layers and over the rows the program took as live (a burst's padded and
    frozen rows among them: a burst's every row holds one token):
    ``moe_identity_picks``  picks on identity experts: ``w x`` added, no row of
    a grouped matmul
    ``moe_held_picks``  picks on experts held here: the rows of the grouped
    matmuls that multiply (the rest of ``moe_routed_rows`` is held elsewhere)
    ``moe_overflow_windows``  trips the expert layers ran beyond their first: a
    pass that held more picks than a window's rows (ISSUE 51); each ran the
    layer's ``moe_expert_rows`` rows once more, so ``moe_held_picks <=
    moe_expert_rows + rows a window x moe_overflow_windows``
    ``moe_experts_hit``  held experts that a layer-pass's live picks named, summed
    over layers and passes (ISSUE 62; a family that tallies it): the expert
    matrices the grouped matmuls must have read, whatever the routing's skew
    """

    FIELDS = ("host_syncs", "dispatches", "uploads", "upload_ints", "compiles",
              "loop_iterations", "step_tokens", "burst_tokens", "flushes",
              "spec_rounds", "spec_proposed", "spec_accepted",
              "token_slots", "live_tokens", "table_slots", "live_blocks",
              "compact_passes", "moe_routed_rows", "moe_expert_rows", "kernel_steps",
              "attn_token_slots", "scan_chunks", "scan_positions", "scan_live_positions",
              "head_rows")
    # counted, and reported by ``snapshot`` / ``delta_since``, only for a family that
    # attends a learned selection of the cache (``selected``): no other engine's
    # snapshot gains a key
    SELECTED_FIELDS = ("dsa_causal_keys", "dsa_selected_keys", "dsa_scored_keys",
                       "dsa_attended_keys")
    # the same for a family that tallies its picks on the device (``tallied``)
    TALLIED_FIELDS = ("moe_identity_picks", "moe_held_picks", "moe_overflow_windows", "moe_experts_hit")
    # the same for a family whose scan walks windows of its rows of several tokens (``walk_trips``)
    WALK_FIELDS = ("scan_overflow_windows", )
    # the same for a family whose attention layers differ in their window (``windowed``)
    WINDOWED_FIELDS = ("kv_blocks_behind_window", )

    def __init__(self, moe_picks: int = 0, moe_rows: Optional[Callable[[int], int]] = None,
                 kernel_slots: Callable[[int], int] = lambda t: 1,
                 attn_slots: Callable[[int, int], int] = lambda n, flat: flat,
                 scan: Optional[tuple] = None, selected: Optional[tuple] = None,
                 tallied: Optional[tuple] = None, windowed: Optional[tuple] = None):
        for f in (self.FIELDS + self.SELECTED_FIELDS + self.TALLIED_FIELDS + self.WALK_FIELDS
                  + self.WINDOWED_FIELDS):
            setattr(self, f, 0)
        self.moe_picks, self.moe_rows, self.kernel_slots = moe_picks, moe_rows, kernel_slots
        self.attn_slots = attn_slots
        self.scan = scan[:3] if scan else None  # (chunks(n, t, flat), positions a chunk, layers that scan)
        # a fourth entry, trips(walked): the scan walks windows of the rows of more than one token alone
        self.walk_trips = scan[3] if scan and len(scan) > 3 else None
        self.selected = selected  # (top-k, attention layers, the pool's block size)
        self.tallied = tallied  # the fields the device's running tallies are, in their order
        self._tallies_seen = (0, ) * len(tallied or ())
        self.windowed = windowed  # (one window a layer, None for a full one; the pool's block size)

    @property
    def reads_spans(self) -> bool:
        """Whether :meth:`count_slots` reads ``spans``: nothing is built for it otherwise."""
        return (self.selected is not None or self.walk_trips is not None
                or self.windowed is not None)

    def count_slots(self, n: int, t: int, b: int, live_tokens: int,
                    live_blocks: int, passes: int = 1,
                    flat: Optional[int] = None, every_position: bool = False,
                    spans: Optional[List[Tuple[int, int]]] = None) -> None:
        """One launch of a forward program over the bucket ``[n, t]`` tokens
        x ``[n, b]`` table slots; a burst of k steps is ``passes=k`` forward
        passes over ``[n, 1]``, and its ``live_tokens`` are the whole
        burst's.  ``flat``: the flat slots the program's per-token layers ran
        over in place of ``n x t`` (``models.transformer.flat_slots``), None
        for a padded program.  ``every_position``: the program's head scored
        every slot (a speculative verify) and not each row's last live token
        alone (a step, a burst).  ``spans``: each live row's ``(start_pos,
        n_tokens)`` of the first pass (a later pass of a burst begins one token
        further), read only where the family attends a selection or its scan
        passes one-token rows by (:attr:`reads_spans`).  Host integers only: no
        clock read, no device sync."""
        slots = n * t if flat is None else flat
        self.token_slots += slots * passes
        self.head_rows += (slots if every_position else n) * passes
        self.attn_token_slots += (n * t if flat is None else self.attn_slots(n, flat)) * passes
        self.live_tokens += live_tokens
        if self.moe_rows is not None:
            self.moe_expert_rows += self.moe_rows(slots) * passes
            self.moe_routed_rows += live_tokens * self.moe_picks
        if self.scan is not None:
            chunks_of, width, layers = self.scan
            if self.walk_trips is not None:
                walked = [count for _, count in spans or () if count > 1]
                chunks, scanned = chunks_of(n, t, flat, len(walked)) * passes, sum(walked)
                if flat is not None:
                    self.scan_overflow_windows += max(self.walk_trips(len(walked)) - 1, 0) * layers
            else:
                chunks, scanned = chunks_of(n, t, flat) * passes, live_tokens
            if chunks:
                self.scan_chunks += chunks
                self.scan_positions += chunks * width
                self.scan_live_positions += scanned * layers
        self.table_slots += n * b * passes
        self.kernel_steps += n * -(-b // self.kernel_slots(t)) * passes
        self.live_blocks += live_blocks * passes
        self.compact_passes += passes if flat is not None else 0
        if self.selected is not None and spans:
            self._count_selected(t, b, spans, passes)
        if self.windowed is not None and spans:
            self._count_windowed(spans, passes)

    def _count_windowed(self, spans, passes: int) -> None:
        from ...ops.attention.paged import walk_first_block
        windows, bs = self.windowed
        for window in set(windows) - {None}:
            layers = windows.count(window)
            for first, count in spans:  # a burst's row moves ``count`` tokens a pass
                self.kv_blocks_behind_window += layers * sum(
                    walk_first_block(start, window, bs)
                    for start in range(first, first + passes * count, count))

    def _count_selected(self, t: int, b: int, spans, passes: int) -> None:
        from ...ops.attention.dsa import INDEX_BLOCKS, token_tile
        topk, layers, bs = self.selected
        tile, scored_step = token_tile(t), INDEX_BLOCKS * bs
        walked_step = self.kernel_slots(t) * bs
        through = lambda m: m * (m + 1) // 2  # 1 + 2 + ... + m
        causal = chosen = scored = walked = 0
        for first, count in spans:
            for start in range(first, first + passes * count, count):
                end = start + count  # the row's tokens of this pass: positions start .. end - 1
                causal += through(end) - through(start)
                chosen += through(min(end, topk)) - through(min(start, topk)) \
                    + topk * (end - max(start, topk) if end > topk else 0)
                for at in range(start, end, tile):  # a tile scores up to its last token's position
                    upto = min(at + tile, end)
                    scored += (upto - at) * min(-(-upto // scored_step) * scored_step, b * bs)
                walked += count * min(-(-end // walked_step) * walked_step,
                                      -(-b * bs // walked_step) * walked_step)
        self.dsa_causal_keys += causal * layers
        self.dsa_selected_keys += chosen * layers
        self.dsa_scored_keys += scored * layers
        self.dsa_attended_keys += walked * layers

    def absorb_tallies(self, running) -> None:
        """The device's running int32 sums as fetched: what they grew by since the
        last fetch goes to their fields (a sum wraps around at 2**32; a wave's
        growth is far under that)."""
        for field, now, seen in zip(self.tallied, running, self._tallies_seen):
            setattr(self, field, getattr(self, field) + (int(now) - seen) % 2 ** 32)
        self._tallies_seen = tuple(int(now) for now in running)

    def _reported(self) -> Tuple[str, ...]:
        return (self.FIELDS + (self.SELECTED_FIELDS if self.selected is not None else ())
                + (self.TALLIED_FIELDS if self.tallied is not None else ())
                + (self.WALK_FIELDS if self.walk_trips is not None else ())
                + (self.WINDOWED_FIELDS if self.windowed is not None else ()))

    def snapshot(self) -> Dict[str, int]:
        return {f: int(getattr(self, f)) for f in self._reported()}

    def delta_since(self, snap: Dict[str, int]) -> Dict[str, int]:
        return {f: int(getattr(self, f)) - snap.get(f, 0) for f in self._reported()}


def materialize(dev_array, counters: Optional[ServeCounters] = None) -> np.ndarray:
    """THE sanctioned device->host sync for v2 serving step results.

    Every fetch of sampled tokens / done masks funnels through here so the
    cost is (a) counted and (b) statically auditable — dslint's
    host-sync-in-hot-path rule treats this helper as the one legal idiom and
    flags direct ``np.asarray`` on step results anywhere else in
    ``inference/v2/``.
    """
    if counters is not None:
        counters.host_syncs += 1
    # no suppression needed: the rule itself recognizes materialize() as the
    # sanctioned deferred-sync helper (tools/staticcheck/rules.py)
    return np.asarray(dev_array)


@dataclasses.dataclass
class DeferredTokens:
    """Handle to one dispatched step's sampled tokens still on device.

    ``emits``  [(uid, position_in_seq_tokens, batch_row)] for every sequence
    that produced a next token this step (finished prefill or decoded) — the
    positions hold :data:`PENDING_TOKEN` until :meth:`wait` patches them.
    ``row_of`` maps uid -> batch row for on-device feeding of the NEXT step's
    input tokens (the value never visits the host).

    ``tracer`` (monitor/tracing.py RequestTracer): the first :meth:`patch`
    is the moment this step's tokens become host-visible — exactly where
    per-request TTFT/TBT marks belong (ISSUE 6).  Reported once even though
    patch() itself is idempotent (the burst path pre-patches the in-flight
    handle and the serve loop settles it again).

    ``journal`` (inference/v2/journal.py RequestJournal): the same
    host-visibility moment is where emitted tokens enter the durable request
    WAL's buffer (ISSUE 8) — tokens the journal never saw die with a crash
    and are regenerated identically from the journaled prefix, so buffering
    at this seam adds zero device syncs and zero extra fetches.
    """
    toks_dev: object
    emits: List[Tuple[int, int, int]]
    row_of: Dict[int, int]
    counters: Optional[ServeCounters] = None
    tracer: Optional[object] = None
    journal: Optional[object] = None
    _cached: Optional[np.ndarray] = None
    _trace_reported: bool = False

    def wait(self) -> np.ndarray:
        """Materialize the sampled tokens (idempotent)."""
        if self._cached is None:
            self._cached = materialize(self.toks_dev, self.counters)
        return self._cached

    def patch(self, manager) -> Dict[int, int]:
        """Write the real token values over the placeholders and return the
        ``{uid: token}`` map of sequences that emitted this step.

        Sequences that vanished (retired/evicted mid-flight) are skipped;
        sequences whose placeholder was already truncated (finish overshoot)
        are skipped too — the patch keys on the recorded position still
        holding :data:`PENDING_TOKEN`.
        """
        toks = self.wait()
        out: Dict[int, int] = {}
        for uid, pos, row in self.emits:
            seq = manager.seqs.get(uid)
            if seq is None:
                continue
            tok = int(toks[row])
            if pos < len(seq.tokens) and seq.tokens[pos] == PENDING_TOKEN:
                seq.tokens[pos] = tok
            out[uid] = tok
        if not self._trace_reported and (self.tracer is not None
                                         or self.journal is not None):
            self._trace_reported = True  # patch() is idempotent; marks are not
            if self.tracer is not None:
                self.tracer.event("absorb", tokens=len(out))
                self.tracer.on_tokens_map(out)
            if self.journal is not None:
                self.journal.note_token_map(out)
        return out

    def drop_emit(self, uid: int) -> None:
        """Forget a uid's pending emit (its overshoot token was truncated)."""
        self.emits = [e for e in self.emits if e[0] != uid]
        self.row_of.pop(uid, None)


@dataclasses.dataclass
class DeferredRuns:
    """Handle to one speculative verify round's packed accept runs still on
    device (ISSUE 20) — the variable-length sibling of :class:`DeferredTokens`.

    ``packed_dev`` holds ``[n, k+2]`` int32 rows ``[count | e_0 .. e_k]``
    from the fused verify program's rejection sampler: row i emits its first
    ``count`` tokens (1 <= count <= k+1 — the accepted draft prefix plus one
    corrected/bonus token).  The count and the run ride the SAME array, so
    absorbing a whole verify round costs the one wave-boundary
    :func:`materialize` the burst path already pays — per-sequence
    acceptance-length variance never adds a second sync.

    ``uids`` maps batch row -> sequence uid for the live rows; padded rows
    beyond ``len(uids)`` carry garbage runs and are never read.
    """
    packed_dev: object
    uids: List[int]
    counters: Optional[ServeCounters] = None
    _cached: Optional[np.ndarray] = None

    def wait(self) -> np.ndarray:
        """Materialize the packed accept runs (idempotent)."""
        if self._cached is None:
            self._cached = materialize(self.packed_dev, self.counters)
        return self._cached

    def runs(self) -> Dict[int, List[int]]:
        """``{uid: emitted tokens}`` — each row truncated to its accept
        count.  Emitted runs are VERIFIED output (accepted prefix + the
        resampled token); unverified draft tails never leave this handle, so
        downstream seams (journal frames, tracer marks) can never observe a
        token the target model did not endorse."""
        packed = self.wait()
        out: Dict[int, List[int]] = {}
        for i, uid in enumerate(self.uids):
            count = int(packed[i, 0])
            out[uid] = [int(t) for t in packed[i, 1:1 + count]]
        return out


@dataclasses.dataclass
class _Slot:
    """One bucket's persistent device arrays plus their host mirror."""
    tokens: object          # device [n, t] int32
    n_tokens: object        # device [n] int32
    start_pos: object       # device [n] int32
    tables: object          # device [n, b] int32
    mirror: np.ndarray      # host [n, 1 + t + 2 + b] packed rows
    active_rows: int = 0


def round_up_pow2(n: int) -> int:
    """Next power of two >= n — the ONE bucketing primitive shared by batch
    shapes (engine ``_bucket``) and scatter-row padding, so the two can never
    silently diverge and multiply compiled shapes."""
    b = 1
    while b < n:
        b *= 2
    return b


class DeviceBatchState:
    """Per-bucket persistent batch buffers with incremental scatter updates.

    Rows are packed host-side as ``[tokens(t) | n_tokens | start_pos |
    tables(b)]`` so the per-step delta is ONE ``[m, 3 + t + b]`` int32 upload
    (changed-row indices ride in column 0) and ONE donated scatter dispatch,
    instead of four full-batch uploads.  The host mirror tracks exactly what
    the device holds, so shrinking batches neutralize their stale rows
    (n_tokens=0, tables=trash) without ever re-uploading unchanged ones —
    a stale row left live would write KV into blocks the allocator may have
    handed to another sequence.

    With a ``mesh`` (TP/DP-sharded serving, ISSUE 15) the persistent buffers
    live REPLICATED over the whole mesh — every device sees the full padded
    batch while params/KV carry the sharded dims, so the shard_mapped ragged
    forward consumes them with zero resharding.  The delta upload is placed
    replicated too, and the scatter/feed programs pin replicated
    ``out_shardings`` so donation still aliases in place (XLA only aliases a
    donated buffer when input and output shardings agree).  The per-step
    host-link cost is unchanged: O(changed seqs) ints, broadcast once.
    """

    def __init__(self, counters: ServeCounters, mesh=None, ledger=None):
        self.counters = counters
        # compile ledger (ISSUE 16): when attached, scatter/feed shape builds
        # are recorded there (site + key + class) and the ledger bumps
        # counters.compiles — the counter's values are unchanged, its units
        # just gain provenance; without a ledger the direct bump remains
        self._ledger = ledger
        self._replicated = (NamedSharding(mesh, PartitionSpec())
                            if mesh is not None else None)
        self._slots: Dict[Tuple[int, int, int], _Slot] = {}
        self._scatter_shapes: set = set()
        self._feed_shapes: set = set()
        if mesh is not None:
            rep = self._replicated
            self._scatter = jax.jit(self._scatter_impl, donate_argnums=(0, 1, 2, 3),
                                    out_shardings=(rep, rep, rep, rep))
            self._feed = jax.jit(self._feed_impl, donate_argnums=(0,),
                                 out_shardings=rep)
        else:
            self._scatter = jax.jit(self._scatter_impl, donate_argnums=(0, 1, 2, 3))
            self._feed = jax.jit(self._feed_impl, donate_argnums=(0,))

    def _device(self, arr: np.ndarray):
        """Host->device upload: replicated over the mesh under sharded
        serving (a committed single-device array would be rejected by the
        shard_mapped forward), default placement otherwise."""
        if self._replicated is not None:
            return jax.device_put(arr, self._replicated)
        return jnp.asarray(arr)

    @staticmethod
    def _scatter_impl(tokens, n_tokens, start_pos, tables, packed):
        t = tokens.shape[1]
        idx = packed[:, 0]
        return (tokens.at[idx].set(packed[:, 1:1 + t]),
                n_tokens.at[idx].set(packed[:, 1 + t]),
                start_pos.at[idx].set(packed[:, 2 + t]),
                tables.at[idx].set(packed[:, 3 + t:]))

    @staticmethod
    def _feed_impl(tokens, toks_prev, pairs):
        # pairs [m, 2]: (dst_row, src_row) — the next step's input token IS
        # the previous step's sampled token; it never visits the host
        return tokens.at[pairs[:, 0], 0].set(toks_prev[pairs[:, 1]])

    # ------------------------------------------------------------------ slots
    def slot(self, key: Tuple[int, int, int], trash_block: int) -> _Slot:
        s = self._slots.get(key)
        if s is None:
            n, t, b = key
            mirror = np.zeros((n, 3 + t + b), np.int32)
            mirror[:, 0] = np.arange(n)
            mirror[:, 3 + t:] = trash_block
            s = _Slot(tokens=self._device(np.zeros((n, t), np.int32)),
                      n_tokens=self._device(np.zeros((n,), np.int32)),
                      start_pos=self._device(np.zeros((n,), np.int32)),
                      tables=self._device(np.full((n, b), trash_block, np.int32)),
                      mirror=mirror)
            self._slots[key] = s
        return s

    # ----------------------------------------------------------------- update
    def update(self, key: Tuple[int, int, int], rows: List[Tuple[int, np.ndarray]],
               n_active: int, trash_block: int) -> _Slot:
        """Scatter ``rows`` ([(row_index, packed_row)]) into the bucket's
        device buffers, neutralizing any previously-active row beyond
        ``n_active``.  Unchanged rows (mirror match) cost nothing."""
        s = self.slot(key, trash_block)
        n, t, b = key
        changed: List[np.ndarray] = []
        for i, packed in rows:
            if not np.array_equal(packed[1:], s.mirror[i, 1:]):
                changed.append(packed)
                s.mirror[i, 1:] = packed[1:]
        neutral = None
        for i in range(n_active, s.active_rows):
            if neutral is None:
                neutral = np.zeros(3 + t + b, np.int32)
                neutral[3 + t:] = trash_block
            if not np.array_equal(neutral[1:], s.mirror[i, 1:]):
                row = neutral.copy()
                row[0] = i
                changed.append(row)
                s.mirror[i, 1:] = row[1:]
        s.active_rows = n_active
        if changed:
            m = len(changed)
            m_pad = round_up_pow2(m)
            # pad with a repeat of the last row: duplicate scatter indices
            # carry identical values, so the write order cannot matter
            changed.extend([changed[-1]] * (m_pad - m))
            packed = np.stack(changed)
            sig = (key, m_pad)
            args = (s.tokens, s.n_tokens, s.start_pos, s.tables, self._device(packed))
            if sig not in self._scatter_shapes:
                self._scatter_shapes.add(sig)
                if self._ledger is not None:
                    # one name, an executable a shape: program_scopes() merges their tables
                    self._ledger.record("scatter", sig, name="_scatter_impl",
                                        program=compile_later(self._scatter, args))
                else:
                    self.counters.compiles += 1
            self.counters.uploads += 1
            self.counters.upload_ints += int(packed.size)
            self.counters.dispatches += 1
            s.tokens, s.n_tokens, s.start_pos, s.tables = self._scatter(*args)
        return s

    def feed(self, key: Tuple[int, int, int], toks_prev,
             pairs: List[Tuple[int, int]]) -> None:
        """Feed previous-step sampled tokens into this step's input slots
        entirely on device (``pairs``: (dst_row, src_row))."""
        if not pairs:
            return
        s = self._slots[key]
        m_pad = round_up_pow2(len(pairs))
        arr = np.empty((m_pad, 2), np.int32)
        arr[:len(pairs)] = pairs
        arr[len(pairs):] = pairs[-1]  # duplicate writes carry identical values
        sig = (key, int(toks_prev.shape[0]), m_pad)
        args = (s.tokens, toks_prev, self._device(arr))
        if sig not in self._feed_shapes:
            self._feed_shapes.add(sig)
            if self._ledger is not None:
                self._ledger.record("feed", sig, name="_feed_impl",
                                    program=compile_later(self._feed, args))
            else:
                self.counters.compiles += 1
        self.counters.uploads += 1
        self.counters.upload_ints += int(arr.size)
        self.counters.dispatches += 1
        s.tokens = self._feed(*args)

    def forget(self) -> None:
        """Drop every slot (tests / bucket-policy changes)."""
        self._slots.clear()
