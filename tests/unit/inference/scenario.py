"""The mixed-arrival serving scenario that the fast-path tests drive an engine
through: a test driver over ``put`` / ``step`` / ``decode_burst`` / ``flush``;
and a recorder of the forward programs an engine launches."""

import time


def launches_of(eng, monkeypatch):
    """``[(token slots, passes)]`` of every forward program ``eng`` launches from here on."""
    launched, count = [], eng.counters.count_slots

    def counted(n, t, b, live_tokens, live_blocks, passes=1, flat=None, **kw):
        launched.append((n * t if flat is None else flat, passes))
        return count(n, t, b, live_tokens, live_blocks, passes=passes, flat=flat, **kw)
    monkeypatch.setattr(eng.counters, "count_slots", counted)
    return launched


def run_scenario(eng, prompts, arrivals, max_new: int):
    """Drive the v2 engine through a continuous-batching scenario: requests
    arrive (``arrivals``: {step_idx: [uids]}) WHILE earlier ones decode, so
    SplitFuse actually mixes prefill chunks and decode singles in one ragged
    batch.  Steers the engine the way its own serve loop does (ISSUE 5):
    once the live set is decode-only, up to ``k`` steps fuse into ONE
    compiled burst — capped so arrivals still land on their scheduled step
    index — and mixed steps run through the device-resident step() path.
    Returns (total_new_tokens, elapsed_s, per-decode-step latencies (a burst
    of k contributes k samples of dt/k), hit_stall_bail, host-link deltas)."""
    produced = {u: 0 for u in range(len(prompts))}
    done = set()
    pending = dict(arrivals)
    lats = []
    tokens = 0
    step_i = 0
    stalled = 0
    link0 = eng.counters.snapshot()
    t_start = time.perf_counter()
    while len(done) < len(prompts):
        if step_i in pending:
            uids = pending.pop(step_i)
            eng.put(uids, [prompts[u] for u in uids])

        def _retire(uid, n_new):
            nonlocal tokens
            tokens += n_new
            produced[uid] += n_new
            if produced[uid] >= max_new:
                eng.manager.seqs[uid].done = True
                done.add(uid)
                eng.flush(uid)

        # adaptive decode fusion between arrival boundaries
        live = [u for u, s in eng.manager.seqs.items() if not s.done]
        k = min((max_new - produced[u] for u in live), default=0)
        next_arrival = min(pending, default=None)
        if next_arrival is not None:
            k = min(k, next_arrival - step_i)
        if k >= 2:
            t0 = time.perf_counter()
            burst = eng.decode_burst(k)
            dt = time.perf_counter() - t0
            if burst is not None:
                lats.extend([dt / k] * k)
                stalled = 0
                for uid, toks in burst.items():
                    _retire(uid, len(toks))
                step_i += k
                continue

        t0 = time.perf_counter()
        out = eng.step()  # host-synchronous: tokens are materialized ints
        dt = time.perf_counter() - t0
        if out:
            lats.append(dt)
            stalled = 0
        elif not pending and not any(s.pending_tokens > 0 and not s.done
                                     for s in eng.manager.seqs.values()):
            break
        else:
            # prefill chunks make progress without emitting; a long run of
            # empty steps means the scheduler is starved (KV pool exhausted)
            # — bail instead of spinning the global budget away
            stalled += 1
            if stalled > 100:
                break
        for uid in out:
            _retire(uid, 1)
        step_i += 1
    link = eng.counters.delta_since(link0)
    return tokens, time.perf_counter() - t_start, lats, stalled > 100, link
