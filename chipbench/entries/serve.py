"""Entry point ``serve``: waves of requests through
``InferenceEngineV2(...).generate(prompts, max_new_tokens=..., strict=False)``.

The warm-up serves the run's wave once (other tokens) and compiles every
bucket the window will meet; while it runs, the logits the engine computes at
the end of every request's prefill are read for the comparison with the plain
reference.
The window then serves the same lengths again and again with fresh tokens,
stops issuing waves once ``--seconds`` have passed, and counts what completed
over the time it really took.  A traced run measures one wave."""

import contextlib
import gc
import os
import time

import numpy as np

from chipbench import common
from chipbench.common import say

# spans the serve loop writes into the profiler's trace while a window is
# open (engine_v2._phase_annotation), and the benchmark's own around the call;
# a gap goes to the innermost of them that covers it (xplane.label_gaps)
HOST_ANNOTATIONS = ("admission_pump", "burst", "burst.prepare", "burst.wait", "burst.absorb",
                    "dispatch", "dispatch.wait", "scatter_upload", "absorb_patch", "flush",
                    "expire", "chipbench.generate")
REFERENCE_PAD = 1024  # reference sequences are padded to this multiple: few shapes to compile


class Recorder:
    """The engine's ``telemetry=`` argument: keeps each finished request's
    exact record (``RequestTrace.record()``) and opens the serve loop's phase
    annotations while the profiler runs.  The engine also calls, on whatever
    it is given, the six methods declined below."""

    enabled = True

    def __init__(self):
        self.records = []
        self.tracing = False

    def record_trace(self, record):
        self.records.append(record)

    def annotation(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _declined(self, *a, **k):
        return None

    record_gauges = record_resilience = rate = _declined
    serve_profile_begin = serve_profile_end = profile_serve_boundary = _declined


class LogitSpy:
    """Reads, from the engine's own forwards, the logits row that ends the
    prefill of each request of the wave.  A row is matched to its request by
    its start position and its tokens, which are the prompt's own.  The engine
    has no public hook for prefill logits, so its private ``_compiled_fwd`` is
    wrapped while the warm-up wave is served (PERF.md section 7 lists the hook
    for the program to add)."""

    def __init__(self, engine, prompts):
        self.engine, self.rows = engine, {}
        self.want = {i: np.asarray(p, np.int32) for i, p in enumerate(prompts)}
        self.compiled_fwd = engine._compiled_fwd  # inspection only: generate() serves

    def __enter__(self):
        self.engine._compiled_fwd = self._wrapped
        return self

    def __exit__(self, *exc):
        del self.engine._compiled_fwd
        self.engine = self.compiled_fwd = None  # a control run frees the engine before comparing

    def _wrapped(self, n, t, b):
        fwd = self.compiled_fwd(n, t, b)
        if len(self.rows) == len(self.want):
            return fwd

        def call(params, kv, tokens, n_tokens, start_pos, tables):
            logits, kv = fwd(params, kv, tokens, n_tokens, start_pos, tables)
            counts, starts = np.asarray(n_tokens), np.asarray(start_pos)
            for i, prompt in self.want.items():
                if i in self.rows:
                    continue
                for row in np.nonzero((counts > 0) & (starts + counts == len(prompt)))[0]:
                    chunk = np.asarray(tokens[row, :counts[row]])
                    if np.array_equal(chunk, prompt[starts[row]:]):
                        self.rows[i] = np.asarray(logits[row, counts[row] - 1], np.float32)
            return logits, kv

        return call


def scale_for_rehearsal(params: dict, scale: dict) -> dict:
    spec = dict(params["prompt_lengths"])
    for key in ("median", "min", "max"):
        if key in spec:
            spec[key] = max(4, spec[key] // scale["length_divisor"])
    return {"requests_per_wave": max(4, params["requests_per_wave"] // scale["requests_divisor"]),
            "prompt_lengths": spec,
            "max_new_tokens": max(4, params["max_new_tokens"] // scale["new_tokens_divisor"]),
            "order_seed": params["order_seed"]}


def compare_with_reference(ref, sizes, params, results, spy_rows, limits):
    """The numbers ``correct`` rests on, each beside its limit."""
    got, want, gaps = [], [], []
    for i, row in sorted(spy_rows.items()):
        ids = list(results[i].tokens)
        n_new = len(ids) - results[i].prompt_len
        first = results[i].prompt_len - 1
        padded = ids + [0] * (-len(ids) % REFERENCE_PAD)
        logits = np.asarray(ref.logits_rows(sizes, params, padded,
                                            list(range(first, first + n_new))))
        picked = np.asarray(ids[first + 1:first + 1 + n_new])
        gaps.append(logits.max(axis=-1) - logits[np.arange(n_new), picked])
        got.append(row)
        want.append(logits[0])
    got, want, gaps = np.stack(got), np.stack(want), np.concatenate(gaps)
    rel_rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    # the largest gap is an extreme of thousands of tokens and grows with their
    # number; the share of tokens that are not the reference's best is steady
    not_best = float(np.mean(gaps > 0))
    say("correct", compared="engine logits at the end of prefill vs float32 reference",
        rows=len(got), logit_rel_rms=f"{rel_rms:.6f}", limit=limits["logit_rel_rms_limit"],
        max_abs_err=f"{float(np.abs(got - want).max()):.4f}")
    say("correct", compared="generated tokens that are not the reference's best at their place",
        tokens=len(gaps), picked_not_best_share=f"{not_best:.6f}",
        limit=limits["picked_not_best_share_limit"], largest_logit_gap=f"{float(gaps.max()):.4f}")
    return (np.isfinite(got).all() and rel_rms <= limits["logit_rel_rms_limit"]
            and not_best <= limits["picked_not_best_share_limit"])


def run(ctx):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    config, sizes, args = ctx.config, ctx.sizes, ctx.args
    ref = common.load_module("references", config["reference"])
    module, model_cfg = common.program_model(config, sizes)

    t = time.perf_counter()
    draw = jax.jit(lambda key: ref.init_params(sizes, key, jnp.bfloat16))
    key = jax.random.PRNGKey(args.seed)
    served = params = jax.block_until_ready(draw(key))
    if args.control:
        # the chip holds one copy of the weights: the engine's are rounded in
        # place of the drawn ones, which are drawn again once the engine is gone
        control = config["correct"]["control"]
        say("control", name=control["name"], what=control["what"])
        params = None
        served = jax.block_until_ready(jax.jit(
            lambda p: ref.round_weights_to(p, control["format"]), donate_argnums=0)(served))
    say("serve", params=f"{common.count_params(served) / 1e9:.3f}B", layers=sizes["num_hidden_layers"],
        weights_s=f"{time.perf_counter() - t:.1f}")

    engine_args = dict(config["engine"])
    if args.rehearse:
        engine_args.update(config["rehearsal"].get("engine", {}))
    recorder = Recorder()
    engine = InferenceEngineV2(module, model_cfg, served, config=engine_args.pop("config"),
                               telemetry=recorder, **engine_args)
    free_at_start = engine.manager.allocator.free_blocks
    # the KV pool as the program holds it, for the readers that look for it in the trace
    pool_shapes = sorted({tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(engine.kv)})

    traffic_params = ctx.traffic["params"]
    if args.rehearse:
        traffic_params = scale_for_rehearsal(traffic_params, config["rehearsal"]["traffic_scale"])
    traffic = common.load_module("generators", ctx.traffic["generator"]).Traffic(
        traffic_params, args.seed, sizes["vocab_size"])
    new_tokens = traffic.max_new_tokens
    lengths = traffic.lengths
    say("serve", requests_per_wave=len(lengths), prompt_tokens_per_wave=sum(lengths),
        shortest=min(lengths), longest=max(lengths), max_new_tokens=new_tokens,
        kv_blocks=engine_args["num_blocks"], kv_block_tokens=engine_args["block_size"])

    def serve(prompts):
        with recorder.annotation("chipbench.generate") if recorder.tracing \
                else contextlib.nullcontext():
            results = engine.generate(prompts, max_new_tokens=new_tokens, strict=False)
        for r, p in zip(results, prompts):
            r.prompt_len = len(p)
        return results

    # ---- warm-up: the run's wave once, with every request's prefill logits read
    t = time.perf_counter()
    prompts0 = traffic.wave(0)
    with LogitSpy(engine, prompts0) as spy:
        warm = serve(prompts0)
    jax.block_until_ready(engine.kv)
    say("serve", warmup_s=f"{time.perf_counter() - t:.1f}", compiles=engine.ledger.total,
        compile_s=f"{engine.ledger.compile_wall_s:.1f}", prefill_rows_read=len(spy.rows))

    # ---- the window
    recorder.records.clear()
    counters0, compiles0 = engine.counters.snapshot(), engine.ledger.total
    forwards0, stepwise0 = engine._kv_steps, engine.scheduler.steps
    trace_dir = os.path.join(common.OUT, "trace")
    window = common.profiler_window(trace_dir) if args.trace else contextlib.nullcontext()
    waves = []
    with window:  # the profiler starts before the window does
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        recorder.tracing = bool(args.trace)
        while True:
            waves.append(serve(traffic.wave(len(waves) + 1)))
            if args.trace or time.perf_counter() - t0 >= args.seconds:
                break
        jax.block_until_ready(engine.kv)
        window_s = time.perf_counter() - t0
        recorder.tracing = False
    peak = common.memory_peak_bytes(ctx.devices)

    results = [r for wave in waves for r in wave]
    records = list(recorder.records)
    ok = [r for r in results if r.status == "ok"]
    run = common.new_run(
        kind="serve", setup_s=setup_s, window_s=window_s, waves=len(waves), results=results,
        records=records, sizes=sizes, lengths=lengths, max_new_tokens=new_tokens,
        generated_ok=sum(len(r.tokens) - r.prompt_len for r in ok),
        prompt_tokens=sum(r.prompt_len for r in results),
        counters=engine.counters.delta_since(counters0),
        compiles_in_window=engine.ledger.total - compiles0,
        forwards=engine._kv_steps - forwards0,
        stepwise_forwards=engine.scheduler.steps - stepwise0, peaks=ctx.peaks, chips=1,
        pool_shapes=pool_shapes,
        memory_peak_bytes=peak, attempted=len(results), failed=len(results) - len(ok),
        trace=None)
    say("window", seconds=f"{window_s:.3f}", waves=len(waves), requests=len(results),
        ok=len(ok), records=len(records), generated_tokens=run.generated_ok,
        compiles_in_window=run.compiles_in_window, counters=run.counters)
    if args.trace:
        run.trace = common.reduce_trace(trace_dir, HOST_ANNOTATIONS, args.rehearse)

    # ---- correct, outside the window
    whole = all(len(r.tokens) == r.prompt_len + new_tokens
                and all(0 <= tok < sizes["vocab_size"] for tok in r.tokens)
                for r in results + warm if r.status == "ok")
    engine.check_kv_invariant()
    reclaimed = engine.manager.allocator.free_blocks == free_at_start
    say("correct", all_ok=run.failed == 0, full_length_and_in_vocabulary=whole,
        kv_invariant="clean", pool_reclaimed=reclaimed, records_match=len(records) == len(results))
    if params is None:
        del engine, served
        gc.collect()
        params = draw(key)
    agrees = len(spy.rows) == len(spy.want) and compare_with_reference(
        ref, sizes, params, warm, spy.rows, common.correct_limits(config, args.rehearse))
    run.correct = bool(run.failed == 0 and whole and reclaimed and agrees
                       and len(records) == len(results))
    return run
