"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                # one chip: serve phase, then train phase
    python chip_smoke.py --four-chips   # four chips: sharded train and TP serving,
                                        # each against its one-device twin, nothing else
    python chip_smoke.py --rehearse     # CPU, tiny widths, kernels interpreted;
                                        # checks the control flow and exits 3

One process, the normal entry points (``InferenceEngineV2.generate``,
``deepspeed_tpu.initialize`` + ``train_batch``), one model at its published
widths: ``MistralConfig.mistral_7b()`` in bf16, weights random from ``--seed``,
depth cut to what one 16 GB chip holds.  No phase is wrapped in try/except: a
phase that raises ends the run with a traceback and a non-zero code.  Only a
run on a TPU, with compiled (not interpreted) kernels, reaches the last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The numbers printed on the way are bring-up readings (does it run, does it
fit, how long does it compile), not benchmark results.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
GiB = float(1 << 30)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one v5e chip (15.75 GiB usable) holds, read off
    ``compiled.memory_analysis()`` of the whole step programs compiled for a
    described v5e before the first chip run (CHANGES.md, PR 21)."""
    # serve: 16 of 32 layers = 6.99 GiB of bf16 weights.  The paged forward
    # scans the KV pool as xs/ys, and the compiler keeps the pool twice while a
    # step runs (temp = pool + ~0.9 GiB), so the pool gets half of what is left.
    serve_layers: int = 16
    kv_block: int = 128
    max_blocks_per_seq: int = 40  # 5120 tokens: the longest request and its answer
    serve_reserve_gib: float = 3.0  # step temporaries + the float32 reference
    n_requests: int = 24
    long_prompt: int = 4200  # past the 4096 window
    max_new_tokens: int = 16
    # train: 2 layers + embeddings = 698M parameters x 12 bytes of fp32 master
    # and moments = 7.80 GiB, + 4.69 GiB of step temporaries at micro 2.
    train_layers: int = 2
    train_micro: int = 2
    train_seq: int = 2048
    train_steps: int = 6
    # four chips: TP serving at 8 layers; training at a global batch of 4
    tp_layers: int = 8
    tp_blocks: int = 64
    # the CPU rehearsal: tiny widths, interpreted kernels, a fixed pool (the
    # CPU reports no memory), no kernel to find in a compiled program
    rehearsal: bool = False
    rehearsal_blocks: int = 160


FULL = Sizes()
TINY = dataclasses.replace(FULL, rehearsal=True, serve_layers=2, train_micro=1, train_seq=256,
                           train_steps=4, tp_layers=2)

# Logits of the bf16 engine against the float32 reference on the same weights.
# bf16 keeps 8 bits of mantissa (relative step 2^-8 = 0.4%); the residual
# stream is rounded to it after every projection and add, while every matmul
# accumulates in float32, so the roundings partly average out instead of
# adding up over the 16 layers.  The first chip run (PR 21) read a relative RMS
# error of 0.005-0.006 and a largest error of 0.032 over 32000 unit-variance
# logits, the same at prompt 93, 1100 and 4200.  The bounds are ~3x that: wide
# enough for another seed, far below the O(1) of a wrong mask, a wrong block
# or a dropped window.
LOGIT_REL_RMS_TOL = 0.02
LOGIT_MAX_ABS_TOL = 0.1
# Two bf16 programs of the same math (four devices against one).  The sharded
# one rounds each device's partial sum to bf16 before the all-reduce, twice a
# layer, so it sits further from its twin than either sits from float32: the
# four-chip run (PR 21) read 0.011-0.013 relative RMS and 0.063 at most, with
# every picked token equal.  Bounds ~2.5x that.
TP_LOGIT_REL_RMS_TOL = 0.03
TP_LOGIT_MAX_ABS_TOL = 0.15
# Losses of the first steps only: on one fixed batch the loss falls three
# orders of magnitude in six steps, and what is left of it then is set by the
# order of the sums.  The four-chip run (PR 21) read a gap of 8e-5.
SHARDED_LOSS_STEPS = 3
SHARDED_LOSS_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(ok, what) -> None:
    """Not ``assert``: the checks must also hold under ``python -O``."""
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def peak_gib(device) -> str:
    stats = device.memory_stats()
    return f"{stats['peak_bytes_in_use'] / GiB:.2f}GiB" if stats else "unreported"


def mistral_config(sz: Sizes, layers: int):
    from deepspeed_tpu.models import mistral
    if sz.rehearsal:  # tiny widths for the CPU rehearsal only; same window
        return mistral.MistralConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                                     num_layers=layers, num_heads=8, num_kv_heads=4,
                                     max_seq_len=8192, sliding_window=4096)
    return dataclasses.replace(mistral.MistralConfig.mistral_7b(), num_layers=layers)


def make_prompts(sz: Sizes, vocab: int, seed: int):
    """Mixed lengths from the seed: one past the window, two longer than one
    SplitFuse chunk (256 tokens), the rest short with a heavy tail."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lengths = [sz.long_prompt, 1100, 700]
    lengths += [int(x) for x in np.clip(rng.lognormal(4.5, 0.8, sz.n_requests - 3), 8, 600)]
    order = rng.permutation(len(lengths))
    return [rng.integers(0, vocab, lengths[i]).tolist() for i in order]


def init_bf16_params(cfg, seed: int):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import mistral
    params = jax.jit(lambda k: mistral.init_params(cfg, k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def build_serving_engine(cfg, params, sz: Sizes, num_blocks: int, topology=None):
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import mistral
    return InferenceEngineV2(mistral, cfg, params, config={"dtype": "bfloat16"},
                             num_blocks=num_blocks, block_size=sz.kv_block,
                             max_blocks_per_seq=sz.max_blocks_per_seq, topology=topology)


def serve_step_kernels(engine, sz: Sizes):
    """Kernel calls of every compiled ragged-forward program the engine holds,
    by bucket ``(n_seqs, chunk, table_width)``; each must hold the paged kernel
    and the KV writer (no dense gather and no scatter standing in)."""
    from deepspeed_tpu.ops._pallas import kernel_calls
    calls = {k: kernel_calls(v.as_text()) for k, v in engine._fwd_cache.items()
             if hasattr(v, "as_text")}
    if not sz.rehearsal:
        require(calls and all(c.get("paged_attention", 0) > 0 and c.get("kv_write", 0) > 0
                              for c in calls.values()),
                f"a serve step compiled without the paged kernel or the KV writer: {calls}")
    return calls


def engine_logits(engine, uids, prompts, n_decode: int):
    """Prefill-then-decode through the engine's own step loop, reading the
    logits each ragged forward produced: {uid: [n_decode + 1 rows of [V]]} and
    the tokens it picked.  Rows are matched to requests by their absolute
    position, so the prompts must differ in length by more than ``n_decode``."""
    import numpy as np
    seen = []
    compiled_fwd = engine._compiled_fwd  # inspection only: generate() is what serves

    def spy(n, t, b):
        fwd = compiled_fwd(n, t, b)

        def call(params, kv, tokens, n_tokens, start_pos, tables):
            logits, kv = fwd(params, kv, tokens, n_tokens, start_pos, tables)
            # a step's forward returns each row's last live logits alone: [n, 1, V]
            seen.append((np.asarray(logits[:, 0], np.float32), np.asarray(start_pos + n_tokens),
                         np.asarray(n_tokens)))
            return logits, kv

        return call

    engine._compiled_fwd = spy
    engine.put(uids, prompts)
    picked = {u: [] for u in uids}
    while min(len(v) for v in picked.values()) < n_decode + 1:
        for uid, tok in engine.step().items():
            picked[uid].append(int(tok))
    for uid in uids:
        engine.flush(uid)
    del engine._compiled_fwd
    rows = {u: {} for u in uids}
    for logits, end, n_tok in seen:
        for i in np.nonzero(n_tok)[0]:
            for uid, prompt in zip(uids, prompts):
                j = int(end[i]) - len(prompt)
                if 0 <= j <= n_decode:
                    rows[uid][j] = logits[i]
    return ({u: [rows[u][j] for j in range(n_decode + 1)] for u in uids},
            {u: picked[u][:n_decode + 1] for u in uids})


def reference_logits(cfg, params, prompt, picked):
    """The model's plain dense forward in float32 on the same weights: dense
    window mask into ``sdpa``, no kernel, no cache, ``highest`` precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import mistral
    plain = dataclasses.replace(cfg, remat=False)
    # a float32 embedding makes the residual stream float32; every weight is
    # then cast up layer by layer inside the scan (``w.astype(x.dtype)``)
    ref_params = {**params, "embed": params["embed"].astype(jnp.float32)}
    ids = jnp.asarray([list(prompt) + list(picked[:-1])], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, x: mistral.forward(
            plain, p, x,
            attention_fn=mistral.dense_windowed_attention(cfg.sliding_window)))(ref_params, ids)
    first = len(prompt) - 1
    return np.asarray(logits[0, first:first + len(picked)], np.float32)


def compare_logits(tag, got, want, rel_tol, abs_tol):
    import numpy as np
    got, want = np.stack(got), np.asarray(want)
    require(got.shape == want.shape and np.isfinite(got).all(), (got.shape, want.shape))
    rel = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    worst = float(np.max(np.abs(got - want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    say(tag, rows=got.shape[0], rel_rms_err=f"{rel:.4f}", max_abs_err=f"{worst:.4f}",
        argmax_agree=f"{agree:.2f}", tol=f"{rel_tol}/{abs_tol}")
    require(rel <= rel_tol and worst <= abs_tol, f"{tag}: logits disagree with the reference")


def cache_events() -> dict:
    """What the persistent compile cache has answered this process so far
    (the set-up account's count, monitor/compile_events.py)."""
    from deepspeed_tpu.monitor import compile_events
    totals = compile_events.ACCOUNT.totals()
    return {"cache_hits": totals["cache_hits"], "cache_misses": totals["cache_misses"]}


# ------------------------------------------------------------------- one chip
def serve_phase(sz: Sizes, seed: int) -> None:
    import jax
    from deepspeed_tpu.models import mistral
    dev = jax.devices()[0]
    cfg = mistral_config(sz, sz.serve_layers)
    t0 = time.perf_counter()
    params = init_bf16_params(cfg, seed)
    block_bytes = (2 * cfg.num_layers * cfg.num_kv_heads * sz.kv_block
                   * (cfg.hidden_size // cfg.num_heads) * 2)
    if sz.rehearsal:
        num_blocks = sz.rehearsal_blocks
    else:
        stats = dev.memory_stats()
        left = stats["bytes_limit"] - stats["bytes_in_use"] - sz.serve_reserve_gib * GiB
        num_blocks = int(left / 2 // block_bytes)
    say("serve", layers=cfg.num_layers, params=f"{mistral.num_params(cfg) / 1e9:.3f}B",
        hidden=cfg.hidden_size, ffn=cfg.intermediate_size, heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads, window=cfg.sliding_window,
        init_s=f"{time.perf_counter() - t0:.1f}", kv_blocks=num_blocks,
        kv_block_tokens=sz.kv_block, kv_pool=f"{num_blocks * block_bytes / GiB:.2f}GiB")
    engine = build_serving_engine(cfg, params, sz, num_blocks)
    free_at_start = engine.manager.allocator.free_blocks
    prompts = make_prompts(sz, cfg.vocab_size, seed)
    lengths = sorted(len(p) for p in prompts)
    say("serve", requests=len(prompts), prompt_tokens=sum(lengths), shortest=lengths[0],
        longest=lengths[-1], max_new_tokens=sz.max_new_tokens)

    for label in ("cold", "steady"):
        before = engine.ledger.total
        t0 = time.perf_counter()
        out = engine.generate(prompts, max_new_tokens=sz.max_new_tokens)
        jax.block_until_ready(engine.kv)
        wall = time.perf_counter() - t0
        require([len(o) for o in out] == [len(p) + sz.max_new_tokens for p in prompts],
                "a request came back short or long")
        require(all(0 <= t < cfg.vocab_size for o in out for t in o),
                "a token outside the vocabulary")
        require(engine.manager.allocator.free_blocks == free_at_start, "pool not reclaimed")
        say("serve", run=label, wall_s=f"{wall:.2f}", per_request_s=f"{wall / len(prompts):.3f}",
            compiles=engine.ledger.total - before, peak=peak_gib(dev))
    require(engine.ledger.total == before, "the steady run compiled")
    # compile_s is the ahead-of-time seam's stopwatch (the fwd programs alone);
    # the stage seconds beside it are JAX's own, over every program the ledger recorded
    say("serve", compile_counter=engine.ledger.total, warm_recompiles=engine.ledger.warm_total,
        compile_s=f"{engine.ledger.compile_wall_s:.1f}",
        **{k: f"{v:.1f}" for k, v in engine.ledger.stage_totals().items() if k.endswith("_s")},
        **cache_events())

    calls = serve_step_kernels(engine, sz)
    say("serve", programs=len(calls), decode_programs=sum(t == 1 for _, t, _ in calls),
        prefill_programs=sum(t > 1 for _, t, _ in calls),
        paged_attention_calls_each=sorted({c.get("paged_attention", 0) for c in calls.values()}))

    # outside any timing: logits against the float32 reference, three requests
    # at once (a short one, one of several chunks, the one past the window)
    check = sorted(prompts, key=len)
    check = [check[len(check) // 2], check[-2], check[-1]]
    uids = [1000, 1001, 1002]
    got, picked = engine_logits(engine, uids, check, n_decode=3)
    engine.check_kv_invariant()
    require(engine.manager.allocator.free_blocks == free_at_start, "pool not reclaimed")
    del engine
    gc.collect()
    for uid, prompt in zip(uids, check):
        compare_logits(f"serve logits prompt={len(prompt)}", got[uid],
                       reference_logits(cfg, params, prompt, picked[uid]),
                       LOGIT_REL_RMS_TOL, LOGIT_MAX_ABS_TOL)
    say("serve", peak=peak_gib(dev), kv_invariant="clean", pool="reclaimed")


def train_run(cfg, sz: Sizes, seed: int, topology, micro: int, tag: str, inspect: bool):
    """A few ZeRO-3 steps on a fixed seeded batch; returns the engine, the
    losses, and (``inspect``) the kernel calls of the compiled step."""
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import mistral
    from deepspeed_tpu.ops._pallas import kernel_calls
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=mistral.make_loss_fn(cfg),
        model_parameters=mistral.abstract_params(cfg),
        param_init_fn=lambda: mistral.init_params(cfg, jax.random.PRNGKey(seed)),
        topology=topology,
        config={"train_micro_batch_size_per_gpu": micro,
                "bf16": {"enabled": True},
                "optimizer": {"type": "fused_adam", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 3},
                "gradient_clipping": 1.0,
                "steps_per_print": 1000})
    jax.block_until_ready(engine.state)
    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                               (engine.train_batch_size, sz.train_seq))
    batch = mistral.causal_lm_batch(ids)
    say(tag, layers=cfg.num_layers, params=f"{mistral.num_params(cfg) / 1e9:.3f}B",
        zero_stage=3, mesh=dict(engine.topology.mesh.shape), batch=engine.train_batch_size,
        micro=micro, seq=sz.train_seq, init_s=f"{time.perf_counter() - t0:.1f}")
    if inspect:
        # the step program, compiled ahead of the first step so that it can be
        # read; train_batch's own jit then finds it in the compilation cache
        placed = engine._shard_batch(engine._ensure_gas_layout(batch))
        t0 = time.perf_counter()
        compiled = engine.train_step_fn.lower(engine.state, placed).compile()
        calls = kernel_calls(compiled.as_text())
        say(tag, compile_s=f"{time.perf_counter() - t0:.1f}", kernel_calls=calls,
            tpu_custom_calls=sum(calls.values()))
        del compiled
    else:
        calls = None
    losses, times = [], []
    for _ in range(sz.train_steps):
        t0 = time.perf_counter()
        metrics = engine.train_batch(batch)
        losses.append(float(jax.block_until_ready(metrics.loss)))
        times.append(time.perf_counter() - t0)
    say(tag, step1_s=f"{times[0]:.2f}", later_steps_s=[round(t, 3) for t in times[1:]],
        losses=[round(x, 4) for x in losses], peak=peak_gib(jax.devices()[0]))
    require(all(np.isfinite(losses)), f"{tag}: loss not finite")
    require(losses[-1] < losses[0], f"{tag}: loss did not fall")
    return engine, losses, calls


def train_phase(sz: Sizes, seed: int) -> None:
    import jax
    from deepspeed_tpu.parallel import MeshTopology
    cfg = dataclasses.replace(mistral_config(sz, sz.train_layers), max_seq_len=sz.train_seq)
    topology = MeshTopology.from_axis_dict({}, devices=jax.devices()[:1])
    _, _, calls = train_run(cfg, sz, seed, topology, sz.train_micro, "train", inspect=True)
    say("train", **cache_events())
    if not sz.rehearsal:
        flash = sum(v for k, v in calls.items() if k.startswith("flash_attention"))
        require(flash >= 3, f"train step compiled without the flash kernels: {calls}")
        require(calls.get("fused_adamw_kernel", 0) > 0,
                f"train step compiled without the fused optimizer: {calls}")


# ----------------------------------------------------------------- four chips
def four_chip_train(sz: Sizes, seed: int) -> None:
    """(a) the train phase on {"fsdp": 4} against a one-device mesh."""
    import jax
    import numpy as np
    from deepspeed_tpu.parallel import MeshTopology
    cfg = dataclasses.replace(mistral_config(sz, sz.train_layers), max_seq_len=sz.train_seq)
    devices = jax.devices()
    # One engine at a time: the process's topology is the last engine's.  The
    # twin goes first, while the first chip is empty: four sequences at once
    # take 14.67 of its 15.75 GiB (accumulating them in two or four
    # micro-steps would take more, 16 GiB, for the float32 gradient carry).
    engine, single, _ = train_run(
        cfg, sz, seed, MeshTopology.from_axis_dict({}, devices=devices[:1]),
        4, "4chip train one-device twin", inspect=False)
    del engine
    gc.collect()
    engine, sharded, calls = train_run(
        cfg, sz, seed, MeshTopology.from_axis_dict({"fsdp": 4}, devices=devices),
        1, "4chip train fsdp=4", inspect=True)
    if not sz.rehearsal:
        require(sum(v for k, v in calls.items() if k.startswith("flash_attention")) >= 3, calls)
    leaves = [x for x in jax.tree_util.tree_leaves((engine.state.params, engine.state.opt_state))
              if x.ndim > 0]
    for leaf in leaves:
        shards = leaf.addressable_shards
        require(len(shards) == 4 and len({s.device for s in shards}) == 4, leaf.sharding)
        require(all(s.data.size * 4 == leaf.size for s in shards), (leaf.shape, leaf.sharding))
    say("4chip train fsdp=4", sharded_leaves=len(leaves), shards_each=4, shard_share="1/4",
        per_device=[f"{d.memory_stats()['bytes_in_use'] / GiB:.2f}GiB" for d in devices]
        if devices[0].memory_stats() else "unreported")
    del engine
    gc.collect()
    a, b = (np.array(x[:SHARDED_LOSS_STEPS]) for x in (sharded, single))
    gap = float(np.max(np.abs(a - b) / np.abs(b)))
    say("4chip train", steps_compared=SHARDED_LOSS_STEPS, max_rel_loss_gap=f"{gap:.5f}",
        rtol=SHARDED_LOSS_RTOL)
    require(gap <= SHARDED_LOSS_RTOL, "sharded and one-device losses disagree")


def four_chip_serve(sz: Sizes, seed: int) -> None:
    """(b) InferenceEngineV2 with tensor=4 against the one-device engine."""
    import jax
    from deepspeed_tpu.parallel import MeshTopology
    cfg = mistral_config(sz, sz.tp_layers)
    params = init_bf16_params(cfg, seed)
    prompts = sorted(make_prompts(sz, cfg.vocab_size, seed), key=len)
    check = [prompts[len(prompts) // 2], prompts[-3], prompts[-2]]
    uids = [1000, 1001, 1002]
    topo = MeshTopology.from_axis_dict({"tensor": 4}, devices=jax.devices())
    engine = build_serving_engine(cfg, params, sz, sz.tp_blocks, topology=topo)
    for name, pool in engine.kv.items():
        shards = pool.addressable_shards
        require(len(shards) == 4 and len({s.device for s in shards}) == 4, pool.sharding)
        require(all(s.data.shape[2] * 4 == pool.shape[2] for s in shards),
                f"KV pool {name} is not sharded over heads: {pool.sharding}")
    out = engine.generate(check, max_new_tokens=8)
    require([len(o) for o in out] == [len(p) + 8 for p in check],
            "a request came back short or long")
    tp_logits, tp_picked = engine_logits(engine, uids, check, n_decode=3)
    engine.check_kv_invariant()
    calls = serve_step_kernels(engine, sz)
    say("4chip serve tensor=4", layers=cfg.num_layers, kv_pool_spec=engine.kv["k"].sharding.spec,
        kv_heads_per_device=engine.kv["k"].addressable_shards[0].data.shape[2],
        programs=len(calls), compiles=engine.ledger.total,
        paged_attention_calls_each=sorted({c.get("paged_attention", 0) for c in calls.values()}))
    del engine
    gc.collect()
    engine = build_serving_engine(cfg, params, sz, sz.tp_blocks)
    one_logits, one_picked = engine_logits(engine, uids, check, n_decode=3)
    del engine
    gc.collect()
    for uid, prompt in zip(uids, check):
        compare_logits(f"4chip serve logits tp4-vs-1 prompt={len(prompt)}", tp_logits[uid],
                       one_logits[uid], TP_LOGIT_REL_RMS_TOL, TP_LOGIT_MAX_ABS_TOL)
        same = sum(a == b for a, b in zip(tp_picked[uid], one_picked[uid]))
        say("4chip serve", prompt=len(prompt), tokens_equal=f"{same}/{len(one_picked[uid])}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths and what they are compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny widths with interpreted kernels; never ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from deepspeed_tpu.monitor import compile_events
    from deepspeed_tpu.ops import _pallas
    from deepspeed_tpu.utils.compile_cache import place_compile_cache
    if args.rehearse:
        _pallas.INTERPRET = True
    else:
        # an interpreted kernel is not the chip's kernel; nothing placed the
        # cache for a rehearsal either, whose programs no chip can load
        place_compile_cache(HERE)
    compile_events.install()  # before the first program: the weights' draw counts too

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say("device", **device, jax=jax.__version__,
        compile_cache=jax.config.jax_compilation_cache_dir)
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {device['platform']!r})", file=sys.stderr)
        return 2
    if args.four_chips and device["count"] != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {device['count']} (a rehearsal "
              f"gets them from XLA_FLAGS=--xla_force_host_platform_device_count=4)",
              file=sys.stderr)
        return 2

    sz = TINY if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_train(sz, args.seed)
        four_chip_serve(sz, args.seed)
    else:
        serve_phase(sz, args.seed)
        gc.collect()
        train_phase(sz, args.seed)
    say("done", wall_s=f"{time.perf_counter() - t0:.0f}")

    if args.rehearse or _pallas.INTERPRET or device["platform"] != "tpu":
        print("chip_smoke: rehearsal passed; kernels were interpreted off the chip, "
              "so this is not a chip run", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
