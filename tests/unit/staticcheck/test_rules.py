"""Per-rule fixture tests: every dslint rule has positive (must flag) and
negative (must NOT flag) snippets, exercised through the same lint_modules
pipeline the CLI uses."""

import textwrap

import pytest

from deepspeed_tpu.tools.staticcheck import lint_source


def run(src, rules=None, **kw):
    return lint_source(textwrap.dedent(src), rule_names=rules, **kw)


def rules_of(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------ host-sync
class TestHostSyncInHotPath:
    RULE = ["host-sync-in-hot-path"]

    def test_flags_float_in_train_batch(self):
        out = run("""
            class Engine:
                def train_batch(self, batch):
                    metrics = self.step_fn(batch)
                    return float(metrics.loss)
            """, self.RULE)
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert out[0].line == 5

    @pytest.mark.parametrize("call", ["x.item()", "np.asarray(x)", "np.array(x)",
                                      "jax.device_get(x)", "x.block_until_ready()"])
    def test_flags_each_sync_form(self, call):
        out = run(f"""
            class Engine:
                def eval_batch(self, x):
                    return {call}
            """, self.RULE)
        assert rules_of(out) == ["host-sync-in-hot-path"]

    def test_ignores_same_calls_outside_hot_path(self):
        out = run("""
            class Engine:
                def save_checkpoint(self, x):
                    return float(x) + np.asarray(x).sum()
            """, self.RULE)
        assert out == []

    def test_step_hot_only_on_engine_classes(self):
        out = run("""
            class InferenceEngineV2:
                def step(self, x):
                    return float(x)

            class BlockAllocator:
                def step(self, x):
                    return float(x)
            """, self.RULE)
        assert len(out) == 1 and out[0].line == 4

    def test_ignores_float_of_literal_and_jitted_nested_step(self):
        out = run("""
            import jax

            class Engine:
                def train_batch(self, batch):
                    def train_step(state, b):
                        return state, float(1e-3)
                    self._fn = jax.jit(train_step)
                    lr = float(1.0)
                    return self._fn(self.state, batch)
            """, self.RULE)
        assert out == []

    # ---- inference/v2 package-wide scan (serving fastpath satellite):
    # direct step-result fetches outside the sanctioned materialize() helper
    def test_v2_flags_direct_asarray_outside_helper(self):
        out = run("""
            import numpy as np

            def collect(dev):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/inference/v2/util.py")
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "materialize" in out[0].message

    def test_v2_sanctioned_materialize_is_clean(self):
        out = run("""
            import numpy as np

            def materialize(dev, counters=None):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/inference/v2/fastpath.py")
        assert out == []

    def test_v2_scan_skips_host_scalars(self):
        # float()/len() gauge math is not a device fetch — the package-wide
        # scan only matches explicit array fetches
        out = run("""
            def gauges(manager):
                return float(len(manager.seqs))
            """, self.RULE, filename="deepspeed_tpu/inference/v2/engine_v2.py")
        assert out == []

    # ---- runtime/heartbeat.py whole-file scan (elastic fault tolerance):
    # liveness stamps are contractually zero-device-sync, so ANY explicit
    # fetch anywhere in the file is a finding — hot-path names or not
    def test_heartbeat_file_flags_asarray_in_any_function(self):
        out = run("""
            import numpy as np

            def stamp_extras(dev):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/runtime/heartbeat.py")
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "zero-device-sync" in out[0].message

    def test_heartbeat_file_flags_item_and_module_level(self):
        out = run("""
            import jax

            PROBE = jax.device_get(0)

            class HeartbeatWriter:
                def stamp(self, step):
                    return step.item()
            """, self.RULE, filename="deepspeed_tpu/runtime/heartbeat.py")
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    def test_heartbeat_file_allows_host_float_parsing(self):
        # float() on config/env values is host math, not a device fetch
        out = run("""
            import os

            def interval():
                return float(os.environ.get("X", "1.0"))
            """, self.RULE, filename="deepspeed_tpu/runtime/heartbeat.py")
        assert out == []

    def test_same_asarray_outside_v2_stays_clean_in_cold_code(self):
        out = run("""
            import numpy as np

            def collect(dev):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/runtime/foo.py")
        assert out == []

    # ---- ops-plane whole-file scan (ISSUE 11): scrape handlers and registry
    # adapters read host-side cached snapshots only — a device fetch anywhere
    # in monitor/metrics|exposition|ops_server is a finding, same contract
    # (and same scan) as runtime/heartbeat.py
    @pytest.mark.parametrize("fname", ["deepspeed_tpu/monitor/metrics.py",
                                       "deepspeed_tpu/monitor/exposition.py",
                                       "deepspeed_tpu/monitor/ops_server.py"])
    def test_ops_plane_flags_fetch_in_any_function(self, fname):
        out = run("""
            import numpy as np

            def populate_from_engine(reg, engine):
                reg.set_gauge("x", np.asarray(engine.dev_value))
            """, self.RULE, filename=fname)
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "zero-device-sync" in out[0].message

    def test_ops_plane_flags_item_and_module_level(self):
        out = run("""
            import jax

            PROBE = jax.device_get(0)

            def render_family(fam):
                return fam.value.item()
            """, self.RULE, filename="deepspeed_tpu/monitor/ops_server.py")
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    def test_ops_plane_allows_host_string_and_float_work(self):
        # the ops plane is pure host string/arithmetic work: float() parsing,
        # dict .items() iteration and json dumps must all stay clean
        out = run("""
            import json

            def render(reg):
                out = []
                for name, fam in reg.families.items():
                    out.append(f"{name} {float(fam.value)}")
                return json.dumps(out)
            """, self.RULE, filename="deepspeed_tpu/monitor/metrics.py")
        assert out == []

    def test_monitor_files_outside_ops_plane_not_whole_file_scanned(self):
        # monitor/telemetry.py keeps the default scoping (hot-path names
        # only) — the whole-file contract covers exactly the ops plane
        out = run("""
            import numpy as np

            def collect(dev):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/monitor/telemetry.py")
        assert out == []

    def test_v2_hot_fn_broad_scan_no_duplicate_findings(self):
        out = run("""
            import numpy as np

            class InferenceEngineV2:
                def decode_burst(self, k):
                    toks = np.asarray(self._toks)
                    return float(toks.sum())
            """, self.RULE, filename="deepspeed_tpu/inference/v2/engine_v2.py")
        # hot-path scan applies (asarray + float), each flagged exactly once
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    # ---- serving perf observatory whole-file scan (ISSUE 16): phase marks
    # run at every serve iteration and ledger records at every compile seam —
    # a device fetch anywhere in monitor/perf.py is a finding, same contract
    # (and same scan) as runtime/heartbeat.py and the ops plane
    def test_perf_observatory_flags_fetch_in_any_function(self):
        out = run("""
            import numpy as np

            class StepPhaseProfiler:
                def mark(self, phase, dev):
                    self.totals[phase] += float(np.asarray(dev))
            """, self.RULE, filename="deepspeed_tpu/monitor/perf.py")
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "zero-device-sync" in out[0].message

    def test_perf_observatory_flags_block_until_ready_and_module_level(self):
        out = run("""
            import jax

            PROBE = jax.device_get(0)

            class CompileLedger:
                def record(self, site, key, compiled):
                    compiled.block_until_ready()
            """, self.RULE, filename="deepspeed_tpu/monitor/perf.py")
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    def test_perf_observatory_allows_host_clock_and_float_math(self):
        # the observatory consumes the engine's injectable clock (a host
        # callable) plus host floats: clock reads, float() math and dict
        # bookkeeping must all stay clean
        out = run("""
            class StepPhaseProfiler:
                def mark(self, phase):
                    now = float(self._clock())
                    self.totals[phase] = self.totals.get(phase, 0.0) + (
                        now - self._t_mark)
                    self._t_mark = now
            """, self.RULE, filename="deepspeed_tpu/monitor/perf.py")
        assert out == []

    def test_tools_are_not_whole_file_scanned(self):
        # tools keep the default scoping: no whole-file fragment covers them
        out = run("""
            import numpy as np

            def collect(dev):
                return np.asarray(dev)
            """, self.RULE, filename="deepspeed_tpu/tools/reportgen.py")
        assert out == []

    # ---- fleet router whole-file scan (ISSUE 17): routing/failover runs in
    # the request admission path and must stay host-side — stricter than the
    # per-function v2 scan that would otherwise apply to the module, since
    # .item() and module-level fetches are findings here too
    def test_fleet_router_flags_fetch_in_any_function(self):
        out = run("""
            import numpy as np

            class FleetRouter:
                def _load_score(self, index):
                    return float(np.asarray(self.replicas[index].load))
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/router.py")
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "zero-device-sync" in out[0].message

    def test_fleet_router_flags_item_and_module_level(self):
        # .item() is a finding in the router even though the package-wide v2
        # scan would let it pass, and module level is covered too
        out = run("""
            import jax

            SEED = jax.device_get(0)

            def route(scores):
                return scores.argmin().item()
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/router.py")
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    def test_fleet_router_allows_host_hashing_and_journal_work(self):
        # the router's real work — affinity hashing, health dict reads,
        # journal replay bookkeeping — is pure host code and must stay clean
        out = run("""
            def route(self, prompt, exclude=()):
                hashes = block_hashes(list(prompt)[:16], self.block_size)
                if not hashes:
                    return None
                home = int.from_bytes(hashes[-1][:8], "big") % len(self.replicas)
                score = float(self.replicas[home].health.get("queue_depth", 0))
                return home if score < 2.0 else None
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/router.py")
        assert out == []

    def test_v2_files_beside_router_keep_per_function_scan(self):
        # the stricter whole-file contract covers exactly router.py — its v2
        # siblings keep the package scan, where .item() on host scalars in
        # non-hot functions stays legal
        out = run("""
            def health(self):
                return {"depth": self._depth.item()}
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/scheduler.py")
        assert out == []

    # ---- spec-decode whole-file scan (ISSUE 20): drafters and the rejection
    # sampler run at every verify round and are contractually zero-device-sync
    # — accept/reject accumulation stays on device until the engine's
    # wave-boundary materialize, so a fetch ANYWHERE in spec_decode.py is a
    # finding, same scan as heartbeat/ops/perf/router
    def test_spec_decode_flags_fetch_in_any_function(self):
        out = run("""
            import numpy as np

            class NgramDrafter:
                def propose(self, tokens, k):
                    return np.asarray(tokens[-k:])
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/spec_decode.py")
        assert rules_of(out) == ["host-sync-in-hot-path"]
        assert "zero-device-sync" in out[0].message

    def test_spec_decode_flags_item_and_module_level(self):
        # .item() on the accept count is exactly the per-round stall the
        # contract forbids, and module-level fetches are covered too
        out = run("""
            import jax

            PROBE = jax.device_get(0)

            class SpecDecodeStats:
                def note_round(self, count):
                    self.accepted += count.item()
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/spec_decode.py")
        assert rules_of(out) == ["host-sync-in-hot-path"] * 2

    def test_spec_decode_jit_root_subtree_skipped(self):
        # the rejection sampler itself is a jit root: device math inside it
        # (argmax, cumprod, categorical) is the point, not a sync
        out = run("""
            import jax
            import jax.numpy as jnp

            def rejection_select(logits, draft, rng):
                tgt = jnp.argmax(logits, axis=-1)
                acc = (draft == tgt[:, :-1]).astype(jnp.int32)
                return 1 + jnp.sum(jnp.cumprod(acc, axis=1), axis=1)

            select = jax.jit(rejection_select)
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/spec_decode.py")
        assert out == []

    def test_spec_decode_allows_host_buffer_staging(self):
        # np.zeros staging buffers filled from python token lists are host
        # work (uploads, not fetches) and must stay clean
        out = run("""
            import numpy as np

            def propose_batch(seqs, k, pad_to):
                out = np.zeros((pad_to, k), np.int32)
                for i, seq in enumerate(seqs):
                    out[i, :len(seq.tokens[-k:])] = seq.tokens[-k:]
                return out
            """, self.RULE,
            filename="deepspeed_tpu/inference/v2/spec_decode.py")
        assert out == []


# ------------------------------------------------------ traced-control-flow
class TestTracedControlFlow:
    RULE = ["traced-control-flow"]

    def test_flags_if_on_traced_param(self):
        out = run("""
            import jax

            def step(x, scale):
                if scale > 0:
                    x = x * scale
                return x

            fn = jax.jit(step)
            """, self.RULE)
        assert rules_of(out) == ["traced-control-flow"]

    def test_flags_while_and_nested_def_params(self):
        out = run("""
            import jax

            def outer(n):
                def body(carry):
                    while carry > 0:
                        carry = carry - 1
                    return carry
                return body(n)

            fn = jax.jit(outer)
            """, self.RULE)
        assert len(out) == 1 and "while" in out[0].message

    def test_allows_static_argnums_shape_isinstance_is_none(self):
        out = run("""
            import jax

            def step(x, mode, y=None):
                if mode == "train":
                    x = x + 1
                if x.shape[0] > 2:
                    x = x * 2
                if y is None:
                    y = x
                if isinstance(y, tuple):
                    y = y[0]
                return x, y

            fn = jax.jit(step, static_argnums=(1, ))
            """, self.RULE)
        assert out == []

    def test_decorator_form_static_argnums_not_flagged(self):
        out = run("""
            from functools import partial
            import jax

            @partial(jax.jit, static_argnums=(1, ))
            def f(x, n):
                if n > 2:
                    return x * n
                return x

            @jax.jit
            def g(x, n):
                if n > 2:
                    return x * n
                return x
            """, self.RULE)
        # f's n is static (decorator keywords honored); g's n is traced
        assert [(f_.rule, f_.line) for f_ in out] == [("traced-control-flow", 13)]

    def test_ignores_unjitted_function_and_closure_vars(self):
        out = run("""
            import jax

            def build(flag):
                def step(x):
                    if flag:
                        return x + 1
                    return x
                return jax.jit(step)

            def plain(x):
                if x > 0:
                    return x
            """, self.RULE)
        assert out == []

    def test_flags_partial_bound_kwarg_conservatively(self):
        # partial-binding makes the branch safe at THIS jit site, but the lint
        # can't prove all sites — the documented resolution is a suppression
        out = run("""
            import functools
            import jax

            def sample(logits, temperature):
                if temperature == 0.0:
                    return logits.argmax()
                return logits / temperature

            fn = jax.jit(functools.partial(sample, temperature=0.0))
            """, self.RULE)
        assert rules_of(out) == ["traced-control-flow"]

    # ---- spec verify jit sites (ISSUE 20): the engine builds one verify
    # program per (n, k, sample_cfg) bucket, so the recompile-risk shape is a
    # branch on a TRACED batch value inside the jit — flag it
    def test_spec_verify_branch_on_traced_draft_flagged(self):
        out = run("""
            import jax
            import jax.numpy as jnp

            def verify(params, kv, tok0, draft, count):
                if count > 0:
                    draft = draft + 1
                tokens = jnp.concatenate([tok0[:, None], draft], axis=1)
                return kv, tokens

            fn = jax.jit(verify, donate_argnums=(1, ))
            """, self.RULE)
        assert rules_of(out) == ["traced-control-flow"]

    def test_spec_verify_closure_bound_sample_cfg_stays_clean(self):
        # the engine's real shape: sample_cfg/k are python values bound by
        # the builder's closure — branching on them specializes the program
        # per bucket (intended), and shape reads are static
        out = run("""
            import jax
            import jax.numpy as jnp

            def build_verify(n, k, sample_cfg=None):
                def verify(params, kv, tok0, draft, rng):
                    tokens = jnp.concatenate([tok0[:, None], draft], axis=1)
                    if sample_cfg is None:
                        picked = jnp.argmax(tokens, axis=-1)
                    else:
                        picked = jax.random.categorical(rng, tokens * sample_cfg[0])
                    if tokens.shape[1] != k + 1:
                        raise ValueError("bucket mismatch")
                    return kv, picked
                return jax.jit(verify, donate_argnums=(1, ))
            """, self.RULE)
        assert out == []


# ------------------------------------------------------- donation-after-use
class TestDonationAfterUse:
    RULE = ["donation-after-use"]

    def test_flags_reuse_after_donation(self):
        out = run("""
            import jax

            def train(state, batch):
                step = jax.jit(lambda s, b: s, donate_argnums=(0, ))
                new_state = step(state, batch)
                return state["params"]
            """, self.RULE)
        assert rules_of(out) == ["donation-after-use"]
        assert out[0].snippet == 'return state["params"]'  # anchored at the reuse, not the call

    def test_reassignment_from_result_is_clean(self):
        out = run("""
            import jax

            class Engine:
                def run(self, batch):
                    self.state, metrics = self._step(self.state, batch)
                    return self.state, metrics

                def build(self):
                    self._step = jax.jit(lambda s, b: (s, 0.0), donate_argnums=(0, ))
            """, self.RULE)
        assert out == []

    def test_attribute_bound_callable_checked_module_wide(self):
        out = run("""
            import jax

            class Trainer:
                def build(self):
                    self._opt = jax.jit(lambda p, g: p, donate_argnums=(0, ))

                def step(self, grads):
                    new_params = self._opt(self.params, grads)
                    norm = self.params  # stale read of the donated buffer
                    return new_params, norm
            """, self.RULE)
        assert rules_of(out) == ["donation-after-use"]
        assert "self.params" in out[0].message

    def test_escaping_callable_flagged_as_contract(self):
        out = run("""
            import jax

            class Engine:
                def compile(self, key, fwd):
                    self._cache[key] = jax.jit(fwd, donate_argnums=(1, ))

            def factory(fn):
                return jax.jit(fn, donate_argnums=(0, ))
            """, self.RULE)
        assert rules_of(out) == ["donation-after-use"] * 2
        assert all(f.severity == "warning" for f in out)

    def test_donate_argnames_resolved_alongside_argnums(self):
        out = run("""
            import jax

            def step(state, extra, batch):
                return state

            def train(state, extra, batch):
                fn = jax.jit(step, donate_argnums=(0, ), donate_argnames=("extra", ))
                new_state = fn(state, extra, batch)
                return extra  # reuse of the argnames-donated buffer
            """, self.RULE)
        assert rules_of(out) == ["donation-after-use"]
        assert "'extra'" in out[0].message and "position 1" in out[0].message

    def test_no_donation_no_finding(self):
        out = run("""
            import jax

            def train(state, batch):
                step = jax.jit(lambda s, b: s)
                new_state = step(state, batch)
                return state
            """, self.RULE)
        assert out == []

    # ---- spec verify jit sites (ISSUE 20): verify donates the KV pool
    # (argnum 1).  The builder RETURNS the jitted callable and the per-bucket
    # cache is a container binding — both escape static call-site analysis,
    # so each is a contract warning the engine resolves with a written
    # suppression at the jit site
    def test_spec_verify_builder_and_cache_flagged_as_contract(self):
        out = run("""
            import jax

            class EngineV2:
                def _build_spec_verify_jit(self, n, k):
                    def verify(params, kv, tok0, draft, rng):
                        return kv, draft, rng
                    return jax.jit(verify, donate_argnums=(1, ))

                def _compiled_spec_verify(self, key):
                    self._fns[key] = jax.jit(lambda p, kv: kv,
                                             donate_argnums=(1, ))
            """, self.RULE)
        assert rules_of(out) == ["donation-after-use"] * 2
        assert all(f.severity == "warning" for f in out)

    def test_spec_verify_kv_reassigned_from_result_is_clean(self):
        # the engine's real call-site contract: self.kv is reassigned from
        # the verify result in the same statement, so the donated buffer is
        # never read again
        out = run("""
            import jax

            class EngineV2:
                def build(self):
                    self._verify = jax.jit(lambda p, kv, d: (kv, d),
                                           donate_argnums=(1, ))

                def decode_spec(self, draft):
                    self.kv, packed = self._verify(self.params, self.kv, draft)
                    return packed
            """, self.RULE)
        assert out == []


# ------------------------------------------------------ nondeterministic-rng
class TestNondeterministicRNG:
    RULE = ["nondeterministic-rng"]

    def test_flags_global_random_and_np_random(self):
        out = run("""
            import random
            import numpy as np

            def layout(nb):
                cols = random.sample(range(nb), 2)
                noise = np.random.randn(nb)
                return cols, noise
            """, self.RULE)
        assert rules_of(out) == ["nondeterministic-rng"] * 2

    def test_seeded_streams_are_clean(self):
        out = run("""
            import random
            import numpy as np

            def layout(nb, seed):
                rng = random.Random(seed)
                cols = rng.sample(range(nb), 2)
                gen = np.random.default_rng(seed)
                return cols, gen.standard_normal(nb)
            """, self.RULE)
        assert out == []

    def test_flags_prng_key_reuse_without_split(self):
        out = run("""
            import jax

            def two_draws(key, shape):
                a = jax.random.normal(key, shape)
                b = jax.random.uniform(key, shape)
                return a, b
            """, self.RULE)
        assert rules_of(out) == ["nondeterministic-rng"]
        assert "split" in out[0].message

    def test_np_random_calls_are_not_prng_keys(self):
        # np.random.choice(pool) twice: two global-state findings, but NO bogus
        # "key 'pool' reused" — only jax.random consumers take PRNG keys
        out = run("""
            import numpy as np

            def pick_two(pool):
                a = np.random.choice(pool)
                b = np.random.choice(pool)
                return a, b
            """, self.RULE)
        assert rules_of(out) == ["nondeterministic-rng"] * 2
        assert all("np.random" in f.message for f in out)

    def test_rebinding_consumer_reuse_ordering(self):
        # `k = jax.random.permutation(k, x)` both CONSUMES the old k (reuse —
        # must flag, line 6) and rebinds it (so line 7's draw is clean)
        out = run("""
            import jax

            def f(k, x, shape):
                a = jax.random.normal(k, shape)
                k = jax.random.permutation(k, x)
                b = jax.random.normal(k, shape)
                return a, k, b
            """, self.RULE)
        assert [(f.rule, f.line) for f in out] == [("nondeterministic-rng", 6)]

    def test_split_between_draws_is_clean(self):
        out = run("""
            import jax

            def two_draws(key, shape):
                a = jax.random.normal(key, shape)
                key, sub = jax.random.split(key)
                b = jax.random.uniform(key, shape)
                return a, b
            """, self.RULE)
        assert out == []


# ----------------------------------------------------- raw-clock-in-serving
class TestRawClockInServing:
    RULE = ["raw-clock-in-serving"]
    V2 = "deepspeed_tpu/inference/v2/engine_v2.py"

    @pytest.mark.parametrize("call", ["time.time()", "time.monotonic()",
                                      "time.perf_counter()"])
    def test_flags_direct_calls_under_v2(self, call):
        out = run(f"""
            import time

            def intake(self, uid):
                return {call}
            """, self.RULE, filename=self.V2)
        assert rules_of(out) == ["raw-clock-in-serving"]
        assert "injectable clock" in out[0].message

    def test_from_import_and_alias_forms_flagged(self):
        out = run("""
            import time as _t
            from time import monotonic as mono

            def a():
                return _t.perf_counter()

            def b():
                return mono()
            """, self.RULE, filename=self.V2)
        assert rules_of(out) == ["raw-clock-in-serving"] * 2

    def test_binding_as_default_is_the_legal_seam(self):
        # referencing time.monotonic WITHOUT calling it is exactly how the
        # injectable-clock seam is wired — must stay clean
        out = run("""
            import time

            class AdmissionQueue:
                def __init__(self, config=None, *, clock=time.monotonic):
                    self.clock = clock

            class InferenceEngineV2:
                def __init__(self, clock=None):
                    self._clock = clock if clock is not None else time.monotonic
            """, self.RULE, filename=self.V2)
        assert out == []

    def test_injected_clock_calls_are_clean(self):
        out = run("""
            def pump(self):
                now = self._clock()
                return now + self.clock()
            """, self.RULE, filename=self.V2)
        assert out == []

    def test_same_calls_outside_v2_stay_clean(self):
        out = run("""
            import time

            def rate(self):
                return time.perf_counter()
            """, self.RULE, filename="deepspeed_tpu/monitor/telemetry.py")
        assert out == []

    def test_suppressible_with_reason(self):
        out = run("""
            import time

            def wall_deadline():
                return time.time()  # dslint: disable=raw-clock-in-serving  # wall-clock wanted: external SLA timestamps
            """, self.RULE, filename=self.V2)
        assert out == []


# ------------------------------------------------------------- silent-except
class TestSilentExcept:
    RULE = ["silent-except"]

    def test_flags_broad_pass(self):
        out = run("""
            def f():
                try:
                    g()
                except Exception:
                    pass
                try:
                    g()
                except:
                    ...
            """, self.RULE)
        assert rules_of(out) == ["silent-except"] * 2

    def test_narrow_or_logged_handlers_are_clean(self):
        out = run("""
            def f():
                try:
                    g()
                except OSError:
                    pass
                try:
                    g()
                except Exception as exc:
                    logger.warning(f"boom: {exc}")
            """, self.RULE)
        assert out == []


# -------------------------------------------------------- float64-in-compute
class TestFloat64InCompute:
    RULE = ["float64-in-compute"]

    def test_flags_attr_and_dtype_string(self):
        out = run("""
            import numpy as np

            def f(x):
                a = np.zeros(4, dtype=np.float64)
                b = x.astype("float64")
                return a, b
            """, self.RULE)
        assert rules_of(out) == ["float64-in-compute"] * 2

    def test_f32_and_nondtype_strings_are_clean(self):
        out = run("""
            import numpy as np

            def f(x):
                a = np.zeros(4, dtype=np.float32)
                name = "float64"  # a plain string, not a dtype position
                return a, name
            """, self.RULE)
        assert out == []


# ---------------------------------------------------- undeclared-config-key
class TestUndeclaredConfigKey:
    RULE = ["undeclared-config-key"]

    def test_flags_typo_against_schema(self):
        out = run("""
            def setup(config):
                return config.get("gradient_acumulation_steps", 1)
            """, self.RULE, extra_declared_keys={"gradient_accumulation_steps"})
        assert rules_of(out) == ["undeclared-config-key"]
        assert "gradient_acumulation_steps" in out[0].message

    def test_declared_keys_and_nonconfig_dicts_are_clean(self):
        out = run("""
            def setup(config, record):
                a = config.get("stage", 0)
                b = config["zero_optimization"]
                c = record.get("whatever_key")  # not a config-named dict
                return a, b, c
            """, self.RULE, extra_declared_keys={"stage", "zero_optimization"})
        assert out == []

    def test_writes_are_not_reads(self):
        # establishing a derived key can't "fall back to a default" — only
        # Load-context subscripts are checked
        out = run("""
            def derive(config):
                config["derived_total_batch"] = 64
                return config["derived_total_batch"]
            """, self.RULE)
        assert [(f.rule, f.line) for f in out] == [("undeclared-config-key", 4)]

    def test_schema_fields_collected_from_configmodel_classes(self):
        out = run("""
            class ConfigModel:
                pass

            class MyConfig(ConfigModel):
                stage: int = 0
                bucket_size: int = Field(5, deprecated_names=("old_bucket_size", ))

            def setup(ds_config):
                a = ds_config.get("stage")
                b = ds_config.get("old_bucket_size")
                c = ds_config.get("not_a_field")
                return a, b, c
            """, self.RULE)
        assert rules_of(out) == ["undeclared-config-key"]
        assert "not_a_field" in out[0].message


# ------------------------------------------------------------------ meta
def test_parse_error_is_reported_not_raised():
    out = lint_source("def broken(:\n")
    assert rules_of(out) == ["parse-error"]


@pytest.mark.slow
def test_in_tree_acceptance_every_rule_demonstrated():
    """The PR's acceptance bar: running dslint over the real package must be
    CLEAN, with every rule witnessed by at least one in-tree suppression or a
    fix covered elsewhere (sparsity seeding, warning_once, host_lr_fn...)."""
    import os
    from deepspeed_tpu.tools.staticcheck import (DEFAULT_BASELINE_NAME, load_baseline,
                                                 run_lint)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    pkg = os.path.join(root, "deepspeed_tpu")
    result = run_lint([pkg], root=root,
                      baseline=load_baseline(os.path.join(root, DEFAULT_BASELINE_NAME)))
    assert result.findings == [], "\n".join(f.format_text() for f in result.findings)
    assert result.files_checked > 100
    # the make-lint latency budget: 20 rules + the cross-module mesh AND
    # thread models must still fit the same full-tree bound (ISSUE 14 perf
    # guard, widened by the ISSUE 18 concurrency rules)
    assert len(result.rules_run) == 20
    assert result.seconds < 30
    assert result.suppressed_count > 0  # the written-reason suppressions exist
