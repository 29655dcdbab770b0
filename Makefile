# Test lanes (VERDICT r3 #9: kernel-parity regressions must not hide behind
# the default `-m "not slow"` lane).  `make fast_then_slow` is the CI target;
# its last line is a JSON summary of every lane's count.

PY ?= python

.PHONY: test test-slow fast_then_slow telemetry-smoke resilience-smoke serving-resilience-smoke serving-fastpath-smoke tracing-smoke ops-smoke ops-stress-smoke kv-obs-smoke prefix-cache-smoke serving-recovery-smoke elastic-smoke perf-smoke fleet-smoke qos-smoke spec-decode-smoke drift-families lint lint-baseline lint-api-surface lint-mesh-manifest lint-changed lint-suppressions

test:
	$(PY) -m pytest tests/ -q

# dslint: JAX/TPU-aware static analysis (tools/staticcheck) over the whole
# package AND tests/ (test files are scanned by the test-scoped rules only,
# e.g. direct-shimmed-import); exits non-zero on any non-baselined finding.
# CI gate (also a lane in run_tests.py).
lint:
	$(PY) bin/dstpu-lint deepspeed_tpu tests

# grandfather the current findings (policy: the baseline only ever shrinks —
# new code suppresses inline with a written reason instead)
lint-baseline:
	$(PY) bin/dstpu-lint deepspeed_tpu tests --update-baseline

# re-pin the package's external jax surface into .dslint-api-surface.json
# after a DELIBERATE surface change — review the manifest diff before
# committing (the jax-api-surface rule fails CI on any unpinned symbol)
lint-api-surface:
	$(PY) bin/dstpu-lint --update-api-surface

# re-pin the package's declared mesh axis names into .dslint-mesh-manifest.json
# after a DELIBERATE mesh change — review the diff before committing (the
# unknown-mesh-axis rule fails CI on any unpinned/stale axis)
lint-mesh-manifest:
	$(PY) bin/dstpu-lint --update-mesh-manifest

# audit every inline suppression: per-rule counts with file:line + reasons,
# stale/reasonless entries highlighted; exits 1 if any need attention
lint-suppressions:
	$(PY) bin/dstpu-lint --list-suppressions

# fast pre-push lane: lint only .py files changed vs BASE (default HEAD =
# uncommitted work; use BASE=origin/main before pushing a branch).  Subset
# lints still build whole-package context, so findings match the full run.
BASE ?= HEAD
lint-changed:
	$(PY) bin/dstpu-lint --changed $(BASE)

# the previously-drifted kernel/onebit/TP/sequence families, gated HARD-GREEN
# (ISSUE 10): these are the tests that protect every multichip ROADMAP item
drift-families:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --drift-families

test-slow:
	$(PY) -m pytest tests/ -q -m slow

fast_then_slow:
	$(PY) run_tests.py

# 3-step CPU train loop with telemetry enabled; asserts 3 well-formed JSONL
# records (loss/step_time/throughput/mfu/hbm) + jax.profiler trace files
telemetry-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --telemetry-smoke

# kill-a-save-mid-write → 'latest' untouched → fresh engine resumes from the
# last valid checkpoint → 3-step loss continuity (fault-injection harness)
resilience-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --resilience-smoke

# fault-injected mixed-arrival serving run on CPU (probabilistic KV-allocator
# failures + throttled admission waves): every request must finish ok with
# zero stalls and the KV pool fully reclaimed; also a lane in run_tests.py
serving-resilience-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --serving-resilience-smoke

# serving fast path invariants on CPU (counters, not wall-clock): <=1 host
# sync per steady-state serve-loop iteration, fused decode dominates, zero
# recompiles on a warm identical rerun, byte-identical to the
# serving_fastpath.enabled=False reference loop; also a lane in run_tests.py
serving-fastpath-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --serving-fastpath-smoke

# request-lifecycle tracing (ISSUE 6): mixed-arrival serve with tracing ON —
# every admitted request yields a complete JSONL span chain whose terminal
# event matches its RequestResult status, TTFT/TBT/e2e/queue-wait histograms
# fill, and the fastpath host-link counters are IDENTICAL to a tracing-off
# run; also a lane in run_tests.py
tracing-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --tracing-smoke

# ops plane (ISSUE 11): mixed-arrival serve with the ops server ON — /metrics
# scrapes mid-serve and after must strict-parse as Prometheus 0.0.4 exposing
# shed/preempt/fastpath counters + TTFT/TBT/e2e histograms, /healthz mirrors
# health(), and the fastpath ServeCounters are byte-identical server on vs
# off (scrapes read host-side cached snapshots; zero added device syncs)
ops-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --ops-smoke

# concurrency stress (ISSUE 18): N threads hammering /metrics + /healthz +
# health() through a mixed serve; strict-parsed responses, zero hammer-thread
# exceptions, ServeCounters byte-identical to an unscraped run
ops-stress-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --ops-stress-smoke

# KV-pool observability (ISSUE 12): a shared-prefix serve must report a
# non-zero counterfactual prefix-cache win (duplicate blocks + hit-rate +
# prefill tokens saved) with the serving_kv_* families strict-parsing off
# /metrics, the census-vs-allocator partition invariant must hold through a
# 25%-fault-injected serve, and the fastpath ServeCounters must be
# byte-identical with kv observability on vs off (zero added device syncs)
kv-obs-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --kv-obs-smoke

# copy-on-write prefix caching (ISSUE 13): a shared-prefix arrival run must
# realize a hit-rate > 0 with prefill tokens saved EQUAL to the
# PrefixObservatory's counterfactual prediction, serve tokens byte-identical
# cache on vs off, fully reclaim the pool AND the tree at drain (refcount +
# census invariants clean, incl. under 25% injected allocator faults), and
# leave the fastpath ServeCounters byte-identical on a no-sharing workload
prefix-cache-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --prefix-cache-smoke

# serving fault tolerance (ISSUE 8): kill a real serving worker mid-decode;
# supervised restart + journal replay must bring every request to a terminal
# status with token streams byte-identical to an uninterrupted seeded run,
# degrade to drain-only past the restart budget, indict a hung worker by
# heartbeat staleness, and keep the journaling tax under 3% tok/s
serving-recovery-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --serving-recovery-smoke

# elastic fault tolerance (ISSUE 7): 4 real worker processes under the
# elastic agent — crash one rank mid-step (gen 0), hang another inside a
# stamped collective (gen 1, caught by heartbeat staleness, NOT exit codes) —
# assert rescale 4→2→1, every generation resumes from the agent-pinned
# consensus tag, losses match an uninterrupted reference run exactly, and
# /proc shows zero orphaned workers; also a lane in run_tests.py
elastic-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --elastic-smoke

# serving perf observatory (ISSUE 16): 3-wave mixed-arrival serve with the
# observatory ON — every phase family non-empty with spans summing to the
# iteration wall, zero warm recompiles, slot counters (live <= computed), the
# serving_phase/compiles/recompiles families and the slot counters
# strict-parsing off a live /metrics scrape, and tokens + ServeCounters
# byte-identical vs off
perf-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --perf-smoke

# serving fleet (ISSUE 17): 3 in-process supervised replicas behind the
# health-gated FleetRouter; one replica crash-injected mid-decode past its
# restart budget — journaled in-flight work must migrate to a healthy
# replica byte-identically, the merged /metrics stays strict-parseable and
# monotone across the failover, prefix affinity realizes KV hits on the
# home replica, and zero requests are lost or orphaned
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --fleet-smoke

# multi-tenant QoS (ISSUE 19): adversarial noisy-neighbor run — a batch-class
# flood tenant against a tight token-rate quota while an interactive tenant
# trickles, under 25% injected KV-allocator faults; interactive TTFT p95 must
# stay within 2x its flood-free baseline, every flood shed must be the
# structured retryable quota_exceeded/queue_full with a finite retry hint,
# zero stalls, pool fully reclaimed, serving_tenant_* families strict-parse
qos-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --qos-smoke

# speculative decoding (ISSUE 20): distribution parity proved under 25%
# injected KV-allocator faults and expiring deadlines — greedy spec-on tokens
# byte-identical to spec-off, rejection-sampler marginal within a measured
# total-variation band of the filtered target at T>0, serving_spec_* families
# strict-parse and agree with the engine counters, spec-off exposition clean
spec-decode-smoke:
	JAX_PLATFORMS=cpu $(PY) run_tests.py --spec-decode-smoke
