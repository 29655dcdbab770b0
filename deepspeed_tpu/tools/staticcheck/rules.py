"""dslint rule registry.

Every rule is grounded in a bug class this codebase actually hit (see the
suppression reasons left in-tree for the survivors):

- host-sync-in-hot-path: implicit device→host syncs inside train/eval/serving
  step code (``float()``/``.item()``/``np.asarray()``/``jax.device_get``/
  ``block_until_ready`` on device values) — each one stalls the XLA dispatch
  pipeline for a full round-trip.
- traced-control-flow: Python ``if``/``while`` on a jitted function's traced
  parameters — a TracerBoolConversionError at best, silently-static control
  flow at worst (when a call site happens to bind the value concretely).
- donation-after-use: reading a buffer after passing it to a
  ``jax.jit(..., donate_argnums=...)`` callable — XLA may have reused the
  memory; also flags donating callables that escape module-local analysis
  (returned / stored in containers), where every call site carries an
  unverifiable contract.
- nondeterministic-rng: global ``random``/``np.random`` module state in
  library code (layouts/decisions diverge across ranks and reruns), and jax
  PRNG keys fed to two consumers without an intervening ``split``.
- raw-clock-in-serving: direct ``time.time()``/``time.monotonic()``/
  ``time.perf_counter()`` calls under ``inference/v2/`` — serving code must
  consume the engine's injectable clock seam (``clock=...``, default bound to
  ``time.monotonic`` WITHOUT calling it), or FakeClock-driven fault/deadline/
  tracing tests silently read real wall-time and stop being deterministic.
- silent-except: ``except Exception: pass`` — failures vanish instead of
  being logged once.
- float64-in-compute: explicit float64 dtypes that silently become float32
  under default x64-disabled JAX (and double memory/bandwidth if x64 is on).
- undeclared-config-key: string keys read from config dicts that no
  ``ConfigModel`` schema declares — a typo'd key silently falls back to its
  default instead of erroring.
- unknown-mesh-axis: a ``PartitionSpec``/``in_specs``/``axis_names`` axis
  literal no declared mesh defines — the typo class behind the PR 9 GSPMD
  kv-projection MISCOMPILE (wrong logits, no error); declared axes are
  pinned in a committed manifest (``.dslint-mesh-manifest.json``).
- sharding-dropped-at-boundary: a NamedSharding-placed value flowing into
  ``np.asarray``/``jax.device_get``/``jnp.asarray``-without-device or a
  fresh un-annotated ``device_put`` — the placement silently collapses to a
  single device (the exact gap keeping DeviceBatchState off the multichip
  fast path, engine_v2.py step()).
- spec-rank-mismatch: a PartitionSpec with more dimensions than the array it
  annotates provably has — GSPMD rejects it at runtime on the first
  multichip mesh, long after the single-chip tests went green.
- recompile-risk: request/batch-cardinality expressions (``len(...)``)
  reaching a jit static argument or a padded-shape construction under
  ``inference/v2/`` without passing through the bucketing helpers — each
  distinct value mints a fresh compiled program, breaking the zero-warm-
  recompiles invariant the fastpath smoke only observes after the fact.
- donation-sharding-mismatch: a donated argument rebound to a
  differently-specced placement — donation aliasing needs identical
  shardings, so the "in-place" update silently degrades to a copy.

The concurrency layer (ISSUE 18) reasons over a cross-module thread model
(``thread_model.py``): which functions run on spawned threads / HTTP handler
threads / collector callbacks / signal handlers, which attributes each plane
touches, and which locks are held at each touch:

- cross-thread-mutation: the same attribute written from two planes with no
  common lock — the bug class behind AsyncCheckpointEngine._error, where a
  worker-thread store raced the caller's read-and-clear swap and lost the
  error.
- atomic-publish: a shared attribute updated by augmented assignment,
  in-place container mutation, or a rebind to a freshly-built mutable
  container — readers on the other plane can observe half-applied state;
  the convention is one GIL-atomic pointer store of a complete immutable
  value (the OpsCache pattern).
- handler-holds-engine: an HTTP handler / collector / signal root that
  reaches an engine or manager object — handlers must read pre-rendered
  snapshots, never drive serving machinery from a foreign thread.
- blocking-under-lock: ``sleep``/``join``/``subprocess``/collective calls
  while holding a lock — stalls every thread contending on it (scrapes,
  health probes) for the full blocking duration.
- lock-order: two locks acquired in both A→B and B→A orders across the
  tree — the classic ABBA deadlock, invisible until two threads interleave.
"""

import ast
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from .api_surface import (DEFAULT_MANIFEST_NAME, PACKAGE_PREFIX,
                          collect_api_surface, symbol_sites)
from .context import (COMPAT_PATH_FRAGMENT, ModuleInfo, ProjectContext, enclosing,
                      enclosing_statement, param_names, parent,
                      terminal_name as _terminal_name)
from .findings import Finding
from .mesh_model import (CREATION_FNS as MESH_CREATION_FNS,
                         DEFAULT_MESH_MANIFEST_NAME, SHARDING_FACTORY_METHODS,
                         UNRESOLVED, creation_rank,
                         is_sharding_factory as _is_sharding_factory,
                         shape_rank)

RULES: Dict[str, type] = {}

# the conventional numpy/jnp import aliases — ONE definition shared by every
# rule that matches module-qualified calls (host-sync, boundary, recompile)
NP_MODULE_NAMES = {"np", "numpy", "onp"}
JNP_MODULE_NAMES = {"jnp"}

# meta findings emitted by the runner itself (documented for --list-rules)
META_RULES = {
    "bad-suppression": "malformed dslint control comment or suppression without a reason",
    "unused-suppression": "suppression comment that matched no finding (stale — remove it)",
    "parse-error": "file failed to parse; nothing else can be checked",
}


def register(cls):
    RULES[cls.name] = cls
    return cls


class Rule:
    name = "rule"
    description = ""
    # most rules encode library contracts (hot-path syncs, config schemas, …)
    # that don't apply to test code; rules that DO police tests/ opt in and
    # the runner scopes the rest to package files
    scan_tests = False

    def check(self, module: ModuleInfo, ctx: ProjectContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, module: ModuleInfo, node: ast.AST, message: str,
                severity: str = "error") -> Finding:
        stmt = enclosing_statement(node)
        end = getattr(stmt, "end_lineno", None) or getattr(node, "end_lineno", 0) or 0
        return Finding(rule=self.name, path=module.relpath, line=node.lineno,
                       col=node.col_offset, message=message,
                       snippet=module.snippet(node.lineno), severity=severity,
                       end_line=end)


def _walk_skipping(root: ast.AST, skip: Set[int]) -> Iterator[ast.AST]:
    """ast.walk that does not descend into nodes whose id is in ``skip``."""
    stack = [root]
    while stack:
        node = stack.pop()
        if id(node) in skip and node is not root:
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# --------------------------------------------------------------------------
@register
class HostSyncInHotPath(Rule):
    name = "host-sync-in-hot-path"
    description = ("device→host sync (float/.item/np.asarray/jax.device_get/"
                   "block_until_ready) inside per-step train/eval/serving code; "
                   "under inference/v2/ any direct np.asarray/np.array/"
                   "device_get/block_until_ready outside the sanctioned "
                   "fastpath.materialize() deferred-sync helper; in "
                   "runtime/heartbeat.py AND the ops plane (monitor/metrics.py, "
                   "monitor/exposition.py, monitor/ops_server.py) AND the "
                   "KV-pool observability layer (inference/v2/kv_metrics.py) "
                   "AND the serving perf observatory (monitor/perf.py) AND "
                   "the spec-decode layer (inference/v2/spec_decode.py) "
                   "any explicit device fetch (np.asarray/np.array/device_get/"
                   "block_until_ready/.item) anywhere in the file — liveness "
                   "stamps, metrics scrapes, pool census hooks and phase/compile "
                   "instruments are contractually "
                   "zero-device-sync (float() on host config "
                   "values stays legal there; float-of-device-value isn't "
                   "statically separable from it)")

    HOT_NAMES = {"train_batch", "_offload_train_batch", "eval_batch",
                 "decode_burst", "train_step"}
    ENGINE_METHOD_NAMES = {"step"}  # hot only when defined on an *Engine class
    NP_NAMES = NP_MODULE_NAMES
    # the v2 serving package defers every step-result fetch through
    # fastpath.materialize() (counted + auditable); a direct fetch anywhere
    # else in inference/v2/ is an unsanctioned host sync even outside the
    # classic hot-path function names
    V2_PATH_FRAGMENT = "inference/v2/"
    V2_SANCTIONED_FNS = {"materialize"}
    # the heartbeat seam's contract is ZERO device syncs — stamps are called
    # from the train hot loop and must only write values the host already
    # owns, so the WHOLE file is scanned (module level included) with the
    # full sync set, not just the hot-path function names
    HEARTBEAT_PATH_FRAGMENT = "runtime/heartbeat.py"
    # the ops plane inherits the same whole-file contract (ISSUE 11): a
    # scrape handler or registry adapter that fetches a device value turns
    # every Prometheus poll into a hidden device stall — these modules read
    # only host-side cached snapshots, and a fetch sneaking in is a lint
    # error, not a scrape-time surprise
    OPS_PATH_FRAGMENTS = ("monitor/metrics.py", "monitor/exposition.py",
                          "monitor/ops_server.py")
    # the KV-pool observability layer (ISSUE 12) makes the same promise: the
    # census/observatory/forecaster read only host ints the allocator and
    # ragged manager already own, and their hooks run inside the serve loop —
    # a device fetch here would charge every step a hidden sync, so the whole
    # file is scanned with the full explicit-fetch set
    KV_METRICS_PATH_FRAGMENT = "inference/v2/kv_metrics.py"
    # the serving perf observatory (ISSUE 16) runs INSIDE the serve loop
    # (phase marks at every iteration, ledger records at every compile seam):
    # it consumes only the engine's injectable clock and host ints the
    # engine already owns — a device fetch here would charge every serve
    # iteration a hidden sync, so the whole file is scanned
    # (monitor/program_scopes.py, the ledger's table of what it compiled, keeps
    # the same contract: it reads text the engines hand it and never a device)
    PERF_PATH_FRAGMENT = ("monitor/perf.py", "monitor/program_scopes.py")
    # the fleet router (ISSUE 17) holds the same whole-file promise, stricter
    # than the per-function v2 scan that would otherwise apply: routing and
    # failover decisions read health dicts and journal files only — a device
    # fetch in the front-end would stall EVERY request's admission, so the
    # full explicit-fetch set (plus .item()) applies module-wide
    ROUTER_PATH_FRAGMENT = "inference/v2/router.py"
    # speculative decoding (ISSUE 20) holds it too: drafters run on host ints
    # the engine already owns (n-gram) or entirely on device (draft model),
    # and accept/reject accumulation stays on device until the engine's
    # wave-boundary materialize — a fetch here would charge every verify
    # round a hidden stall, so the whole file is scanned
    SPEC_PATH_FRAGMENT = "inference/v2/spec_decode.py"

    def _is_hot(self, fn: ast.AST) -> bool:
        if fn.name in self.HOT_NAMES:
            return True
        if fn.name in self.ENGINE_METHOD_NAMES:
            cls = enclosing(fn, ast.ClassDef)
            return cls is not None and "Engine" in cls.name
        return False

    def check(self, module, ctx):
        jit_roots = ctx.jit_roots(module)
        relpath = module.relpath.replace("\\", "/")
        if relpath.endswith(self.HEARTBEAT_PATH_FRAGMENT):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in runtime/heartbeat.py — heartbeat stamps are contractually "
                "zero-device-sync (they run in the train hot loop); stamp only "
                "host-native values")
            return
        if any(relpath.endswith(f) for f in self.OPS_PATH_FRAGMENTS):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in the ops plane (monitor/metrics|exposition|ops_server) — "
                "scrape handlers and registry adapters are contractually "
                "zero-device-sync: they read host-side cached snapshots only, "
                "or every Prometheus poll becomes a hidden device stall")
            return
        if relpath.endswith(self.KV_METRICS_PATH_FRAGMENT):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in inference/v2/kv_metrics.py — the KV-pool census/"
                "observatory/forecaster are contractually zero-device-sync: "
                "they consume host ints the allocator and ragged manager "
                "already own, and their hooks run inside the serve loop")
            return
        if relpath.endswith(self.PERF_PATH_FRAGMENT):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in monitor/perf.py — the serving perf observatory (phase "
                "profiler / compile ledger) is contractually "
                "zero-device-sync: it consumes only the engine's injectable "
                "clock and host floats, and its hooks run inside the serve "
                "loop at every iteration and compile seam")
            return
        if relpath.endswith(self.ROUTER_PATH_FRAGMENT):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in inference/v2/router.py — the fleet router is "
                "contractually zero-device-sync: routing, health gating, and "
                "journal-transplant failover read host dicts and journal "
                "files only, or every request's admission stalls on a device "
                "round-trip")
            return
        if relpath.endswith(self.SPEC_PATH_FRAGMENT):
            yield from self._check_zero_sync_file(
                module, jit_roots,
                " in inference/v2/spec_decode.py — drafters and the rejection "
                "sampler are contractually zero-device-sync: accept/reject "
                "accumulation stays on device until the engine's "
                "wave-boundary fastpath.materialize(), or every verify round "
                "charges an extra host stall")
            return
        in_v2 = self.V2_PATH_FRAGMENT in relpath
        seen: Set[int] = set()  # a nested def is also walked via its parent
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if id(node) in jit_roots:
                continue
            hot = self._is_hot(node)
            v2_scan = in_v2 and not hot and node.name not in self.V2_SANCTIONED_FNS
            if not hot and not v2_scan:
                continue
            # nested jitted defs run on device — their bodies can't host-sync
            skip = {id(n) for n in ast.walk(node)
                    if id(n) in jit_roots and n is not node}
            for sub in _walk_skipping(node, skip):
                if not isinstance(sub, ast.Call) or id(sub) in seen:
                    continue
                msg = self._sync_call(sub) if hot else self._v2_sync_call(sub)
                if not msg:
                    continue
                seen.add(id(sub))
                if hot:
                    yield self.finding(module, sub, msg + f" inside hot path '{node.name}' "
                                       "— every occurrence stalls dispatch for a host "
                                       "round-trip; hoist it, batch it into one fetch, or "
                                       "suppress with a reason if this is the step's one "
                                       "deliberate sync")
                else:
                    yield self.finding(module, sub, msg + f" in '{node.name}' under "
                                       "inference/v2/ — serving step results must be "
                                       "fetched through fastpath.materialize() (the "
                                       "counted deferred-sync seam) so syncs stay "
                                       "observable and deferrable; route it through the "
                                       "helper or suppress with a reason if this is "
                                       "host-only data")

    def _check_zero_sync_file(self, module, jit_roots, suffix: str) -> Iterator[Finding]:
        """Whole-file scan with the full explicit-fetch set (heartbeat seam
        and the ops plane): these modules run inside hot loops or behind
        scrape endpoints, so a sync sneaking into ANY helper becomes a silent
        recurring stall — flag it everywhere, module level included."""
        for sub in _walk_skipping(module.tree, set(jit_roots)):
            if not isinstance(sub, ast.Call):
                continue
            # explicit-fetch set + .item(): float() on host config values is
            # legitimate and pervasive here (same reasoning as the v2 scan)
            msg = self._v2_sync_call(sub)
            if msg is None and isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr == "item":
                msg = ".item() forces a device value to host"
            if msg:
                yield self.finding(module, sub, msg + suffix)

    def _sync_call(self, call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name) and f.id == "float" and call.args and \
                not isinstance(call.args[0], ast.Constant):
            return "float() forces a device value to host"
        if isinstance(f, ast.Attribute):
            if f.attr == "item":
                return ".item() forces a device value to host"
            if f.attr == "block_until_ready":
                return ".block_until_ready() blocks on device execution"
            if f.attr in ("asarray", "array") and isinstance(f.value, ast.Name) and \
                    f.value.id in self.NP_NAMES:
                return f"np.{f.attr}() copies a device value to host"
            if f.attr == "device_get" and isinstance(f.value, ast.Name) and \
                    f.value.id == "jax":
                return "jax.device_get() copies device values to host"
        return None

    def _v2_sync_call(self, call: ast.Call) -> Optional[str]:
        """The inference/v2-wide subset: explicit array fetches only.
        ``float()``/``.item()`` on host scalars are everywhere in gauge code
        and are not device fetches, so the package-wide scan skips them."""
        f = call.func
        if isinstance(f, ast.Attribute):
            if f.attr == "block_until_ready":
                return ".block_until_ready() blocks on device execution"
            if f.attr in ("asarray", "array") and isinstance(f.value, ast.Name) and \
                    f.value.id in self.NP_NAMES:
                return f"direct np.{f.attr}()"
            if f.attr == "device_get" and isinstance(f.value, ast.Name) and \
                    f.value.id == "jax":
                return "direct jax.device_get()"
        return None


# --------------------------------------------------------------------------
@register
class TracedControlFlow(Rule):
    name = "traced-control-flow"
    description = ("Python if/while on a traced parameter inside a jitted "
                   "function (trace error, or silently-static branching)")

    ALLOWED_CALLS = {"isinstance", "len", "getattr", "hasattr", "type", "callable"}

    def check(self, module, ctx):
        for root in ctx.jit_roots(module).values():
            fn = root.fn
            traced = set(param_names(fn)) - root.static_names
            for child in ast.iter_child_nodes(fn):
                yield from self._check_body(module, child, traced)

    def _check_body(self, module, node, traced: Set[str]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            # nested defs are traced too when called with traced values; their
            # params join the traced set for their own subtree (conservative)
            traced = traced | set(param_names(node))
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            bad = self._raw_traced_use(node.test, traced)
            if bad:
                sub = node
                kind = "while" if isinstance(sub, ast.While) else "if"
                yield self.finding(
                    module, sub,
                    f"Python `{kind}` on traced parameter '{bad}' of a jitted function — "
                    f"use jnp.where/lax.cond/lax.while_loop, mark the argument static "
                    f"(static_argnums / functools.partial before jit), or suppress with "
                    f"a reason documenting why every call site binds it concretely")
        for child in ast.iter_child_nodes(node):
            yield from self._check_body(module, child, traced)

    def _raw_traced_use(self, test: ast.expr, traced: Set[str]) -> Optional[str]:
        for name in ast.walk(test):
            if not (isinstance(name, ast.Name) and name.id in traced):
                continue
            if self._allowed(name, test):
                continue
            return name.id
        return None

    def _allowed(self, name: ast.Name, stop: ast.expr) -> bool:
        cur = parent(name)
        prev: ast.AST = name
        while cur is not None:
            if isinstance(cur, ast.Attribute) and cur.value is prev:
                return True  # x.shape / x.ndim / x.dtype — static under trace
            if isinstance(cur, ast.Call):
                f = cur.func
                if isinstance(f, ast.Name) and f.id in self.ALLOWED_CALLS:
                    return True
            if isinstance(cur, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in cur.ops):
                return True  # `x is None` — identity, not value
            if cur is stop:
                return False
            prev, cur = cur, parent(cur)
        return False


# --------------------------------------------------------------------------
@register
class DonationAfterUse(Rule):
    name = "donation-after-use"
    description = ("buffer read after being passed to a donate_argnums callable; "
                   "also donating callables escaping module-local verification")

    def check(self, module, ctx):
        for site in ctx.donation_sites(module):
            if site.binding == "immediate":
                call = parent(site.jit_call)
                yield from self._check_call(module, call, site.donated)
            elif site.binding == "local":
                fn = enclosing(site.jit_call, ast.FunctionDef, ast.AsyncFunctionDef)
                scope = fn if fn is not None else module.tree
                for call in self._calls_named(scope, site.name, attribute=False):
                    yield from self._check_call(module, call, site.donated)
            elif site.binding == "attribute":
                for call in self._calls_named(module.tree, site.name, attribute=True):
                    yield from self._check_call(module, call, site.donated)
            else:
                how = {"returned": "returned from its factory",
                       "container": "stored into a container"}.get(
                           site.binding, "bound in a way module-local analysis cannot follow")
                yield self.finding(
                    module, site.jit_call,
                    f"donating callable (donate_argnums={site.donated}) is {how} — call "
                    f"sites cannot be verified here; every caller must reassign the "
                    f"donated argument(s) from the result. Suppress with a reason "
                    f"naming the call sites that uphold the contract",
                    severity="warning")

    def _calls_named(self, scope: ast.AST, name: str, attribute: bool) -> Iterator[ast.Call]:
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if attribute and isinstance(f, ast.Attribute) and f.attr == name:
                yield node
            elif not attribute and isinstance(f, ast.Name) and f.id == name:
                yield node

    def _check_call(self, module, call: ast.Call, donated: Tuple[int, ...]):
        fn = enclosing(call, ast.FunctionDef, ast.AsyncFunctionDef)
        if fn is None:
            return
        stmt = enclosing_statement(call)
        end_line = getattr(stmt, "end_lineno", stmt.lineno)
        for idx in donated:
            if idx >= len(call.args):
                continue
            arg = call.args[idx]
            if not isinstance(arg, (ast.Name, ast.Attribute)):
                continue
            expr = ast.unparse(arg)
            if self._stored_in(stmt, expr):
                continue  # reassigned from the result in the same statement
            reuse = self._first_load_before_store(fn, expr, after_line=end_line)
            if reuse is not None:
                yield self.finding(
                    module, reuse,
                    f"'{expr}' is read after being DONATED to a jitted callable at "
                    f"line {call.lineno} (donate_argnums includes position {idx}) — "
                    f"XLA may have already reused its buffer; reassign it from the "
                    f"call's result or drop the donation")

    def _stored_in(self, stmt: ast.stmt, expr: str) -> bool:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None), ast.Store) and \
                    ast.unparse(node) == expr:
                return True
        return False

    def _first_load_before_store(self, fn, expr: str, after_line: int) -> Optional[ast.AST]:
        first_load = first_store = None
        for node in ast.walk(fn):
            if not isinstance(node, (ast.Name, ast.Attribute)):
                continue
            if node.lineno <= after_line or ast.unparse(node) != expr:
                continue
            if isinstance(node.ctx, ast.Store):
                if first_store is None or node.lineno < first_store.lineno:
                    first_store = node
            elif isinstance(node.ctx, ast.Load):
                if first_load is None or node.lineno < first_load.lineno:
                    first_load = node
        if first_load is None:
            return None
        if first_store is not None and first_store.lineno < first_load.lineno:
            return None
        return first_load


# --------------------------------------------------------------------------
@register
class NondeterministicRNG(Rule):
    name = "nondeterministic-rng"
    description = ("global random/np.random module state in library code; "
                   "jax PRNG key fed to two consumers without split")

    GLOBAL_RANDOM_FNS = {"random", "randint", "sample", "choice", "choices",
                         "shuffle", "uniform", "gauss", "seed", "randrange",
                         "getrandbits", "betavariate", "expovariate"}
    NP_RANDOM_FNS = {"seed", "rand", "randn", "randint", "random", "choice",
                     "shuffle", "permutation", "standard_normal", "uniform",
                     "normal", "sample", "random_sample"}
    KEY_CONSUMERS = {"normal", "uniform", "bernoulli", "categorical", "randint",
                     "truncated_normal", "permutation", "choice", "gumbel",
                     "bits", "exponential", "laplace", "poisson", "gamma",
                     "beta", "dirichlet", "rademacher", "ball", "orthogonal"}

    def check(self, module, ctx):
        random_aliases = self._module_aliases(module.tree, "random")
        np_aliases = self._module_aliases(module.tree, "numpy") | \
            {a for a in ("np", ) if a in self._imported_names(module.tree)}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                f = node.func
                if isinstance(f.value, ast.Name) and f.value.id in random_aliases \
                        and f.attr in self.GLOBAL_RANDOM_FNS:
                    yield self.finding(
                        module, node,
                        f"global random.{f.attr}() in library code — layouts/decisions "
                        f"differ across ranks and reruns; use a seeded random.Random "
                        f"(or jax.random with a config-derived key)")
                elif isinstance(f.value, ast.Attribute) and f.value.attr == "random" and \
                        isinstance(f.value.value, ast.Name) and \
                        f.value.value.id in (np_aliases or {"np"}) and \
                        f.attr in self.NP_RANDOM_FNS:
                    yield self.finding(
                        module, node,
                        f"global np.random.{f.attr}() in library code — use "
                        f"np.random.default_rng(seed)")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_key_reuse(module, node)

    def _module_aliases(self, tree, mod_name: str) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == mod_name:
                        out.add(alias.asname or alias.name)
        return out

    def _imported_names(self, tree) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                out |= {a.asname or a.name for a in node.names}
        return out

    def _check_key_reuse(self, module, fn):
        """Linear scan: the same Name passed as the key to two jax.random
        consumers with no intervening reassignment."""
        events: List[Tuple[int, int, str, str, ast.AST]] = []
        nested = {id(n) for n in ast.walk(fn)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n is not fn}
        for node in _walk_skipping(fn, nested):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
                events.append((node.lineno, node.col_offset, "store", node.id, node))
            elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
                f = node.func
                # jax.random.<dist> specifically — np.random.<fn> takes data,
                # not a PRNG key, and is covered by the global-state check
                if f.attr in self.KEY_CONSUMERS and isinstance(f.value, ast.Attribute) \
                        and f.value.attr == "random" \
                        and isinstance(f.value.value, ast.Name) \
                        and f.value.value.id == "jax" and node.args \
                        and isinstance(node.args[0], ast.Name):
                    events.append((node.lineno, node.col_offset, "consume",
                                   node.args[0].id, node))
        # within one line, consumes order BEFORE stores: in `k = consume(k)` the
        # RHS reads the old key, then the assignment rebinds — sorting by column
        # would process the col-0 Store first, missing a real line-2 reuse and
        # falsely flagging the legitimate post-rebind use
        events.sort(key=lambda e: (e[0], 0 if e[2] == "consume" else 1, e[1]))
        consumed: Dict[str, int] = {}
        for line, _col, kind, name, node in events:
            if kind == "store":
                consumed.pop(name, None)
            elif name in consumed:
                yield self.finding(
                    module, node,
                    f"PRNG key '{name}' already consumed by a jax.random call at line "
                    f"{consumed[name]} and reused here without jax.random.split — the "
                    f"two draws are perfectly correlated")
            else:
                consumed[name] = line


# --------------------------------------------------------------------------
@register
class RawClockInServing(Rule):
    name = "raw-clock-in-serving"
    description = ("direct time.time/monotonic/perf_counter CALL under "
                   "inference/v2/ — serving timestamps must flow through the "
                   "engine's injectable clock seam so FakeClock tests stay "
                   "deterministic (binding time.monotonic as a default is the "
                   "seam and stays legal)")

    V2_PATH_FRAGMENT = "inference/v2/"
    CLOCK_FNS = {"time", "monotonic", "perf_counter",
                 "time_ns", "monotonic_ns", "perf_counter_ns"}

    def check(self, module, ctx):
        if self.V2_PATH_FRAGMENT not in module.relpath.replace("\\", "/"):
            return
        time_aliases: Set[str] = set()
        from_imports: Dict[str, str] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "time":
                        time_aliases.add(alias.asname or alias.name)
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in self.CLOCK_FNS:
                        from_imports[alias.asname or alias.name] = alias.name
        if not time_aliases and not from_imports:
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            hit = None
            if isinstance(f, ast.Attribute) and f.attr in self.CLOCK_FNS and \
                    isinstance(f.value, ast.Name) and f.value.id in time_aliases:
                hit = f"{f.value.id}.{f.attr}()"
            elif isinstance(f, ast.Name) and f.id in from_imports:
                hit = f"time.{from_imports[f.id]}()"
            if hit is None:
                continue
            yield self.finding(
                module, node,
                f"direct {hit} under inference/v2/ — serving code must take "
                f"timestamps from the engine's injectable clock (the "
                f"``clock=...`` seam; binding time.monotonic as a DEFAULT is "
                f"fine, calling it directly is not), otherwise FakeClock-driven "
                f"deadline/trace tests read real wall-time and lose "
                f"determinism; thread the injected clock through, or suppress "
                f"with a reason if this is genuinely wall-clock-only code")


# --------------------------------------------------------------------------
@register
class SilentExcept(Rule):
    name = "silent-except"
    description = "broad `except: pass` — the failure vanishes without a log line"

    BROAD = {"Exception", "BaseException"}

    def check(self, module, ctx):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad(node.type):
                continue
            if all(self._is_noop(stmt) for stmt in node.body):
                what = ast.unparse(node.type) if node.type else "bare except"
                yield self.finding(
                    module, node,
                    f"`except {what}` swallows the failure without logging — log once "
                    f"(utils.logging.warning_once) or suppress with a reason why "
                    f"silence is correct here")

    def _is_broad(self, t: Optional[ast.expr]) -> bool:
        if t is None:
            return True
        return _terminal_name(t) in self.BROAD

    def _is_noop(self, stmt: ast.stmt) -> bool:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            return True
        return isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant)


# --------------------------------------------------------------------------
@register
class Float64InCompute(Rule):
    name = "float64-in-compute"
    description = ("explicit float64 dtype — silently downcast to f32 under "
                   "default x64-disabled JAX")

    ATTR_OWNERS = {"np", "numpy", "jnp", "jax"}
    F64_ATTRS = {"float64", "double"}
    F64_STRINGS = {"float64", "f8", "<f8", ">f8"}
    DTYPE_CALLS = {"astype", "asarray", "array", "zeros", "ones", "full", "empty",
                   "arange", "linspace"}

    def check(self, module, ctx):
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute) and node.attr in self.F64_ATTRS and \
                    isinstance(node.value, ast.Name) and node.value.id in self.ATTR_OWNERS:
                yield self.finding(
                    module, node,
                    f"{node.value.id}.{node.attr}: float64 never survives into device "
                    f"compute (JAX default x64-disabled silently downcasts to f32) — "
                    f"use float32, or suppress with a reason if this is host-only data")
            elif isinstance(node, ast.Constant) and isinstance(node.value, str) and \
                    node.value in self.F64_STRINGS and self._dtype_position(node):
                yield self.finding(
                    module, node,
                    f'dtype "{node.value}": float64 never survives into device compute '
                    f"(JAX default x64-disabled silently downcasts to f32) — use "
                    f"float32, or suppress with a reason if this is host-only data")

    def _dtype_position(self, node: ast.Constant) -> bool:
        up = parent(node)
        if isinstance(up, ast.keyword) and up.arg == "dtype":
            return True
        if isinstance(up, ast.Call) and node in up.args:
            name = _terminal_name(up.func)
            return name in self.DTYPE_CALLS
        return False


# --------------------------------------------------------------------------
@register
class UndeclaredConfigKey(Rule):
    name = "undeclared-config-key"
    description = ("string key read from a config dict that no ConfigModel "
                   "schema declares — typos silently fall back to defaults")

    EXACT_NAMES = {"config", "cfg", "ds_config", "user_config", "param_dict",
                   "config_dict"}
    SUFFIXES = ("_config", "_cfg")

    def _is_config_ref(self, node: ast.AST) -> bool:
        name = _terminal_name(node)
        if name is None:
            return False
        return name in self.EXACT_NAMES or name.endswith(self.SUFFIXES)

    def check(self, module, ctx):
        declared = ctx.declared_config_keys
        for node in ast.walk(module.tree):
            key_node = None
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "get" and self._is_config_ref(node.func.value) and \
                    node.args and isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                key_node = node.args[0]
            elif isinstance(node, ast.Subscript) and self._is_config_ref(node.value) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                # Load only: a WRITE establishes a key (it can't "fall back to
                # a default"), so derived-key assignment stays legal
                key_node = node.slice
            if key_node is None:
                continue
            key = key_node.value
            if key in declared or not key:
                continue
            yield self.finding(
                module, node,
                f"config key '{key}' is not declared by any ConfigModel schema or the "
                f"DECLARED_EXTRA_KEYS registry (runtime/config.py) — a typo here "
                f"silently falls back to the default; declare the key or fix the "
                f"spelling")


# --------------------------------------------------------------------------
@register
class DirectShimmedImport(Rule):
    name = "direct-shimmed-import"
    description = ("import or attribute use of a jax symbol shimmed by "
                   "deepspeed_tpu/compat outside compat/ itself — the banned "
                   "spellings are read from compat's SHIMMED_SYMBOLS registry "
                   "(by AST, never import), so the rule can't go stale; "
                   "scans tests/ too")
    # the one rule that polices test files as well: a drifted test import is a
    # lint error, not a silent collection failure
    scan_tests = True

    def check(self, module, ctx):
        if COMPAT_PATH_FRAGMENT in module.relpath:
            return
        # banned fully-qualified spelling -> (exported name, "module:attr")
        banned: Dict[str, Tuple[str, str]] = {}
        for exported, specs in ctx.shimmed_symbols.items():
            for spec in specs:
                mod_name, _, attr = spec.partition(":")
                banned[f"{mod_name}.{attr}"] = (exported, spec)
        if not banned:
            return
        roots = {spec.partition(":")[0].split(".")[0]
                 for _, spec in banned.values()}
        for symbol, node in symbol_sites(module, roots=roots):
            hit = next((b for b in banned
                        if symbol == b or symbol.startswith(b + ".")), None)
            if hit is None:
                continue
            exported, spec = banned[hit]
            yield self.finding(
                module, node,
                f"direct use of '{hit}' — this symbol is version-shimmed; "
                f"``from deepspeed_tpu.compat import {exported}`` instead "
                f"(SHIMMED_SYMBOLS['{exported}'] lists the spelling "
                f"'{spec}'), so the next upstream rename lands as one edit "
                f"to compat/ instead of red call sites")


# --------------------------------------------------------------------------
@register
class JaxApiSurface(Rule):
    name = "jax-api-surface"
    description = ("external jax.* symbol used by the package but not pinned "
                   "in the committed api-surface manifest "
                   f"({DEFAULT_MANIFEST_NAME}) — after a deliberate surface "
                   "change, regenerate with bin/dstpu-lint "
                   "--update-api-surface; upstream drift then lands as one "
                   "reviewable manifest diff")

    def __init__(self):
        self._missing_reported = False
        self._stale_reported = False

    def check(self, module, ctx):
        if not module.relpath.startswith(PACKAGE_PREFIX):
            return
        if ctx.api_surface is None:
            if not self._missing_reported:
                self._missing_reported = True
                yield Finding(
                    rule=self.name, path=DEFAULT_MANIFEST_NAME, line=1, col=0,
                    message=f"api-surface manifest {DEFAULT_MANIFEST_NAME} does "
                            f"not exist — generate it once with "
                            f"'bin/dstpu-lint --update-api-surface' and commit "
                            f"it; without it the package's external jax surface "
                            f"is unpinned and upstream drift lands as red tests")
            return
        if not self._stale_reported:
            self._stale_reported = True
            # ctx covers the whole package even on subset lints (the runner
            # guarantees it), so staleness is computed against the full tree
            stale = sorted(ctx.api_surface - collect_api_surface(ctx.modules))
            if stale:
                shown = ", ".join(stale[:5]) + ("…" if len(stale) > 5 else "")
                yield Finding(
                    rule=self.name, path=DEFAULT_MANIFEST_NAME, line=1, col=0,
                    message=f"{len(stale)} pinned symbol(s) no longer used by "
                            f"the package ({shown}) — the manifest must stay "
                            f"exact; regenerate with 'bin/dstpu-lint "
                            f"--update-api-surface'",
                    severity="warning")
        for symbol, node in symbol_sites(module):
            if symbol in ctx.api_surface:
                continue
            yield self.finding(
                module, node,
                f"jax symbol '{symbol}' is not pinned in {DEFAULT_MANIFEST_NAME} "
                f"— every external jax touch must be manifest-pinned so version "
                f"drift is a one-file diff; if this use is deliberate, "
                f"regenerate the manifest with 'bin/dstpu-lint "
                f"--update-api-surface' (and review the diff)")


# -------------------------------------------------------- sharding dataflow
# callables that PLACE a value with an explicit sharding; "place" is this
# repo's own pytree placement helper (inference/v2/tp.py)
PLACEMENT_FNS = {"device_put", "make_array_from_callback", "place"}


def _is_sharding_expr(node: ast.AST, sharding_names: Set[str]) -> bool:
    if _is_sharding_factory(node):
        return True
    if isinstance(node, (ast.Name, ast.Attribute)):
        return ast.unparse(node) in sharding_names
    return False


def _placement_value(node: ast.AST, sharding_names: Set[str]) -> bool:
    """True when ``node`` is a call that places its input with an explicit
    sharding: ``jax.device_put(x, <sharding>)``, ``make_array_from_callback``
    with a sharding argument, or the repo's ``place(topology, tree, specs)``."""
    if not isinstance(node, ast.Call):
        return False
    t = _terminal_name(node.func)
    if t == "device_put":
        if len(node.args) >= 2 and _is_sharding_expr(node.args[1], sharding_names):
            return True
        return any(kw.arg in ("device", "sharding") and
                   _is_sharding_expr(kw.value, sharding_names)
                   for kw in node.keywords)
    if t == "make_array_from_callback":
        return any(_is_sharding_expr(a, sharding_names) for a in node.args) or \
            any(_is_sharding_expr(kw.value, sharding_names) for kw in node.keywords)
    # tp.py's place(topology, tree, specs) — the arity keeps unrelated
    # .place() helpers (a grid placement, a scheduler slot) from matching
    return t == "place" and len(node.args) >= 3


def _calls_of_name(scope: ast.AST, name: str, attribute: bool) -> Iterator[ast.Call]:
    """Calls of a local (``fn(...)``) or attribute (``self.fn(...)``) binding."""
    for node in ast.walk(scope):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if attribute and isinstance(f, ast.Attribute) and f.attr == name:
            yield node
        elif not attribute and isinstance(f, ast.Name) and f.id == name:
            yield node


def _scopes(tree: ast.Module) -> Iterator[ast.AST]:
    """The module itself plus every function def (each analyzed with its
    nested defs skipped, so one statement belongs to exactly one scope)."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _own_nodes(scope: ast.AST) -> Iterator[ast.AST]:
    """Walk ``scope`` without descending into nested function defs."""
    nested = {id(n) for n in ast.walk(scope)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) and n is not scope}
    yield from _walk_skipping(scope, nested)


# --------------------------------------------------------------------------
@register
class UnknownMeshAxis(Rule):
    name = "unknown-mesh-axis"
    description = ("PartitionSpec/in_specs/axis_names axis literal no declared "
                   "mesh defines (alias-aware: *_AXIS constants resolve "
                   "cross-module) — the typo class behind the PR 9 GSPMD "
                   "kv-projection miscompile; declared axes are pinned in the "
                   f"committed {DEFAULT_MESH_MANIFEST_NAME} manifest "
                   "(regenerate after a deliberate mesh change with "
                   "bin/dstpu-lint --update-mesh-manifest)")

    def __init__(self):
        self._missing_reported = False
        self._sync_reported = False

    def check(self, module, ctx):
        info = ctx.mesh_model.module_info(module)
        uses = [u for site in info.spec_sites for u in site.axis_uses()]
        uses += list(info.axis_name_uses)
        declared = ctx.mesh_model.declared_axis_names()
        if ctx.mesh_manifest is None:
            if uses and not self._missing_reported:
                self._missing_reported = True
                # the three manifest-level findings share rule+path+line, so
                # each carries a distinct snippet: fingerprints must differ or
                # one baseline entry / SARIF upload dedup swallows another
                yield Finding(
                    rule=self.name, path=DEFAULT_MESH_MANIFEST_NAME, line=1, col=0,
                    snippet="mesh-manifest-missing",
                    message=f"mesh manifest {DEFAULT_MESH_MANIFEST_NAME} does not "
                            f"exist — generate it once with 'bin/dstpu-lint "
                            f"--update-mesh-manifest' and commit it; without it "
                            f"the tree's mesh axis names are unpinned and an "
                            f"axis typo lands as a silent GSPMD miscompile "
                            f"instead of a lint error")
            return
        if not self._sync_reported:
            self._sync_reported = True
            unpinned = sorted(declared - ctx.mesh_manifest)
            if unpinned:
                yield Finding(
                    rule=self.name, path=DEFAULT_MESH_MANIFEST_NAME, line=1, col=0,
                    snippet="mesh-manifest-unpinned",
                    message=f"mesh axis(es) declared in the tree but not pinned "
                            f"in {DEFAULT_MESH_MANIFEST_NAME}: "
                            f"{', '.join(unpinned)} — after a deliberate mesh "
                            f"change regenerate with 'bin/dstpu-lint "
                            f"--update-mesh-manifest' (and review the diff)")
            stale = sorted(ctx.mesh_manifest - declared)
            if stale:
                yield Finding(
                    rule=self.name, path=DEFAULT_MESH_MANIFEST_NAME, line=1, col=0,
                    snippet="mesh-manifest-stale",
                    message=f"{len(stale)} pinned mesh axis(es) no longer "
                            f"declared anywhere in the tree "
                            f"({', '.join(stale)}) — the manifest must stay "
                            f"exact; regenerate with 'bin/dstpu-lint "
                            f"--update-mesh-manifest'",
                    severity="warning")
        # module-local declarations count too: an ad-hoc Mesh in a script or
        # bench file validates that file's own specs without entering the
        # package manifest
        known = declared | ctx.mesh_manifest | set(info.declarations)
        for u in uses:
            if u.axis == UNRESOLVED or u.axis in known:
                continue
            via = f" (via constant {u.via})" if u.via else ""
            yield self.finding(
                module, u.node,
                f"mesh axis '{u.axis}'{via} is not declared by any Mesh/"
                f"make_mesh construction or *_AXIS constant "
                f"(declared: {', '.join(sorted(known)) or 'none'}) — an axis "
                f"typo in a PartitionSpec does not error at trace time, it "
                f"silently changes the GSPMD partitioning (the PR 9 "
                f"kv-projection miscompile class); fix the spelling, or "
                f"declare the axis and re-pin with 'bin/dstpu-lint "
                f"--update-mesh-manifest'")


# --------------------------------------------------------------------------
@register
class ShardingDroppedAtBoundary(Rule):
    name = "sharding-dropped-at-boundary"
    description = ("NamedSharding-placed value flowing into np.asarray/"
                   "jax.device_get/jnp.asarray-without-device or a fresh "
                   "un-annotated device_put — the placement silently collapses "
                   "to a single device (the exact gap keeping DeviceBatchState "
                   "off the multichip fast path)")

    def check(self, module, ctx):
        sharding_names = ctx.mesh_model.module_info(module).sharding_var_names
        for scope in _scopes(module.tree):
            yield from self._check_locals(module, scope, sharding_names)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_class_attrs(module, node, sharding_names)

    def _drop_call(self, call: ast.Call):
        """(dropped-arg node, message) when ``call`` collapses a placement."""
        f = call.func
        if not (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and call.args):
            return None
        owner = f.value.id
        if f.attr in ("asarray", "array") and owner in NP_MODULE_NAMES:
            return call.args[0], f"{owner}.{f.attr}() pulls the placed value to host"
        if f.attr == "device_get" and owner == "jax":
            return call.args[0], "jax.device_get() pulls the placed value to host"
        has_device = any(kw.arg in ("device", "sharding") for kw in call.keywords)
        if f.attr == "asarray" and owner in JNP_MODULE_NAMES and not has_device:
            return call.args[0], ("jnp.asarray() without device= re-commits the "
                                  "value without its NamedSharding")
        if f.attr == "device_put" and owner == "jax" and len(call.args) == 1 \
                and not has_device:
            return call.args[0], ("jax.device_put() without a sharding commits "
                                  "the value to the default single device")
        return None

    def _finding(self, module, node, expr, how, placed_line):
        return self.finding(
            module, node,
            f"{how}: '{expr}' was placed with a NamedSharding (line "
            f"{placed_line}) and this boundary silently collapses it to "
            f"single-device — under a TP/DP mesh the next sharded computation "
            f"either gathers the world or miscompiles (the DeviceBatchState "
            f"commit-path gap that forces tp>1 serving onto the slow path); "
            f"carry the sharding across the boundary (device=..., an explicit "
            f"NamedSharding arg) or suppress with a reason if this collapse "
            f"is deliberate (checkpoint-save host serialization, init-time "
            f"staging)")

    def _check_locals(self, module, scope, sharding_names):
        """Linear scan: placement stores, unbinding stores, drop calls —
        within one line drops (loads of the old value) order before stores.
        ANY store of a name (for target, with-as, tuple unpack) unbinds it:
        a placed name reused as a loop variable is no longer the placement."""
        events = []
        modeled: Set[int] = set()
        for node in _own_nodes(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                modeled.add(id(node.targets[0]))
                kind = "place" if _placement_value(node.value, sharding_names) \
                    else "unbind"
                events.append((node.lineno, kind, node.targets[0].id, node))
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, (ast.Store, ast.Del)) and \
                    id(node) not in modeled:
                # parents precede children in the walk, so modeled targets
                # are already excluded here
                events.append((node.lineno, "unbind", node.id, node))
            elif isinstance(node, ast.Call):
                hit = self._drop_call(node)
                if hit is not None:
                    arg, how = hit
                    if isinstance(arg, ast.Name):
                        events.append((node.lineno, "drop", arg.id, (node, how)))
        events.sort(key=lambda e: (e[0], 0 if e[1] == "drop" else 1))
        placed: Dict[str, int] = {}
        for line, kind, name, payload in events:
            if kind == "place":
                placed[name] = line
            elif kind == "unbind":
                placed.pop(name, None)
            elif name in placed:
                node, how = payload
                yield self._finding(module, node, name, how, placed[name])

    def _check_class_attrs(self, module, cls, sharding_names):
        """Cross-method attribute flow: ``self.x`` placed in one method (the
        __init__-placement / step-drop split is where the real serving bug
        lives) and collapsed in another — no line ordering, the placement is
        the attribute's steady state."""
        placed: Dict[str, int] = {}
        for node in ast.walk(cls):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Attribute) and \
                    _placement_value(node.value, sharding_names):
                placed.setdefault(ast.unparse(node.targets[0]), node.lineno)
        if not placed:
            return
        for node in ast.walk(cls):
            if not isinstance(node, ast.Call):
                continue
            hit = self._drop_call(node)
            if hit is None:
                continue
            arg, how = hit
            if not isinstance(arg, ast.Attribute):
                continue
            expr = ast.unparse(arg)
            if expr in placed:
                yield self._finding(module, node, expr, how, placed[expr])


# --------------------------------------------------------------------------
@register
class SpecRankMismatch(Rule):
    name = "spec-rank-mismatch"
    description = ("PartitionSpec with more dimensions than the annotated "
                   "array's statically-known rank — over-ranked specs are a "
                   "runtime error on the first real multichip mesh, long "
                   "after single-chip tests went green")

    def check(self, module, ctx):
        info = ctx.mesh_model.module_info(module)
        site_rank = {id(s.call): s.rank for s in info.spec_sites}
        for scope in _scopes(module.tree):
            yield from self._check_scope(module, scope, site_rank)

    def _spec_rank(self, expr, site_rank, spec_vars, shard_vars):
        """Rank of a spec/sharding expression, else None."""
        if isinstance(expr, ast.Call):
            t = _terminal_name(expr.func)
            if t == "NamedSharding" and len(expr.args) >= 2:
                return self._spec_rank(expr.args[1], site_rank, spec_vars,
                                       shard_vars)
            if id(expr) in site_rank:
                return site_rank[id(expr)]
            return None
        if isinstance(expr, ast.Name):
            if expr.id in spec_vars:
                return spec_vars[expr.id]
            return shard_vars.get(expr.id)
        return None

    def _check_scope(self, module, scope, site_rank):
        value_rank: Dict[str, int] = {}
        spec_vars: Dict[str, int] = {}
        shard_vars: Dict[str, int] = {}
        # ONE source-ordered linear scan (the tree walk is not source-ordered):
        # spec-variable chains resolve, and a rebind to an unknown-rank value
        # INVALIDATES the name instead of leaving a stale "provable" rank —
        # within a line, calls order before stores (args evaluate first).
        # ANY other store of the name (for target, with-as, tuple unpack,
        # augmented assign) also invalidates: kinds call=0, invalidate=1,
        # modeled-assign=2
        events = []
        modeled: Set[int] = set()
        for node in _own_nodes(scope):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                modeled.add(id(node.targets[0]))
                events.append((node.lineno, 2, node))
            elif isinstance(node, ast.Name) and \
                    isinstance(node.ctx, (ast.Store, ast.Del)) and \
                    id(node) not in modeled:
                # parents precede children in the walk, so a modeled assign's
                # own target Name is already excluded here
                events.append((node.lineno, 1, node))
            elif isinstance(node, ast.Call) and \
                    _terminal_name(node.func) in ("device_put",
                                                  "make_array_from_callback") \
                    and len(node.args) >= 2:
                events.append((node.lineno, 0, node))
        for _, kind, node in sorted(events, key=lambda e: (e[0], e[1])):
            if kind == 1:
                for table in (value_rank, spec_vars, shard_vars):
                    table.pop(node.id, None)
                continue
            if kind == 2:
                tgt, val = node.targets[0].id, node.value
                for table in (value_rank, spec_vars, shard_vars):
                    table.pop(tgt, None)
                rank = creation_rank(val)
                if rank is not None:
                    value_rank[tgt] = rank
                    continue
                srank = self._spec_rank(val, site_rank, spec_vars, shard_vars)
                if srank is not None:
                    if isinstance(val, ast.Call) and \
                            _terminal_name(val.func) == "NamedSharding":
                        shard_vars[tgt] = srank
                    else:
                        spec_vars[tgt] = srank
                continue
            if _terminal_name(node.func) == "device_put":
                vrank = self._value_rank(node.args[0], value_rank)
            else:
                vrank = shape_rank(node.args[0])
            srank = self._spec_rank(node.args[1], site_rank, spec_vars,
                                    shard_vars)
            if vrank is None or srank is None or srank <= vrank:
                continue
            yield self.finding(
                module, node,
                f"PartitionSpec names {srank} dimension(s) but the annotated "
                f"array is provably rank {vrank} — an over-ranked spec is "
                f"rejected at placement time on a real multichip mesh (and "
                f"nothing catches it on the single-device CPU lane); trim the "
                f"spec — trailing dimensions replicate implicitly")

    def _value_rank(self, expr, value_rank) -> Optional[int]:
        rank = creation_rank(expr)
        if rank is not None:
            return rank
        if isinstance(expr, ast.Name):
            return value_rank.get(expr.id)
        return None


# --------------------------------------------------------------------------
@register
class RecompileRisk(Rule):
    name = "recompile-risk"
    description = ("request/batch-cardinality expression (len/sum of runtime "
                   "state) reaching a jit static argument or a padded-shape "
                   "array construction under inference/v2/ without passing "
                   "through the bucketing helpers — each distinct value mints "
                   "a fresh compiled program, breaking the zero-warm-"
                   "recompiles invariant the fastpath smoke only observes "
                   "after the fact")

    V2_PATH_FRAGMENT = "inference/v2/"
    DYNAMIC_CALLS = {"len", "sum"}
    # the sanctioned cardinality->shape launders: one shared pow2 bucketer +
    # the engine's table-width stepper (engine_v2/fastpath)
    SANCTIFIERS = {"round_up_pow2", "_bucket", "_stepped_width"}
    CREATION_OWNERS = NP_MODULE_NAMES | JNP_MODULE_NAMES
    CREATION_FNS = MESH_CREATION_FNS  # one definition of "array creation"

    def check(self, module, ctx):
        if self.V2_PATH_FRAGMENT not in module.relpath.replace("\\", "/"):
            return
        yield from self._check_static_args(module, ctx)
        yield from self._check_shape_constructions(module)

    # ---- leg a: static jit arguments
    def _check_static_args(self, module, ctx):
        for site in ctx.static_jit_sites(module):
            offset = 0
            if site.binding == "local":
                fn = enclosing(site.jit_call, ast.FunctionDef, ast.AsyncFunctionDef)
                scope = fn if fn is not None else module.tree
                calls = _calls_of_name(scope, site.name, attribute=False)
            elif site.binding == "attribute":
                calls = _calls_of_name(module.tree, site.name, attribute=True)
            elif site.binding == "decorated":
                # @jax.jit(...)-decorated def: calls bind the decorated NAME —
                # bare for a module-level function, self.<name> for a method
                # (where bound calls shift static_argnums left past `self`)
                is_method = enclosing(site.fn_node, ast.ClassDef) is not None
                offset = 1 if is_method else 0
                calls = _calls_of_name(module.tree, site.name,
                                       attribute=is_method)
            else:
                continue
            for call in calls:
                if call is site.jit_call:
                    continue
                for pos in site.static_positions:
                    if pos - offset >= 0 and pos - offset < len(call.args):
                        yield from self._check_expr(module, call.args[pos - offset],
                                                    f"static position {pos}")
                for kw in call.keywords:
                    if kw.arg in site.static_names:
                        yield from self._check_expr(module, kw.value,
                                                    f"static argument '{kw.arg}'")

    def _check_expr(self, module, expr, where: str):
        dyn = self._dynamic_node(expr)
        if dyn is None:
            return
        yield self.finding(
            module, dyn,
            f"'{ast.unparse(dyn)}' — a runtime-cardinality value — reaches "
            f"{where} of a jitted callable: every distinct value traces and "
            f"compiles a FRESH program, so steady-state serving recompiles "
            f"exactly when load shifts (the warm-recompile stall the fastpath "
            f"smoke's zero-warm-recompiles counter only observes after the "
            f"fact); bucket it through round_up_pow2/_bucket/_stepped_width "
            f"first, or make the argument traced")

    # ---- leg b: padded-shape constructions
    def _check_shape_constructions(self, module):
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute) and f.attr in self.CREATION_FNS
                    and isinstance(f.value, ast.Name)
                    and f.value.id in self.CREATION_OWNERS):
                continue
            dyn = self._dynamic_node(node.args[0])
            if dyn is None:
                continue
            yield self.finding(
                module, dyn,
                f"array shape derived from raw runtime cardinality "
                f"'{ast.unparse(dyn)}' — this buffer's shape changes with "
                f"load, and every new shape that reaches a jitted program is "
                f"a fresh compile; pad through round_up_pow2/_bucket/"
                f"_stepped_width (the shared bucketing primitives) instead")

    def _dynamic_node(self, expr) -> Optional[ast.AST]:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call) and \
                    _terminal_name(node.func) in self.DYNAMIC_CALLS and \
                    not self._sanctified(node, expr):
                return node
        return None

    def _sanctified(self, node, stop) -> bool:
        """A bucketing call strictly WITHIN the checked expression encloses
        ``node``.  The walk must not escape ``stop``: bucketing the RESULT of
        a jitted call (``round_up_pow2(fn(len(x)))``) does nothing for the
        static argument inside it."""
        if node is stop:
            return False
        cur = parent(node)
        while cur is not None:
            if isinstance(cur, ast.Call) and \
                    _terminal_name(cur.func) in self.SANCTIFIERS:
                return True
            if cur is stop:
                return False
            cur = parent(cur)
        return False


# --------------------------------------------------------------------------
@register
class DonationShardingMismatch(Rule):
    name = "donation-sharding-mismatch"
    description = ("argument donated to a jitted callable rebound to a "
                   "differently-specced placement — donation aliasing needs "
                   "identical shardings, so the in-place update silently "
                   "degrades to a copy (and a recompile)")

    def check(self, module, ctx):
        info = ctx.mesh_model.module_info(module)
        sharding_names = info.sharding_var_names
        site_key = {id(s.call): self._site_key(s) for s in info.spec_sites}
        donated = self._donated_exprs(module, ctx)
        if not donated:
            return
        for scope in _scopes(module.tree):
            yield from self._check_scope(module, scope, donated, site_key,
                                         sharding_names, attr_mode=False)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                yield from self._check_scope(module, node, donated, site_key,
                                             sharding_names, attr_mode=True)

    def _site_key(self, site):
        """Canonical identity of a spec: resolved axis tuples with trailing
        replicated dims stripped (PartitionSpec('x') == PartitionSpec('x',
        None)); unresolved entries fall back to textual identity."""
        if site.rank is None or any(u.axis == UNRESOLVED
                                    for u in site.axis_uses()):
            return ast.unparse(site.call)
        dims = [tuple(u.axis for u in dim) for dim in site.entries]
        while dims and not dims[-1]:
            dims.pop()
        return tuple(dims)

    def _donated_exprs(self, module, ctx) -> Set[str]:
        out: Set[str] = set()
        for site in ctx.donation_sites(module):
            if site.binding == "local":
                fn = enclosing(site.jit_call, ast.FunctionDef, ast.AsyncFunctionDef)
                scope = fn if fn is not None else module.tree
                attribute = False
            elif site.binding == "attribute":
                scope, attribute = module.tree, True
            else:
                continue
            for call in _calls_of_name(scope, site.name, attribute=attribute):
                for idx in site.donated:
                    if idx < len(call.args) and \
                            isinstance(call.args[idx], (ast.Name, ast.Attribute)):
                        out.add(ast.unparse(call.args[idx]))
        return out

    def _placement_key(self, value, site_key, sharding_names):
        """Spec identity of a placement expression, else None."""
        if not _placement_value(value, sharding_names):
            return None
        t = _terminal_name(value.func)
        if t == "device_put" and len(value.args) >= 2:
            return self._sharding_key(value.args[1], site_key)
        if t == "make_array_from_callback":
            for a in list(value.args) + [kw.value for kw in value.keywords]:
                key = self._sharding_key(a, site_key)
                if key is not None:
                    return key
        return None

    def _sharding_key(self, expr, site_key):
        if isinstance(expr, ast.Call):
            t = _terminal_name(expr.func)
            if t == "NamedSharding" and len(expr.args) >= 2:
                spec = expr.args[1]
                if isinstance(spec, ast.Call) and id(spec) in site_key:
                    return site_key[id(spec)]
                return None  # spec via a variable/attr: the model never guesses
            if t in SHARDING_FACTORY_METHODS and expr.args:
                spec = expr.args[0]
                if isinstance(spec, ast.Call) and id(spec) in site_key:
                    return site_key[id(spec)]
                return None
        return None

    def _check_scope(self, module, scope, donated, site_key, sharding_names,
                     attr_mode: bool):
        placements: Dict[str, Tuple[object, int]] = {}  # expr -> (key, line)
        nodes = ast.walk(scope) if attr_mode else _own_nodes(scope)
        # the tree walks are not source-ordered — sort, or the finding anchors
        # on the ORIGINAL placement and cites the rebind as "its placement"
        assigns = sorted(
            (n for n in nodes
             if isinstance(n, ast.Assign) and len(n.targets) == 1),
            key=lambda n: n.lineno)
        for node in assigns:
            tgt = node.targets[0]
            if attr_mode and not isinstance(tgt, ast.Attribute):
                continue
            if not attr_mode and not isinstance(tgt, ast.Name):
                continue
            expr = ast.unparse(tgt)
            if expr not in donated:
                continue
            key = self._placement_key(node.value, site_key, sharding_names)
            if key is None:
                continue
            prev = placements.get(expr)
            # flag only when BOTH specs resolved to canonical axis tuples —
            # a textual fallback key (unresolved spec site) can't prove a
            # genuine mismatch against a resolved one
            if prev is not None and prev[0] != key and \
                    isinstance(prev[0], tuple) and isinstance(key, tuple):
                yield self.finding(
                    module, node.value,
                    f"'{expr}' is DONATED to a jitted callable but rebound "
                    f"here with a different sharding than its placement at "
                    f"line {prev[1]} — XLA only aliases a donated buffer when "
                    f"the sharding matches the compiled expectation, so this "
                    f"donation silently degrades to a full copy (plus a "
                    f"recompile for the new layout); keep one spec for the "
                    f"donated value's lifetime or drop the donation")
            else:
                placements[expr] = (key, node.lineno)


# --------------------------------------------------------------------------
# Concurrency rules (threadcheck).  All five consume ctx.thread_model — the
# cross-module thread plane built by thread_model.py (thread roots,
# reachability, attribute events with held-lock sets, lock-order edges).
# The model is global but rules report per-module: each rule runs the
# project-wide analysis once per context and replays the findings that land
# in the module being linted.


class _ThreadRule(Rule):
    """Base: one project-wide analysis per ProjectContext, findings replayed
    per module (the runner lints module-by-module; a cross-module race must
    surface in whichever file is being checked)."""

    def check(self, module, ctx):
        if getattr(self, "_ctx_id", None) != id(ctx):
            self._ctx_id = id(ctx)
            self._by_module: Dict[str, List] = {}
            for relpath, node, message in self._analyze(ctx.thread_model):
                self._by_module.setdefault(relpath, []).append((node, message))
            for findings in self._by_module.values():
                findings.sort(key=lambda t: (t[0].lineno, t[0].col_offset))
        for node, message in self._by_module.get(module.relpath, []):
            yield self.finding(module, node, message)

    def _analyze(self, tm):
        raise NotImplementedError


def _root_phrase(tm, key) -> str:
    root = tm.root_for(key, ("thread", "handler", "collector"))
    return f" (thread-entered via {root.label})" if root is not None else ""


@register
class CrossThreadMutation(_ThreadRule):
    name = "cross-thread-mutation"
    description = ("shared attribute written from a thread-reachable function "
                   "AND written (or read-modify-written) from the main "
                   "serve/train path with no common lock — a lost-update race "
                   "outside the sanctioned single-writer atomic-publish "
                   "pattern (the AsyncCheckpointEngine._error class of bug)")

    def _analyze(self, tm):
        from .thread_model import AttrEvent  # noqa: F401 (documentation)
        for (owner, attr), events in sorted(tm.attr_events.items()):
            if tm.is_threadsafe_attr(owner, attr):
                continue
            evs = [e for e in events
                   if not e.in_init and tm.plane_of(e.func) != "signal"]
            thread = [e for e in evs if tm.plane_of(e.func) == "thread"]
            main = [e for e in evs if tm.plane_of(e.func) == "main"]
            if not thread or not main:
                continue
            reported: Set[int] = set()

            def report(e, other, why):
                if id(e.node) in reported:
                    return ()
                reported.add(id(e.node))
                return ((e.relpath, e.node,
                         f"'{owner}.{attr}' {why} — the other side is at "
                         f"{other.relpath}:{other.node.lineno}"
                         f"{_root_phrase(tm, e.func if tm.plane_of(e.func) == 'thread' else other.func)}; "
                         f"hold one common lock on both sides, or restructure "
                         f"so a single thread owns every write and publishes "
                         f"whole immutable values (the OpsCache pattern)"), )

            t_writes = [e for e in thread if e.kind in ("rebind", "augassign")]
            m_writes = [e for e in main if e.kind in ("rebind", "augassign")]
            for tw in t_writes:
                for mw in m_writes:
                    if tw.locks & mw.locks:
                        continue
                    yield from report(
                        tw, mw, "is written from a thread entrypoint here and "
                        "also written on the main plane with no common lock "
                        "(concurrent writes lose updates)")
                    yield from report(
                        mw, tw, "is written on the main plane here and also "
                        "written from a thread entrypoint with no common lock "
                        "(concurrent writes lose updates)")
            for aug, others in ((e, main) for e in thread
                                if e.kind == "augassign"):
                for o in others:
                    if aug.locks & o.locks:
                        continue
                    yield from report(
                        aug, o, "is read-modify-written (+=/-=) from a thread "
                        "entrypoint here while the main plane touches it — "
                        "augmented assignment is not atomic even under the GIL")
            for aug, others in ((e, thread) for e in main
                                if e.kind == "augassign"):
                for o in others:
                    if aug.locks & o.locks:
                        continue
                    yield from report(
                        aug, o, "is read-modify-written (+=/-=) on the main "
                        "plane here while a thread entrypoint touches it — "
                        "augmented assignment is not atomic even under the GIL")


@register
class AtomicPublish(_ThreadRule):
    name = "atomic-publish"
    description = ("cross-thread published state must be a whole-attribute "
                   "rebind of an immutable value: on a class instances of "
                   "which are touched from BOTH the thread plane and the main "
                   "plane, in-place container mutation / subscript stores / "
                   "augmented assignment on an unlocked attribute is a "
                   "finding — this makes the OpsCache \"GIL-atomic whole-"
                   "string assignment\" convention a checked contract")

    def _analyze(self, tm):
        from .thread_model import INPLACE_KINDS, is_mutable_value
        planes_by_class: Dict[str, Set[str]] = {}
        for (owner, _attr), events in tm.attr_events.items():
            for e in events:
                if not e.in_init:
                    planes_by_class.setdefault(owner, set()).add(
                        tm.plane_of(e.func))
        shared = {c for c, planes in planes_by_class.items()
                  if "thread" in planes and "main" in planes}
        for (owner, attr), events in sorted(tm.attr_events.items()):
            if owner not in shared or tm.is_threadsafe_attr(owner, attr):
                continue
            evs = [e for e in events
                   if not e.in_init and tm.plane_of(e.func) != "signal"]
            for e in evs:
                other = [o for o in evs
                         if tm.plane_of(o.func) != tm.plane_of(e.func)]
                # lock-disciplined attrs are exempt: the event holds a lock
                # every other-plane access of this attr also holds
                if e.locks and all(e.locks & o.locks for o in other):
                    continue
                if e.kind == "augassign" and not other:
                    # (with other-plane access this is cross-thread-mutation's
                    # finding; here the attr itself never crosses, but it
                    # rides on an object that DOES — same publish contract)
                    yield (e.relpath, e.node,
                           f"'{owner}.{attr}' is read-modify-written (+=) on "
                           f"an instance shared across threads — not an "
                           f"atomic publish; rebind a complete immutable "
                           f"value instead, or move the counter off the "
                           f"shared object")
                elif e.kind in INPLACE_KINDS:
                    yield (e.relpath, e.node,
                           f"in-place mutation of '{owner}.{attr}' on an "
                           f"instance shared across threads — a concurrent "
                           f"reader can observe the half-applied mutation; "
                           f"the atomic-publish contract requires building "
                           f"the new value privately and rebinding the whole "
                           f"attribute (one GIL-atomic pointer store)")
                elif e.kind == "rebind" and is_mutable_value(e.value) and \
                        any(o.kind == "read" for o in other):
                    yield (e.relpath, e.node,
                           f"'{owner}.{attr}' publishes a freshly-built "
                           f"MUTABLE container to a cross-thread reader — "
                           f"later in-place edits through this attribute "
                           f"race those readers; publish an immutable "
                           f"rendering (str/bytes/tuple) instead")


@register
class HandlerHoldsEngine(_ThreadRule):
    name = "handler-holds-engine"
    description = ("ops handlers, thread targets, collector callbacks and "
                   "signal handlers may not capture or reach an engine/"
                   "manager reference — the scrape-safety contract: a "
                   "thread-entered function touching the engine can sync a "
                   "device or race a step; hand it pre-rendered host state "
                   "(the OpsCache pattern) instead")

    KIND_LABEL = {"thread": "thread target", "handler": "HTTP handler",
                  "collector": "collector callback", "signal": "signal handler"}

    def _analyze(self, tm):
        done: Set[Tuple] = set()
        for root in tm.roots:
            key = root.key
            if key is None or key not in tm.functions or \
                    (key, root.kind) in done:
                continue
            done.add((key, root.kind))
            fn = tm.functions[key]
            label = self.KIND_LABEL.get(root.kind, root.kind)
            refs = tm.engine_refs.get(key)
            if refs:
                node, cls = refs[0]
                yield (fn.relpath, node,
                       f"{label} '{fn.key[1]}' holds a reference to "
                       f"engine/manager class '{cls}' — thread-entered code "
                       f"must not capture or reach the engine (it could sync "
                       f"a device or race a step); pass pre-rendered host "
                       f"state instead")
                continue
            hit = self._reachable_engine_ref(tm, key)
            if hit is not None:
                hk, cls = hit
                yield (fn.relpath, fn.node,
                       f"{label} '{fn.key[1]}' reaches engine/manager class "
                       f"'{cls}' through '{hk[1]}' ({hk[0]}) — thread-entered "
                       f"code must not reach the engine; pass pre-rendered "
                       f"host state instead")

    def _reachable_engine_ref(self, tm, key):
        seen, todo = set(), sorted(tm.functions[key].resolved_callees)
        while todo:
            k = todo.pop(0)
            if k in seen or k not in tm.functions:
                continue
            seen.add(k)
            refs = tm.engine_refs.get(k)
            if refs:
                return k, refs[0][1]
            todo.extend(sorted(tm.functions[k].resolved_callees))
        return None


@register
class BlockingUnderLock(_ThreadRule):
    name = "blocking-under-lock"
    description = ("sleep / thread-or-queue join / fsync / subprocess / "
                   "collective entry / device sync while holding a lock — "
                   "every other thread contending for that lock stalls for "
                   "the full blocking duration (and a collective under a "
                   "lock deadlocks the fleet if any peer needs the lock to "
                   "reach its own collective)")

    def _analyze(self, tm):
        for bc in tm.blocking_calls:
            locks = ", ".join(sorted(bc.locks))
            yield (bc.relpath, bc.node,
                   f"blocking call ({bc.what}) while holding lock(s) "
                   f"[{locks}] — move the blocking work outside the critical "
                   f"section (compute under the lock, block outside it)")


@register
class LockOrder(_ThreadRule):
    name = "lock-order"
    description = ("inconsistent lock-acquisition order across the project — "
                   "somewhere lock A is taken under lock B while elsewhere B "
                   "is taken under A: the classic ABBA deadlock; pick one "
                   "global order (document it where the locks are defined)")

    def _analyze(self, tm):
        edges: Dict[Tuple[str, str], List] = {}
        for e in tm.lock_edges:
            edges.setdefault((e.outer, e.inner), []).append(e)
        seen_pairs: Set[frozenset] = set()
        for (a, b), sites in sorted(edges.items()):
            if a == b or (b, a) not in edges:
                continue
            pair = frozenset((a, b))
            if pair in seen_pairs:
                continue
            seen_pairs.add(pair)
            rev = edges[(b, a)]
            for e in sites:
                yield (e.relpath, e.node,
                       f"lock '{b}' acquired while holding '{a}' here, but "
                       f"{rev[0].relpath}:{rev[0].node.lineno} acquires "
                       f"'{a}' while holding '{b}' — inconsistent ordering "
                       f"is an ABBA deadlock waiting for contention; pick "
                       f"one project-wide order")
            for e in rev:
                yield (e.relpath, e.node,
                       f"lock '{a}' acquired while holding '{b}' here, but "
                       f"{sites[0].relpath}:{sites[0].node.lineno} acquires "
                       f"'{b}' while holding '{a}' — inconsistent ordering "
                       f"is an ABBA deadlock waiting for contention; pick "
                       f"one project-wide order")


def build_rules(enabled: Optional[Iterable[str]] = None,
                disabled: Iterable[str] = ()) -> List[Rule]:
    names = list(RULES) if enabled is None else list(enabled)
    unknown = [n for n in list(names) + list(disabled) if n not in RULES]
    if unknown:
        raise KeyError(f"unknown rule(s): {', '.join(unknown)}; known: {', '.join(RULES)}")
    return [RULES[n]() for n in names if n not in set(disabled)]
