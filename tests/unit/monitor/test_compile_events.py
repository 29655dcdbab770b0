"""The set-up account (ISSUE 36): JAX's own compile events as rows by program
name, outermost spans only, one listener a process; the ``CompileLedger``
joins them by name; ``monitor/perf.py`` still imports neither jax nor numpy."""

import ast
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.monitor import compile_events
from deepspeed_tpu.monitor.compile_events import ENGINE_INIT, LOAD, LOWER, TRACE, Account
from deepspeed_tpu.monitor.exposition import parse_exposition, render
from deepspeed_tpu.monitor.metrics import MetricsRegistry, populate_from_engine
from deepspeed_tpu.monitor.perf import CompileLedger

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture
def account():
    return compile_events.install()


def named(name, body=lambda x: jnp.sin(x) * 2 + 1):
    """A fresh function under ``name``: no trace cache of an earlier test hits."""
    def fn(x):
        return body(x)
    fn.__name__ = name
    return fn


def stages_of(account, name):
    """The stages recorded under ``name``: a test's names are its own, so nothing earlier carries
    one (an index into ``rows()`` would not do: a thread keeps its newest ``MAX_ROWS`` rows, and in
    a worker that has built hundreds of programs before this file the list no longer grows)."""
    return [row.stage for row in account.rows() if row.program == name]


# ------------------------------------------------------------- JAX's events
@pytest.mark.parametrize("how", ["call", "lower_compile"])
def test_a_named_program_gives_one_row_a_stage_under_its_one_name(account, how):
    name = f"fwd_n4_t1_b2_{how}"
    jitted, x = jax.jit(named(name)), jnp.ones(8)
    if how == "call":
        jitted(x)
    else:
        jitted.lower(jax.ShapeDtypeStruct((8, ), jnp.float32)).compile()
    assert stages_of(account, name) == [TRACE, LOWER, LOAD]
    program = account.by_program()[name]
    assert (program["traces"], program["lowers"], program["loads"]) == (1, 1, 1)
    assert program["trace_s"] > 0 and program["lower_s"] > 0 and program["load_s"] > 0
    rows = [r for r in account.rows() if r.program == name]
    assert [r.end for r in rows] == sorted(r.end for r in rows)
    assert all(r.start < r.end and r.thread == threading.get_ident() for r in rows)
    if how == "call":  # a cached dispatch fires nothing: a warm step pays nothing
        arrivals = account.totals()["events"]
        jitted(x)
        assert account.totals()["events"] == arrivals
        assert stages_of(account, name) == [TRACE, LOWER, LOAD]


@pytest.mark.parametrize("spelling", ["fwd_n32_t1_b20", "jit(fwd_n32_t1_b20)", "jit_fwd_n32_t1_b20"])
def test_every_spelling_of_a_program_is_one_name(spelling):
    assert compile_events.program_name(spelling) == "fwd_n32_t1_b20"


def test_operators_and_nested_jits_add_inner_traces_and_no_seconds(account):
    inner = jax.jit(named("nested_inner"))
    outer = named("nested_outer", lambda x: jnp.inner(inner(x) + 1, inner(x * 2)))
    x = jnp.ones(8)
    before = account.totals()
    jax.jit(outer)(x)
    after, programs = account.totals(), account.by_program()
    # sin, multiply, add (inside the inner jit, traced once) + the inner jit +
    # add, multiply, inner: counted on the outer row, whose seconds hold theirs
    assert programs["nested_outer"]["inner_traces"] >= 6
    assert "nested_inner" not in programs
    assert after["traces"] - before["traces"] == 1
    trace_row = next(r for r in account.rows() if r.program == "nested_outer" and r.stage == TRACE)
    assert after["trace_s"] - before["trace_s"] == pytest.approx(trace_row.end - trace_row.start)
    assert after["inner_traces"] - before["inner_traces"] == trace_row.inner_traces


def test_install_twice_registers_once_and_counts_once(account):
    assert compile_events.install() is account is compile_events.ACCOUNT
    x = jnp.ones(4)  # its own eager program is built before the count starts
    before = account.totals()
    jax.jit(named("counted_once"))(x)
    after = account.totals()
    assert (after["traces"] - before["traces"], after["loads"] - before["loads"]) == (1, 1)
    assert stages_of(account, "counted_once") == [TRACE, LOWER, LOAD]


# --------------------------------------------------- the account, fed by hand
def test_an_arriving_span_swallows_what_started_inside_it_on_its_thread():
    acc = Account()
    acc.arrive(TRACE, "earlier", 0.0, 1.0)
    acc.arrive(TRACE, "multiply", 2.1, 2.2)
    acc.arrive(TRACE, "inner_jit", 2.3, 2.6)  # itself holds two operator traces
    acc.rows()  # a read between arrivals changes nothing
    acc.arrive(TRACE, "fwd_n4_t1_b2", 2.0, 3.0)
    acc.arrive(LOWER, "jit(fwd_n4_t1_b2)", 3.0, 3.5)
    rows = acc.rows()
    assert [(r.stage, r.program, r.inner_traces) for r in rows] == [
        (TRACE, "earlier", 0), (TRACE, "fwd_n4_t1_b2", 2), (LOWER, "fwd_n4_t1_b2", 0)]
    totals = acc.totals()
    assert totals["trace_s"] == pytest.approx(2.0) and totals["lower_s"] == pytest.approx(0.5)
    assert (totals["traces"], totals["inner_traces"], totals["events"]) == (2, 2, 5)
    assert acc.by_program()["fwd_n4_t1_b2"]["trace_s"] == pytest.approx(1.0)


def test_totals_and_programs_cut_at_until():
    acc = Account()
    for i, name in enumerate(["a", "b", "c"]):
        acc.arrive(TRACE, name, 10.0 * i, 10.0 * i + 1)
        acc.arrive(LOAD, f"jit({name})", 10.0 * i + 1, 10.0 * i + 3)
    assert acc.totals()["load_s"] == pytest.approx(6.0)
    cut = acc.totals(until=13.0)  # b's load ends at 13: at or before counts
    assert (cut["traces"], cut["loads"]) == (2, 2) and cut["load_s"] == pytest.approx(4.0)
    assert sorted(acc.by_program(until=12.9)) == ["a", "b"]
    assert acc.by_program(until=12.9)["b"]["loads"] == 0
    assert [r.program for r in acc.rows(until=1.0)] == ["a"]
    assert acc.totals()["loads"] == 3  # a cut read leaves the whole as it was


def test_the_row_list_is_bounded_and_the_sums_outlive_it(monkeypatch):
    monkeypatch.setattr(compile_events, "MAX_ROWS", 4)
    acc = Account()
    for i in range(10):
        acc.arrive(LOAD, f"jit(p{i % 2})", float(i), i + 0.5)
    assert len(acc.rows()) == 4 and acc.totals()["dropped"] == 6
    assert acc.totals()["loads"] == 10 and acc.totals()["load_s"] == pytest.approx(5.0)
    assert acc.by_program()["p0"]["loads"] == 5


def test_an_engine_init_span_covers_rows_and_swallows_none():
    acc = Account()
    acc.arrive(TRACE, "zeros", 1.0, 1.2)
    acc.arrive(LOAD, "jit(zeros)", 1.2, 1.5)
    acc.span(ENGINE_INIT, "InferenceEngineV2", 0.5, 2.0)
    acc.arrive(TRACE, "fwd", 2.5, 3.0)  # after the span: the span is not inside it
    assert [r.stage for r in acc.rows()] == [TRACE, LOAD, ENGINE_INIT, TRACE]
    totals = acc.totals()
    assert totals["engine_init_s"] == pytest.approx(1.5) and totals["traces"] == 2


def test_each_threads_cache_answers_stay_on_its_own_load_row():
    acc = Account()
    # JAX says "cache_misses" only where it then writes an entry: a load is a miss by having no hit
    hit, miss = "/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses"
    took = "/jax/compilation_cache/cache_retrieval_time_sec"
    gate = threading.Barrier(2, timeout=30)

    def compile_on_a_thread(name, answer):
        gate.wait()          # both threads are inside their backend stage at once
        acc.on_event(answer)
        if answer == hit:
            acc.on_duration(took, 0.25)
        gate.wait()
        # (a stage of a minute: it began before the cache answered, however late this thread ran)
        acc.on_duration("/jax/core/compile/backend_compile_duration", 60.0, fun_name=name)

    threads = [threading.Thread(target=compile_on_a_thread, args=args)
               for args in (("jit(was_cached)", hit), ("jit(was_compiled)", miss))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    rows = {r.program: r for r in acc.rows()}
    assert rows["was_cached"].cache_hit is True and rows["was_cached"].retrieval_s == 0.25
    assert rows["was_compiled"].cache_hit is False and rows["was_compiled"].retrieval_s == 0.0
    assert rows["was_cached"].thread != rows["was_compiled"].thread
    totals = acc.totals()
    assert (totals["cache_hits"], totals["cache_misses"], totals["retrieval_s"]) == (1, 1, 0.25)
    # an answer older than the load it would ride on is not that load's
    acc.on_event(hit)
    acc.arrive(LOAD, "jit(later)", acc.rows()[-1].end + 1e6, acc.rows()[-1].end + 2e6)
    assert acc.rows()[-1].cache_hit is False
    assert (acc.totals()["loads"], acc.totals()["cache_hits"], acc.totals()["cache_misses"]) == (3, 1, 2)


def test_concurrent_arrivals_lose_no_row():
    import sys
    acc, per_thread, n_threads = Account(), 2000, 8
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(per_thread):
                acc.arrive(LOAD, f"jit(t{k})", 2.0 * i, 2.0 * i + 1)
                if i % 500 == 0:
                    acc.totals()
        threads = [threading.Thread(target=work, args=(k, )) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    totals = acc.totals()
    assert totals["loads"] == totals["events"] == per_thread * n_threads
    assert totals["load_s"] == pytest.approx(per_thread * n_threads)
    assert all(p["loads"] == per_thread for p in acc.by_program().values())


# ------------------------------------------------------- the ledger's join
def test_the_ledger_joins_the_account_by_program_name():
    acc = Account()
    led = CompileLedger(events=acc)
    led.record("fwd", (4, 1, 2), wall_s=0.5, prewarmed=True, name="fwd_n4_t1_b2")
    led.record("burst", (4, 5), name="burst_n4_k5")
    led.record("pick", 4, name="pick_n4")  # recorded, never built: no rows, no seconds
    acc.arrive(TRACE, "fwd_n4_t1_b2", 0.0, 0.2)
    acc.arrive(LOWER, "jit(fwd_n4_t1_b2)", 0.2, 0.5)
    acc.arrive(LOAD, "jit(fwd_n4_t1_b2)", 0.5, 1.5)  # no hit inside it: an XLA compile
    acc.arrive(TRACE, "multiply", 2.0, 2.1)
    acc.arrive(TRACE, "burst_n4_k5", 1.9, 2.9)
    acc.arrive(LOAD, "jit(convert_element_type)", 3.0, 9.0)  # not the ledger's: left out
    snap = led.snapshot()
    assert snap["compile_wall_s"] == 0.5 and snap["total"] == 3  # the stopwatch keeps its meaning
    assert (snap["trace_s"], snap["lower_s"], snap["load_s"]) == (1.2, 0.3, 1.0)
    assert (snap["cache_hits"], snap["cache_misses"]) == (0, 1)
    assert [(p["name"], p["site"], p["class"], p["seconds"], p["inner_traces"])
            for p in snap["slowest"]] == [("fwd_n4_t1_b2", "fwd", "prewarmed", 1.5, 0),
                                          ("burst_n4_k5", "burst", "cold", 1.0, 1)]
    assert led.stage_totals() == {k: snap[k] for k in ("trace_s", "lower_s", "load_s",
                                                       "cache_hits", "cache_misses")}
    plain = CompileLedger()
    plain.record("fwd", (4, 1, 2), name="fwd_n4_t1_b2")
    assert plain.stage_totals() == {} and "slowest" not in plain.snapshot()


def test_a_warm_recompiles_flight_recorder_line_carries_the_seconds():
    class Tracer:
        lines = []

        def event(self, name, **fields):
            self.lines.append((name, fields))

    acc, tracer = Account(), Tracer()
    led = CompileLedger(tracer=tracer, events=acc)
    led.record("fwd", (4, 1, 2), name="fwd_n4_t1_b2")
    acc.arrive(TRACE, "fwd_n4_t1_b2", 0.0, 0.25)
    acc.arrive(LOAD, "jit(fwd_n4_t1_b2)", 0.25, 1.0)
    assert led.record("fwd", (4, 1, 2), name="fwd_n4_t1_b2") == "warm"
    (name, fields), = tracer.lines
    assert name == "warm_recompile" and fields["program"] == "fwd_n4_t1_b2"
    assert fields["builds"] == 2 and (fields["trace_s"], fields["load_s"]) == (0.25, 0.75)


def tiny_engine():
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=1, heads=2, kv_heads=2, seq=256)
    return InferenceEngineV2(llama, cfg, llama.init_params(cfg, jax.random.PRNGKey(2)),
                             config={"dtype": "float32"}, num_blocks=32, block_size=8,
                             max_blocks_per_seq=8, token_budget=32, max_seqs_per_step=4)


def test_every_program_a_tiny_engine_records_has_rows_under_its_own_name(account):
    inits = sum(r.stage == ENGINE_INIT for r in account.rows())
    eng = tiny_engine()
    assert sum(r.stage == ENGINE_INIT for r in account.rows()) == inits + 1
    eng.generate([[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12]], max_new_tokens=4)
    names = {e["name"] for e in eng.ledger.events}
    assert names and not any("lambda" in n for n in names)
    programs = account.by_program()
    for name in names:
        assert programs[name]["traces"] and programs[name]["lowers"] and programs[name]["loads"], name
    snap = eng.health()["perf"]["compile_ledger"]
    assert {p["name"] for p in snap["slowest"]} <= names and snap["slowest"][0]["seconds"] > 0
    assert snap["slowest"] == sorted(snap["slowest"], key=lambda p: (-p["seconds"], p["name"]))
    assert snap["trace_s"] > 0 and snap["lower_s"] > 0 and snap["load_s"] > 0
    # what the parent's ledger and counter read, they read: the stopwatch of the
    # seams that compile ahead, one count a record
    assert eng.counters.compiles == eng.ledger.total == len(eng.ledger.events)
    assert eng.ledger.compile_wall_s == pytest.approx(
        sum(e["wall_s"] for e in eng.ledger.events), abs=1e-5)
    assert 0 < eng.ledger.compile_wall_s and all(
        (e["wall_s"] > 0) == (e["site"] == "fwd") for e in eng.ledger.events)
    # a warm serve arrives nowhere in the account
    arrivals = account.totals()["events"]
    eng.generate([[1, 2, 3], [4, 5, 6, 7], [8, 9], [10, 11, 12]], max_new_tokens=4)
    assert account.totals()["events"] == arrivals

    reg = MetricsRegistry()
    populate_from_engine(reg, eng)
    fams = parse_exposition(render(reg))
    seconds = {dict(labels)["stage"]: value
               for _, labels, value in fams["dstpu_serving_compile_seconds_total"]["samples"]}
    assert seconds == pytest.approx({s: snap[s + "_s"] for s in ("trace", "lower", "load")},
                                    abs=1e-5)
    # a load the cache did not answer is exported as a miss whatever JAX's own event
    # said (with no cache directory it fires none, and every load was an XLA compile)
    (_, _, misses), = fams["dstpu_serving_compile_cache_misses_total"]["samples"]
    (_, _, hits), = fams["dstpu_serving_compile_cache_hits_total"]["samples"]
    assert (hits, misses) == (snap["cache_hits"], snap["cache_misses"])
    assert hits + misses == sum(programs[n]["loads"] for n in names) >= len(names)


def test_the_training_engine_names_its_programs_and_tells_telemetry_once(account, tmp_path):
    import json

    import deepspeed_tpu
    from deepspeed_tpu.parallel import MeshTopology

    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    path = tmp_path / "telemetry.jsonl"
    inits = sum(r.stage == ENGINE_INIT and r.program == "Engine" for r in account.rows())
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=loss_fn, model_parameters={"w": jnp.ones((4, 2))},
        topology=MeshTopology.from_axis_dict({"data": 8}),
        config={"train_batch_size": 8, "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "jsonl_path": str(path)}})
    assert sum(r.stage == ENGINE_INIT and r.program == "Engine"
               for r in account.rows()) == inits + 1
    batch = {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))}
    for _ in range(3):
        engine.train_batch(batch)
    engine.get_fp32_params()
    engine.telemetry.flush_jsonl()
    programs = account.by_program()
    assert programs["train_step"]["loads"] >= 1 and programs["gather_fp32_params"]["loads"] >= 1
    records = [json.loads(line) for line in path.read_text().splitlines()]
    told = [r for r in records if r.get("kind") == "gauges" and r.get("prefix") == "Train/Setup"]
    assert len(told) == 1 and told[0]["step"] == 1  # once, after the first step
    assert told[0]["trace_s"] > 0 and told[0]["loads"] >= 1 and "callback_s" in told[0]


def test_perf_still_imports_neither_jax_nor_numpy():
    with open(os.path.join(ROOT, "deepspeed_tpu", "monitor", "perf.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            imported.add((node.module or "").split(".")[0])
    assert not imported & {"jax", "numpy", "jaxlib"}
