"""The five ``setup.*`` readers (PR 36) over a hand-built set-up account and
run: the cut at the window's start, outermost rows only, a miss among hits, an
engine's construction less JAX's stages inside it, nothing without an origin;
and their metric files beside the entries that ``BENCHMARK.json`` lists since
PR 42: each entry is its metric file's own fields, and the harness reads them
through a copy of the committed files."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from chipbench.readers import (setup_engine_init_s, setup_load_s, setup_lower_s, setup_programs,
                               setup_trace_s)
from chipbench.reduce import setup_account
from deepspeed_tpu.monitor import compile_events
from deepspeed_tpu.monitor.compile_events import ENGINE_INIT, LOAD, LOWER, TRACE, Account
from tests.chipbench.conftest import REPO, ROOT, SERVING_THEN

T0 = 1000.0  # the process's start on the account's clock
HIT = "/jax/compilation_cache/cache_hits"
MISS = None  # a load the cache did not answer: no hit fires inside it
NAMES = ["setup.trace_s", "setup.lower_s", "setup.load_s", "setup.programs", "setup.engine_init_s"]


def program(acc, name, at, trace, lower, load, answer=HIT, inner=()):
    """One program's three stages back to back from ``at``; ``inner`` are
    operator-level traces that close inside its trace."""
    for k, op in enumerate(inner):
        acc.arrive(TRACE, op, at + 0.001 * (k + 1), at + 0.001 * (k + 1) + 0.0005)
    acc.arrive(TRACE, name, at, at + trace)
    acc.arrive(LOWER, f"jit({name})", at + trace, at + trace + lower)
    if answer:
        acc.on_event(answer)
        acc.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
        acc._book().cache["first"] = at + trace + lower  # stamped inside the load, on this test's clock
    acc.arrive(LOAD, f"jit({name})", at + trace + lower, at + trace + lower + load)
    return at + trace + lower + load


@pytest.fixture
def account(monkeypatch):
    """A set-up of 40 s: an engine built from 1002 to 1006 with one eager
    program inside it, three engine programs and the weight draw before the
    window, a reference compiled after it and one straggler inside it."""
    acc = Account()
    program(acc, "draw", T0 + 1.0, 0.2, 0.3, 0.5)                                 # the harness's
    program(acc, "broadcast_in_dim", T0 + 2.5, 0.1, 0.2, 0.7, inner=())             # inside the init
    acc.span(ENGINE_INIT, "InferenceEngineV2", T0 + 2.0, T0 + 6.0)
    end = program(acc, "fwd_n32_t1_b20", T0 + 10.0, 2.0, 3.0, 1.0, inner=("multiply", "add", "dot"))
    end = program(acc, "fwd_n32_t256_b20", end, 4.0, 5.0, 1.5, answer=MISS, inner=("multiply", ))
    program(acc, "burst_n16_k64", end, 1.0, 1.0, 0.5)
    program(acc, "convert_element_type", T0 + 42.0, 0.1, 0.1, 0.3)                 # inside the window
    program(acc, "logits_rows", T0 + 60.0, 5.0, 5.0, 5.0, answer=MISS)             # after it
    monkeypatch.setattr(compile_events, "ACCOUNT", acc)
    return acc


def a_run(**fields):
    return types.SimpleNamespace(**{"kind": "serve", "setup_s": 40.0, "window_s": 10.0,
                                    "t_start": T0, "trace": object(), **fields})


def test_seconds_are_the_outermost_rows_that_ended_before_the_window(account):
    value, note = setup_trace_s.read(a_run())
    assert value == pytest.approx(0.2 + 0.1 + 2.0 + 4.0 + 1.0)
    assert note["rows"] == 5 and note["inner_traces"] == 4
    assert note["most"] == "fwd_n32_t256_b20:4.000,fwd_n32_t1_b20:2.000,burst_n16_k64:1.000"
    assert note["events"] == account.totals()["events"] and "callback_s" in note
    value, note = setup_lower_s.read(a_run())
    assert value == pytest.approx(0.3 + 0.2 + 3.0 + 5.0 + 1.0) and note["rows"] == 5
    assert note["most"].startswith("fwd_n32_t256_b20:5.000,fwd_n32_t1_b20:3.000")


def test_a_miss_among_hits_says_the_reading_was_not_a_warm_one(account):
    value, note = setup_load_s.read(a_run())
    assert value == pytest.approx(0.5 + 0.7 + 1.0 + 1.5 + 0.5)
    assert (note["cache_hits"], note["cache_misses"], note["warm"]) == (4, 1, False)
    assert note["retrieval_s"] == pytest.approx(0.04)  # a miss retrieves nothing
    # the straggler and the reference are not set-up's: a cut before the miss reads warm
    early = a_run(setup_s=16.0)  # fwd_n32_t1_b20 ends here: at or before the cut counts
    value, note = setup_load_s.read(early)
    assert value == pytest.approx(0.5 + 0.7 + 1.0) and note["warm"] is True and note["rows"] == 3


def test_programs_counts_loads_names_the_engines_and_what_ended_in_the_window(account):
    value, note = setup_programs.read(a_run())
    assert value == 5 and note == {"names": 5, "in_window": 1}
    # a counter reads off the chip too (a rehearsal), the seconds do not
    off_chip = a_run(trace=None)
    assert setup_programs.read(off_chip)[0] == 5
    for reader in (setup_trace_s, setup_lower_s, setup_load_s, setup_engine_init_s):
        assert reader.read(off_chip) is None


def test_engine_init_is_the_construction_less_the_stages_inside_it(account):
    value, note = setup_engine_init_s.read(a_run())
    assert value == pytest.approx(4.0 - (0.1 + 0.2 + 0.7))
    accounted = value + (0.2 + 0.1 + 2.0 + 4.0 + 1.0) + (0.3 + 0.2 + 3.0 + 5.0 + 1.0) + 4.2
    assert note == {"other_s": pytest.approx(40.0 - accounted, abs=1e-3), "setup_s": 40.0}
    # a second engine whose construction overlaps the first is not counted twice
    account.span(ENGINE_INIT, "Engine", T0 + 5.0, T0 + 7.0)
    assert setup_engine_init_s.read(a_run())[0] == pytest.approx(5.0 - 1.0)


def test_the_four_seconds_sum_to_less_than_setup_s(account):
    run = a_run()
    seconds = [r.read(run)[0] for r in (setup_trace_s, setup_lower_s, setup_load_s,
                                        setup_engine_init_s)]
    assert 0 < sum(seconds) < run.setup_s
    assert sum(seconds) + setup_engine_init_s.read(run)[1]["other_s"] == pytest.approx(run.setup_s,
                                                                                       abs=1e-3)


READERS = (setup_trace_s, setup_lower_s, setup_load_s, setup_programs, setup_engine_init_s)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_without_an_origin_or_an_account_there_is_nothing_to_read(account, monkeypatch, reader):
    run = a_run()
    del run.t_start
    for module in ("__main__", "chipbench.run"):
        if hasattr(sys.modules.get(module), "T_START"):
            monkeypatch.delattr(sys.modules[module], "T_START")
    assert reader.read(run) is None
    # the module that measured setup_s holds the origin where run does not
    monkeypatch.setattr(sys.modules["__main__"], "T_START", T0, raising=False)
    assert reader.read(run) is not None
    # a program that has no account (this PR's parent) is nothing to read, not an error
    monkeypatch.delattr(sys.modules["deepspeed_tpu.monitor"], "compile_events")
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.monitor.compile_events", None)
    assert reader.read(a_run()) is None


def test_an_empty_account_reads_nothing(monkeypatch):
    monkeypatch.setattr(compile_events, "ACCOUNT", Account())
    assert setup_programs.read(a_run()) is None and setup_engine_init_s.read(a_run()) is None


def test_spans_are_covered_once():
    assert setup_account.covered([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == pytest.approx(4.0)
    assert setup_account.covered([]) == 0.0


@pytest.mark.reads_benchmark
def test_the_metric_files_make_entries_the_benchmark_can_take():
    """Each listed entry is its metric file's own fields, in the cells it was
    accepted with (every cell has a set-up); a cell added later may join."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(NAMES) <= set(listed)
    for name in NAMES:
        with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as f:
            metric = json.load(f)
        assert metric["name"] == name and metric["reader"] == name.replace(".", "_")
        entry = dict(listed[name])
        assert SERVING_THEN | {"train.zero3-fsdp4"} <= set(entry.pop("workloads")) <= cells
        assert entry == {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["layer"] == listed[NAMES[0]]["layer"] and entry["layer"].startswith("set-up (")
        assert len(entry["layer"]) <= 200
        assert (entry["source"], entry["unit"]) == (
            ("program_counter", "count") if name == "setup.programs" else ("program_span", "s"))
    setup_s = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup_s  # every cell reports it, so every cell can list the five


def test_in_a_copy_of_the_committed_files_the_harness_reads_them(tmp_path):
    """The command as ``__main__`` (where the readers find ``T_START``), the
    train cell traced at its rehearsal size: the count reads, the seconds do
    not (a time comes only from a chip run), ``would_be_correct`` as before."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), "--workload",
         "train.zero3-fsdp4", "--seed", str(2 ** 31 + 36), "--seconds", "0", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert done.returncode == 3, done.stderr[-2000:]
    line = [l for l in done.stdout.splitlines() if l.startswith("[rehearsal-not-a-result] ")][-1]
    result = json.loads(line.split(" ", 1)[1])
    assert result["would_be_correct"] is True
    assert set(NAMES) & set(result["metrics"]) == {"setup.programs"}  # the one counter of the five
    assert result["metrics"]["setup.programs"]["value"] >= 2  # train_step and make_state at least
    printed = {name: next(l for l in done.stdout.splitlines() if l.startswith(f"[metric] name={name} "))
               for name in NAMES}
    assert all(("nothing to read" in l) == (name != "setup.programs") for name, l in printed.items())
    assert "in_window=0" in printed["setup.programs"]
