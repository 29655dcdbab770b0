"""The reader of ``paged.slots_per_step`` (PR 35) on hand-built inputs, and
its metric file beside ``BENCHMARK.json``'s entry."""

import json
import os
import types

import pytest

from chipbench.readers import slots_per_step
from tests.chipbench.conftest import ROOT, SERVING_THEN


def serve_run(**fields):
    return types.SimpleNamespace(**{"kind": "serve", "trace": None, "counters": {}, **fields})


@pytest.mark.parametrize("table_slots,kernel_steps,want", [
    (640, 160, 4.0),                     # decode passes over a table 20 wide: four slots a step
    (640 + 576, 160 + 288, 1216 / 448),  # beside chunk passes at two
    (36, 12, 3.0),                       # a table of three in a step of four walks three
    (640, 640, 1.0)])
def test_slots_a_step_is_walked_slots_over_grid_steps_with_both_counts_noted(table_slots, kernel_steps,
                                                                             want):
    value, note = slots_per_step.read(serve_run(counters={
        "table_slots": table_slots, "kernel_steps": kernel_steps, "live_blocks": 90}))
    assert value == pytest.approx(want)
    assert note == {"table_slots": table_slots, "kernel_steps": kernel_steps}


@pytest.mark.parametrize("counters", [{}, {"table_slots": 640, "live_blocks": 90},
                                      {"table_slots": 0, "kernel_steps": 0}])
def test_a_program_without_the_counter_is_nothing_to_read(counters):
    # the parent commit counts no kernel steps, and a window may launch no forward:
    # no division, no zero, nothing; and a training run has no paged kernel
    assert slots_per_step.read(serve_run(counters=counters)) is None
    assert slots_per_step.read(serve_run(kind="train", counters={"table_slots": 4,
                                                                 "kernel_steps": 1})) is None


@pytest.mark.reads_benchmark
def test_the_metric_file_and_the_benchmarks_entry_agree():
    with open(os.path.join(ROOT, "chipbench", "metrics", "paged.slots_per_step.json")) as f:
        metric = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == "paged.slots_per_step")
    assert metric["name"] == entry["name"]
    assert all(entry[k] == metric[k] for k in ("unit", "better", "source", "layer", "moves"))
    assert entry["moves"] == "serve_tok_s" and entry["layer"].startswith("kernels")
    serving = {w["name"] for w in bench["workloads"] if w["name"].startswith("serve.")}
    assert SERVING_THEN <= set(entry["workloads"]) <= serving  # a new serving cell may join or not
    assert metric["reader"] == "slots_per_step" and "kernel_steps" in metric["what"]
