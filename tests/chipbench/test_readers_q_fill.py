"""The reader of ``paged.q_fill`` (PR 42) on hand-built counters, and its
metric file beside ``BENCHMARK.json``'s entry."""

import json
import os
import types

import pytest

from chipbench.readers import q_fill
from tests.chipbench.conftest import ROOT, SERVING_THEN


def serve_run(**fields):
    return types.SimpleNamespace(**{"kind": "serve", "trace": None, "counters": {}, **fields})


@pytest.mark.parametrize("live,attn_slots,want", [
    (256, 32 * 256, 3.125),                     # a padded pass: 31 decode rows beside one 225-token chunk
    (256, 256 + 32 * 3, 100 * 256 / 352),       # the same pass compacted: S slots and three a sequence
    (32 * 16, 32 * 16, 100.0),                  # a burst of 16 decode steps over 32 full rows
    (16067, 20009, 100 * 16067 / 20009)],       # a wave of chat-burst on the chip (PERF.md, PR 40): 80.3%
    ids=["padded", "flat", "burst", "wave"])
def test_q_fill_is_live_tokens_over_the_attention_layouts_positions_with_both_counts_noted(
        live, attn_slots, want):
    value, note = q_fill.read(serve_run(counters={
        "live_tokens": live, "attn_token_slots": attn_slots, "token_slots": 256}))
    assert value == pytest.approx(want) and 0 < value <= 100
    assert note == {"live_tokens": live, "attn_token_slots": attn_slots}


@pytest.mark.parametrize("counters", [
    {},                                                  # no counters at all
    {"live_tokens": 256, "token_slots": 8192},           # a program before PR 40 counts no such slots
    {"live_tokens": 0, "attn_token_slots": 0},           # a window with no forward
    {"live_tokens": 0, "attn_token_slots": 512}],        # passes that advanced nothing: no share of 0
    ids=["none", "before-pr40", "no-forward", "no-token"])
def test_without_both_counts_there_is_nothing_to_read(counters):
    # no division, no zero, nothing; and a training run has no paged kernel
    assert q_fill.read(serve_run(counters=counters)) is None
    assert q_fill.read(types.SimpleNamespace(kind="train")) is None
    assert q_fill.read(serve_run(kind="train", counters={"live_tokens": 4,
                                                         "attn_token_slots": 8})) is None


@pytest.mark.reads_benchmark
def test_the_metric_file_and_the_benchmarks_entry_agree():
    with open(os.path.join(ROOT, "chipbench", "metrics", "paged.q_fill.json")) as f:
        metric = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["per_layer"] if e["name"] == "paged.q_fill")
    # test_harness.py holds the entry to the file's fields; here what the metric is
    assert (metric["unit"], metric["better"], metric["source"]) == ("%", "higher", "program_counter")
    assert entry["moves"] == "serve_tok_s" and entry["layer"].startswith("kernels")
    serving = {w["name"] for w in bench["workloads"] if w["name"].startswith("serve.")}
    assert SERVING_THEN <= set(entry["workloads"]) <= serving  # a new serving cell may join or not
    assert metric["reader"] == "q_fill" and "attn_token_slots" in metric["what"]
