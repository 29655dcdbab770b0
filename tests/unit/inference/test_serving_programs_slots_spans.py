"""What the serving engine asks the device to compute, made legible (ISSUE 24):
every compiled program named by its bucket, the same name in the compile
ledger and in the lowered module; token and table slots counted where a
program is launched; and the serve loop's and the train step's profiler spans
written whoever started the profiler, with no ``telemetry=`` object and with
training telemetry off."""

import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.monitor.perf import PHASES

NAMED_SITES = ("fwd", "burst", "pick", "spec_verify")
_PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]


def _tiny_engine(conf=None, **kw):
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=1, heads=2,
                                 kv_heads=2, seq=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    defaults = dict(config={"dtype": "float32", **(conf or {})},
                    num_blocks=32, block_size=8, max_blocks_per_seq=8,
                    token_budget=32, max_seqs_per_step=4)
    defaults.update(kw)
    return InferenceEngineV2(llama, cfg, params, **defaults)


def _slots(eng):
    snap = eng.counters.snapshot()
    return tuple(snap[f] for f in ("token_slots", "live_tokens", "table_slots", "live_blocks"))


def _names(eng, site):
    return {e["name"] for e in eng.ledger.events if e["site"] == site}


# ------------------------------------------------------------ slot accounting
# Three prompts of 3, 4 and 2 tokens, blocks of 8 tokens, put() and stepped by
# hand.  The prefill step is the bucket [4, 4] (3 rows -> 4, longest chunk 4);
# the table is 4 wide on the fast path (TABLE_STEP) and 1 wide on the
# reference path (next power of two of one block).
#
# The fifth case gives the same prompts a token budget of 8: the step is 3 + 4
# tokens and the first 1 of the third prompt, and its bucket [4, 4] holds twice
# what the budget lets a step fill, so the program's per-token layers run over
# 8 flat slots (ISSUE 25) and those are what ``token_slots`` counts.
@pytest.mark.parametrize("path", ["_dispatch_step", "_step_reference", "decode_burst",
                                  "decode_spec", "compacted"])
def test_slot_counters_equal_the_count_by_hand(path):
    conf = {"serving_fastpath": {"enabled": path != "_step_reference"}}
    if path == "decode_spec":
        conf["serving_spec_decode"] = {"enabled": True, "k": 4}
    if path == "compacted":
        eng = _tiny_engine(conf, token_budget=8)
        eng.put([0, 1, 2], _PROMPTS)
        assert len(eng.step()) == 2  # two prompts end; the third has a token to go
        assert _names(eng, "fwd") == {"fwd_n4_t4_b4"}  # the bucket's name, compacted or not
        # 8 flat slots for 8 live tokens of the [4, 4] bucket; the table as ever
        assert _slots(eng) == (8, 8, 4 * 4, 3)
        assert eng.counters.compact_passes == 1
        assert eng.counters.head_rows == 4  # the head over a last row a sequence, not 8 slots
        assert len(eng.step()) == 3  # two decodes and the prompt's last token: [4, 1]
        assert _slots(eng) == (8 + 4, 8 + 3, 16 + 16, 3 + 3)
        assert eng.counters.compact_passes == 1  # 4 slots fit the bound: padded
        assert eng.health()["fastpath"]["compact_passes"] == 1
        assert eng.health()["fastpath"]["head_rows"] == 4 + 4
        return
    eng = _tiny_engine(conf)
    eng.put([0, 1, 2], _PROMPTS)
    assert len(eng.step()) == 3  # every prompt fits the budget: one prefill step
    b = 1 if path == "_step_reference" else 4
    assert _names(eng, "fwd") == {f"fwd_n4_t4_b{b}"}
    # 4 x 4 token slots for 9 prompt tokens; 4 x b table slots for 3 blocks
    assert _slots(eng) == (16, 9, 4 * b, 3)
    assert eng.counters.compact_passes == 0  # 16 slots fit the budget of 32
    assert eng.counters.head_rows == 4  # n rows of the padded [4, 4] too

    if path in ("_dispatch_step", "_step_reference"):
        assert len(eng.step()) == 3  # one decode step: the bucket [4, 1]
        assert _names(eng, "fwd") == {f"fwd_n4_t4_b{b}", f"fwd_n4_t1_b{b}"}
        assert _slots(eng) == (16 + 4, 9 + 3, 4 * b + 4 * b, 3 + 3)
        assert eng.counters.head_rows == 4 + 4
    elif path == "decode_burst":
        out = eng.decode_burst(4)
        assert sorted(len(v) for v in out.values()) == [4, 4, 4]
        assert _names(eng, "burst") == {f"burst_n4_k4_b{b}"}  # the table's width is a shape: in the name
        # 4 forward passes over [4, 1]; positions up to 8, 9 and 7 need 1, 2
        # and 1 blocks, and every pass walks the [4, 4] table
        assert _slots(eng) == (16 + 4 * 4, 9 + 12, 16 + 4 * 16, 3 + 4 * (1 + 2 + 1))
        assert eng.counters.head_rows == 4 + 4 * 4  # n a pass, k passes
    else:
        out = eng.decode_spec(3)
        assert out is not None and all(1 <= len(run) <= 4 for run in out.values())
        assert _names(eng, "spec_verify") == {"spec_verify_n4_k3_b4"}
        # one forward pass over [4, 3 + 1]; the accepted runs are the live
        # tokens; positions up to 7, 8 and 6 fit the one block each row has
        assert _slots(eng) == (16 + 16, 9 + sum(len(r) for r in out.values()), 16 + 16, 3 + 3)
        assert eng.counters.head_rows == 4 + 16  # a verify scores every position: n x (k + 1)
    token_slots, live_tokens, table_slots, live_blocks = _slots(eng)
    assert live_tokens <= token_slots and live_blocks <= table_slots
    assert eng.health()["fastpath"]["token_slots"] == token_slots


def test_fast_path_on_and_off_run_the_same_live_tokens():
    prompts = _PROMPTS + [[10, 11, 12, 13, 14]]
    counted = {}
    for fastpath in (True, False):
        eng = _tiny_engine({"serving_fastpath": {"enabled": fastpath}})
        tokens = eng.generate(prompts, max_new_tokens=6)
        counted[fastpath] = (tokens, eng.counters.live_tokens)
        # every token but each request's last has been run through the model
        assert eng.counters.live_tokens == sum(len(t) - 1 for t in tokens)
        assert eng.counters.live_tokens <= eng.counters.token_slots
    assert counted[True] == counted[False]


# ------------------------------------------------------ one name per program
def _module_name(eng, event):
    """The module name of the program the ledger event names, read from its
    lowered (or, for an ahead-of-time compiled bucket, compiled) text."""
    key = ast.literal_eval(event["key"])
    program = eng._fwd_cache[key]
    if not hasattr(program, "lower"):  # jax.stages.Compiled
        return re.match(r"HloModule (\w+)", program.as_text()).group(1)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    n = key[1]
    if event["site"] == "pick":
        args = (jax.ShapeDtypeStruct((n, 1, 64), jnp.float32), eng._rng)
    else:  # burst: neither the table width nor the pool is part of its key
        args = (eng.params, eng.kv, ints(n), ints(n), ints(n, 4), eng._rng,
                jax.ShapeDtypeStruct((n, ), jnp.bool_))
    return re.match(r"module @(\w+)", program.lower(*args).as_text()).group(1)


@pytest.mark.parametrize("serve", ["greedy", "sampled_eos", "spec"])
def test_every_ledger_name_is_the_modules_and_spells_its_bucket(serve):
    conf = {"serving_spec_decode": {"enabled": True, "k": 4}} if serve == "spec" else {}
    eng = _tiny_engine({"temperature": 0.8, "top_k": 8, **conf})
    if serve == "sampled_eos":
        eng.generate(_PROMPTS, max_new_tokens=8, greedy=False, eos_token_id=63)
    else:
        eng.generate(_PROMPTS, max_new_tokens=8)
    events = [e for e in eng.ledger.events if e["site"] in NAMED_SITES]
    assert {"fwd", "pick"} <= {e["site"] for e in events}
    assert ("spec_verify" if serve == "spec" else "burst") in {e["site"] for e in events}
    for e in eng.ledger.events:
        assert re.fullmatch(r"[A-Za-z0-9_]+", e["name"]), e
        # the benchmark's reader selects the burst programs by this substring
        assert ("burst" in e["name"]) == (e["site"] == "burst"), e
    for e in events:
        assert _module_name(eng, e) == "jit_" + e["name"], e
        key = ast.literal_eval(e["key"])
        spelled = {k: int(v) for k, v in re.findall(r"_([ntbk])(\d+)", e["name"])}
        if e["site"] == "fwd":
            assert e["name"].startswith("fwd_") and spelled == dict(zip("ntb", key))
        elif e["site"] == "pick":
            assert e["name"] == f"pick_n{key[1]}" + ("" if key[2] else "_sampled")
        elif e["site"] == "burst":
            _, n, k, b, sample_cfg, eos = key
            assert e["name"] == (f"burst_n{n}_k{k}_b{b}" + ("_sampled" if sample_cfg else "")
                                 + (f"_eos{eos}" if eos >= 0 else ""))
        else:
            assert e["name"].startswith("spec_verify_")
            assert spelled == dict(zip("nkb", key[1:4]))
    if serve == "sampled_eos":
        assert any(e["name"].endswith("_sampled_eos63") for e in events)
        assert any(e["name"].startswith("pick_") and e["name"].endswith("_sampled")
                   for e in events)


# ------------------------------------------ spans that need nobody's permission
def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return {event.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for event in line.events}


class _ProfilerTrace:
    """A jax.profiler trace the test itself opens: nothing of the engine's."""

    def __init__(self, trace_dir):
        self.trace_dir = str(trace_dir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceAnnotations stay, Python frames do not
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


@pytest.mark.parametrize("fastpath", [True, False])
def test_serve_spans_are_in_a_trace_with_no_telemetry_object(fastpath, tmp_path):
    eng = _tiny_engine({"serving_fastpath": {"enabled": fastpath},
                        "serving_perf": {"enabled": True}})
    assert eng.telemetry is None
    with _ProfilerTrace(tmp_path):
        eng.generate(_PROMPTS + [[10, 11, 12]], max_new_tokens=6)
    names = _host_span_names(str(tmp_path))
    # the phases the loop entered, by the host-clock profiler's own marks
    # ("other" is the residual: a sum, not a span)
    entered = {p for p, h in eng.phase_profiler.hists.items() if h.count and p != "other"}
    assert {"admission_pump", "absorb_patch", "burst", "expire"} <= entered <= set(PHASES)
    assert entered <= names, sorted(entered - names)
    assert "dispatch" in names  # (the reference step marks its time absorb_patch)
    assert {"burst.prepare", "burst.wait", "burst.absorb"} <= names
    # step() blocks on its tokens at once only where nothing is pipelined
    assert ("dispatch.wait" in names) == (not fastpath)
    nested = {n for n in names if "." in n and n.split(".")[0] in PHASES}
    assert nested <= {"burst.prepare", "burst.wait", "burst.absorb", "dispatch.wait"}


def test_a_phase_annotation_takes_only_a_name_of_the_phase_list():
    eng = _tiny_engine()
    with eng._phase_annotation("burst", "wait"):
        pass
    with pytest.raises(KeyError, match="not a serve phase"):
        eng._phase_annotation("burst.wait")


def test_train_spans_are_in_a_trace_with_telemetry_off(tmp_path):
    from tests.unit.test_engine import make_engine, HIDDEN
    from tests.unit.simple_model import random_batch
    engine = make_engine(stage=0)
    assert not engine.telemetry.enabled
    batches = [random_batch(engine.train_batch_size, hidden=HIDDEN, seed=s) for s in (1, 2)]
    with _ProfilerTrace(tmp_path):
        for batch in batches:
            engine.train_batch(batch)
    assert {"batch_prep", "train_step"} <= _host_span_names(str(tmp_path))
    # the spans did not buy what enabling telemetry buys (the loss sync, records)
    assert not engine.telemetry.enabled
    assert engine.telemetry.records_written == 0
    assert engine._last_telemetry_record is None


@pytest.mark.parametrize("stage", [0, 3])
def test_train_step_program_carries_the_three_scopes(stage):
    from tests.unit.test_engine import make_engine, HIDDEN
    from tests.unit.simple_model import random_batch
    engine = make_engine(stage=stage, extra_cfg={"gradient_clipping": 1.0})
    batch = engine._shard_batch(engine._ensure_gas_layout(
        random_batch(engine.train_batch_size, hidden=HIDDEN, seed=1)))
    text = engine.train_step_fn.lower(engine.state, batch).as_text(debug_info=True)
    for scope in ("forward_backward", "grad_norm_clip", "optimizer"):
        assert re.search(rf'jit\(train_step\)/{scope}/', text), scope


# ------------------------------------------------- a sink of one method
class _RecordsOnly:
    """A ``telemetry=`` object that wants finished requests' records only."""

    def __init__(self):
        self.records = []

    def record_trace(self, record):
        self.records.append(record)


def test_a_telemetry_object_with_only_record_trace_serves_a_wave():
    sink = _RecordsOnly()
    eng = _tiny_engine({"serving_tracing": {"enabled": True},
                        "serving_resilience": {"default_ttl_s": 1e6}}, telemetry=sink)
    tokens = eng.generate(_PROMPTS, max_new_tokens=6)
    assert [len(t) for t in tokens] == [len(p) + 6 for p in _PROMPTS]
    assert sorted(r["uid"] for r in sink.records) == [0, 1, 2]
    assert eng.counters.burst_tokens > 0  # the gauge sites on both decode paths ran
