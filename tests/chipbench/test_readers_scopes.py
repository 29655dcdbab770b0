"""The scope readers on hand-built traces: ``chipbench/reduce/scopes.py`` lays
the program's own table of its executables (``deepspeed_tpu/monitor/
program_scopes.py``) over the trace's operation line.  An operation goes to the
program event that covers it, two programs with a ``%fusion.7`` each are kept
apart, the five group shares add up to ``scope.attributed_share`` and with the
rest to 100, each reader reads a fake run, and none raises where there is
nothing to read."""

import json
import os
import types

import pytest

from chipbench.readers import (kv_write_share, scope_attention_share, scope_attributed_share,
                               scope_dense_ffn_share, scope_expert_share, scope_head_share,
                               scope_mixer_share, train_optimizer_share)
from chipbench.reduce import scopes, xplane
from deepspeed_tpu.monitor import program_scopes
from tests.chipbench.conftest import ROOT, SERVING_THEN

MS = 1_000_000  # ns
READERS = {"scope.attributed_share": scope_attributed_share,
           "scope.attention_share": scope_attention_share,
           "scope.expert_share": scope_expert_share, "scope.mixer_share": scope_mixer_share,
           "scope.dense_ffn_share": scope_dense_ffn_share, "scope.head_share": scope_head_share,
           "train.optimizer_share": train_optimizer_share}


def module_text(name, instructions):
    """An optimized module of ``[(instruction, op_name or None)]``, every one in the entry."""
    lines = [f"HloModule jit_{name}, is_scheduled=true", "", f"ENTRY %main.1 (p: f32[4]) -> f32[4] {{"]
    for instruction, op_name in instructions:
        meta = "" if op_name is None else f', metadata={{op_name="jit({name})/{op_name}"}}'
        lines.append(f"  %{instruction} = f32[4]{{0}} custom-call(%p){meta}")
    return "\n".join(lines + ["}", ""])


# one chunk pass and one burst of a hybrid with experts; both programs hold a
# %fusion.7, under different scopes
CHUNK = [("embed_fusion", "embed/gather", 10),
         ("fusion.7", "while/body/attn_qkv/dot_general", 100),
         ("kv_write.3", "while/body/kv_write/pallas_call", 20),
         ("paged_attention.4", "while/body/attn_kernel/paged_attention/pallas_call", 200),
         ("fusion.9", "while/body/layer_finish/dot_general", 50),
         ("fusion.10", "while/body/layer_finish/dense_ffn/dot_general", 300),
         ("gmm.5", "while/body/mixer_layer/moe_expert_ffn/gmm/pallas_call", 400),
         ("fusion.12", "while/body/mixer_layer/moe_shared_expert/dense_ffn/dot_general", 60),
         ("ssd_scan.2", "while/body/mixer_layer/ssm_mixer/ssm_scan/pallas_call", 250),
         ("fusion.13", "while/body/mixer_layer/add", 30),
         ("fusion.14", "while/body/seq_state/scatter", 40),
         ("copy.5", None, 25),                       # the compiler's: unscoped
         ("fusion.15", "while/body/dynamic_slice", 15),  # the scan's slice of the weights: unscoped
         ("fusion.16", "head/dot_general", 70)]
BURST = [("fusion.7", "while/body/while/body/layer_finish/dense_ffn/dot_general", 500),
         ("ssd_update.1", "while/body/while/body/mixer_layer/ssm_mixer/ssm_update/ssm_state/pallas_call", 80),
         ("fusion.20", "while/body/pick/argmax", 5)]


def event(instruction):
    return f"%{instruction} = f32[4]{{0}} fusion(...)"


def trace_of(*programs, stray=(), devices=1):
    """Programs ``(name, [(instruction, op_name, ms)])`` one after another, each
    inside its program event, and ``stray`` operations no program event covers."""
    tree = {"devices": {}, "host": []}
    for d in range(devices):
        ops, modules = [], []
        for i, (name, body) in enumerate(programs):
            t = 10_000 * (i + 1) * MS
            modules.append((f"jit_{name}({7 * i + 1})", t, 6000 * MS))
            ops.append(("%while.1 = (s32[], f32[4]) while(...)", t, sum(ms for *_, ms in body) * MS))
            for instruction, _, ms in body:
                ops.append((xplane.short_name(event(instruction)), t, ms * MS))
                t += ms * MS
        for instruction, ms in stray:
            ops.append((xplane.short_name(event(instruction)), 100, ms * MS))
        tree["devices"][f"/device:TPU:{d}"] = {"ops": ops, "modules": modules}
    return xplane.Reduction(tree)


class Owner:
    """An engine's stand-in: the registry holds its owners weakly."""


@pytest.fixture
def registered():
    """An owner whose programs the registry holds for as long as the test runs."""
    program_scopes.REGISTRY.clear()
    owner = Owner()

    def register(name, body):
        program_scopes.register(owner, name, lambda: module_text(name, [b[:2] for b in body]))
    yield register
    program_scopes.REGISTRY.clear()


def serve_run(**fields):
    return types.SimpleNamespace(**{"kind": "serve", "trace": None, **fields})


@pytest.fixture
def hybrid(registered):
    registered("fwd_n4_t256_b36", CHUNK)
    registered("burst_n4_k16_b36", BURST)
    return serve_run(trace=trace_of(("fwd_n4_t256_b36", CHUNK), ("burst_n4_k16_b36", BURST),
                                    stray=[("fusion.99", 45)]))


# ------------------------------------------------------------ the arithmetic
def test_an_operation_goes_to_the_program_event_that_covers_it():
    modules = [("jit_a(1)", 100, 50), ("jit_b(2)", 200, 50)]
    ops = [("%x.1", 100, 10), ("%x.2", 149, 10), ("%x.3", 150, 5), ("%x.4", 200, 1), ("%x.5", 90, 5)]
    got = dict(scopes.by_program(ops, modules))
    assert {k: [o[0] for o in v] for k, v in got.items()} == {
        "a": ["%x.1", "%x.2"], "b": ["%x.4"], None: ["%x.3", "%x.5"]}
    assert scopes.program_of("jit_fwd_n32_t1_b20(8632186328526641690)") == "fwd_n32_t1_b20"
    assert scopes.program_of("train_step") == "train_step"
    assert scopes.instruction_of("%fusion.735 bf16[32,1,16768]") == "fusion.735"
    assert scopes.instruction_of("%gmm.7") == "gmm.7"


def test_two_programs_with_a_fusion_of_one_name_are_kept_apart(hybrid):
    found = scopes.split(hybrid)
    paths = scopes.summed(found, "paths")
    assert paths[("attn_qkv", )] == pytest.approx(0.100)                 # the chunk's %fusion.7
    assert paths[("layer_finish", "dense_ffn")] == pytest.approx(0.800)  # the burst's, and fusion.10
    assert found["programs"] == found["tables"] == 2 and found["tables_s"] >= 0
    assert hybrid.scope_split is found and scopes.split(hybrid) is found  # once a run


def test_the_container_is_left_out_and_self_times_are_what_is_summed():
    table = {"p": program_scopes.scope_table(module_text("p", [("fusion.1", "embed/x"),
                                                               ("inner.2", "head/y")]))}
    ops = [("%while.3", 0, 100), ("%fusion.1", 0, 60), ("%inner.2", 10, 20)]  # inner.2 nests in fusion.1
    got = scopes.split_events(ops, [("jit_p(1)", 0, 100)], table)
    assert got["paths"] == {("embed", ): 40, ("head", ): 20}
    assert got["unattributed"] == 0 and not got["unscoped"]


@pytest.mark.parametrize("path,group", [
    (("layer_finish", ), "attention"), (("layer_finish", "dense_ffn"), "dense_ffn"),
    (("layer_finish", "moe_shared_expert", "dense_ffn"), "expert"),
    (("layer_finish", "scmoe_shortcut", "moe_route"), "expert"),
    (("layer_finish", "scmoe_shortcut"), "expert"),
    (("mixer_layer", "moe_expert_ffn"), "expert"), (("mixer_layer", "dense_ffn"), "dense_ffn"),
    (("mixer_layer", ), "mixer"), (("mixer_layer", "ssm_mixer", "ssm_update", "ssm_state"), "mixer"),
    (("seq_state", ), "mixer"), (("attn_kernel", "dsa_index"), "attention"),
    (("attn_qkv", "mla_absorb"), "attention"), (("pick", ), "head"), (("embed", ), "head"),
    (("optimizer", ), None), ((), None)])
def test_an_operations_group_is_the_first_that_has_a_name_on_its_path(path, group):
    assert scopes.group_of(path) == group


def test_every_serving_scope_is_in_exactly_one_group():
    grouped = [name for _, names in scopes.GROUPS for name in names]
    assert len(grouped) == len(set(grouped))
    assert set(grouped) | set(scopes.TRAIN) == set(program_scopes.SCOPES)
    assert not set(grouped) & set(scopes.TRAIN)


# ----------------------------------------------------------------- the readers
def test_the_five_shares_add_to_the_attributed_share_and_with_the_rest_to_100(hybrid):
    busy = hybrid.trace.busy_s
    assert busy == pytest.approx((sum(ms for *_, ms in CHUNK + BURST) + 45) / 1e3)
    attributed, note = scope_attributed_share.read(hybrid)
    shares = {name: READERS[name].read(hybrid)[0] for name in READERS
              if name not in ("scope.attributed_share", "train.optimizer_share")}
    assert sum(shares.values()) == pytest.approx(attributed)
    rest = note["unscoped_s"] + note["unattributed_s"]
    assert attributed + 100 * rest / busy == pytest.approx(100, abs=0.01)
    assert note["unscoped_s"] == pytest.approx(0.040) and note["unattributed_s"] == pytest.approx(0.045)
    assert note["top_unscoped"].startswith("fwd_n4_t256_b36:%copy.5:f32[4]:0.0250,"
                                           "fwd_n4_t256_b36:%fusion.15:f32[4]:0.0150")
    assert note["largest_table"] == len(CHUNK) and note["programs"] == note["tables"] == 2
    assert shares["scope.attention_share"] == pytest.approx(100 * 0.370 / busy)
    assert shares["scope.expert_share"] == pytest.approx(100 * 0.460 / busy)
    assert shares["scope.mixer_share"] == pytest.approx(100 * 0.400 / busy)
    assert shares["scope.dense_ffn_share"] == pytest.approx(100 * 0.800 / busy)
    assert shares["scope.head_share"] == pytest.approx(100 * 0.085 / busy)


@pytest.mark.parametrize("metric,seconds", [
    ("scope.attention_share", {"attn_qkv_s": 0.1, "kv_write_s": 0.02, "attn_kernel_s": 0.2,
                               "layer_finish_s": 0.05, "attn_kernel.paged_attention_s": 0.2,
                               "kv_write.kv_write_s": 0.02}),
    ("scope.expert_share", {"moe_expert_ffn_s": 0.4, "moe_shared_expert_s": 0.06,
                            "moe_expert_ffn.gmm_s": 0.4}),
    ("scope.mixer_share", {"ssm_scan_s": 0.25, "ssm_state_s": 0.08, "mixer_layer_s": 0.03,
                           "seq_state_s": 0.04, "ssm_scan.ssd_scan_s": 0.25,
                           "ssm_state.ssd_update_s": 0.08}),
    ("scope.dense_ffn_share", {"dense_ffn_s": 0.8}),
    ("scope.head_share", {"embed_s": 0.01, "head_s": 0.07, "pick_s": 0.005}),
])
def test_a_group_reader_lists_its_scopes_and_kernels_apart(hybrid, metric, seconds):
    value, note = READERS[metric].read(hybrid)
    assert {k: v for k, v in note.items() if k not in ("group_s", "busy_s")} == pytest.approx(seconds)
    assert note["group_s"] == pytest.approx(value * hybrid.trace.busy_s / 100, abs=1e-4)


def test_the_writers_seconds_are_kv_write_shares_own(hybrid):
    """The agreement the scope table is trusted by: the same events found two ways."""
    _, by_name = kv_write_share.read(hybrid)
    _, by_scope = scope_attention_share.read(hybrid)
    assert by_name["write_s"] == by_scope["kv_write.kv_write_s"] == by_scope["kv_write_s"]


STEP = [("fusion.1", "forward_backward/jvp(dense_ffn)/dot_general", 600),
        ("dynamic-update-slice_fusion.2", "forward_backward/transpose(jvp(dense_ffn))/dot_general", 300),
        ("all-gather.3", None, 50),
        ("fusion.4", "grad_norm_clip/reduce_sum", 20),
        ("fusion.5", "optimizer/mul", 130)]


def test_the_optimizers_share_is_read_on_the_device_where_it_is_largest(registered):
    registered("train_step", STEP)
    run = types.SimpleNamespace(kind="train", trace=trace_of(("train_step", STEP), devices=2))
    slow = run.trace.devices["/device:TPU:1"]["ops"]
    slow[:] = [(n, s, d * 2 if "fusion.5" in n else d) for n, s, d in slow]
    run.trace = xplane.Reduction({"devices": run.trace.devices, "host": []})
    value, note = train_optimizer_share.read(run)
    assert value == pytest.approx(100 * 260 / 1230) and note["device"] == "TPU:1"
    assert note["forward_backward_s"] == pytest.approx(0.9) and note["grad_norm_clip_s"] == 0.02
    assert note["optimizer_s"] == pytest.approx(0.26) and note["unscoped_s"] == 0.05
    largest = note["largest"].split(",")
    assert largest[0] == "%fusion.1:f32[4]:0.6000:forward_backward/dense_ffn"
    assert "%dynamic-update-slice_fusion.2:f32[4]:0.3000:forward_backward/dense_ffn" in largest
    assert "%all-gather.3:f32[4]:0.0500:unscoped" in largest
    assert scope_head_share.read(run) is None  # a train cell has no serving groups


# --------------------------------------------------------- nothing to read
@pytest.mark.parametrize("metric", sorted(READERS))
def test_without_a_trace_or_without_tables_a_reader_gives_nothing(metric, registered):
    reader = READERS[metric]
    kind = "train" if metric.startswith("train.") else "serve"
    assert reader.read(types.SimpleNamespace(kind=kind, trace=None)) is None
    # a trace of programs nobody registered (the parent's engines register none)
    run = types.SimpleNamespace(kind=kind, trace=trace_of(("fwd_n4_t256_b36", CHUNK)))
    assert reader.read(run) is None and run.scope_split is False
    registered("fwd_n4_t256_b36", CHUNK)  # the other kind of cell reads nothing either
    other = types.SimpleNamespace(kind="train" if kind == "serve" else "serve",
                                  trace=trace_of(("fwd_n4_t256_b36", CHUNK)))
    assert reader.read(other) is None


def test_a_program_without_program_scopes_is_nothing_to_read(monkeypatch, hybrid):
    import sys
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.monitor.program_scopes", None)  # the parent
    monkeypatch.delattr("deepspeed_tpu.monitor.program_scopes", raising=False)
    hybrid.scope_split = None
    assert scopes.split(hybrid) is None
    assert all(reader.read(hybrid) is None for reader in READERS.values())


# ----------------------------------------------------------------- the files
@pytest.mark.reads_benchmark
@pytest.mark.parametrize("metric", sorted(READERS))
def test_each_metric_file_agrees_with_its_benchmark_entry(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
        {k: entry[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    assert spec["reader"] == READERS[metric].__name__.rsplit(".", 1)[-1]
    assert entry["source"] == "device_trace" and entry["unit"] == "%"
    cells = {w["name"]: w for w in bench["workloads"]}
    assert entry["workloads"] and all(c in cells for c in entry["workloads"])
    if metric in ("scope.attributed_share", "scope.attention_share", "scope.head_share"):
        # the serving cells the benchmark held when the metric was listed; a later cell may join
        assert set(entry["workloads"]) >= SERVING_THEN | {
            "serve.gdn-long-prompt", "serve.dsa-long-prompt", "serve.scmoe-decode-wide",
            "serve.ssm-chat-burst"}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m["name"] in (
        "step.busy_ms_per_ktok", "train.mfu")}
