"""``monitor/program_scopes.py`` (ISSUE 54): the one list of scope names, the
table of an optimized module's instructions, and the registry the engines fill
where they build a program and nobody reads until asked."""

import ast
import gc
import os
import re
import weakref

import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.monitor import program_scopes
from deepspeed_tpu.monitor.program_scopes import INNER, KINDS, SCOPES, FirstCall, Path, Registry, scope_table

PACKAGE = os.path.dirname(deepspeed_tpu.__file__)

# An optimized module as ``Compiled.as_text()`` prints one: a fused computation
# under one scope, one that straddles two, a reduction's region, a layer scan's
# condition and body (a Pallas custom call and a plain product inside), the
# entry with an instruction the compiler made (no metadata at all).
MODULE = '''HloModule jit_fwd_n4_t1_b4, is_scheduled=true, entry_computation_layout={(f32[4,8]{1,0})->f32[4,8]{1,0}}

%fused_computation.1 (param_0.1: f32[4,8]) -> f32[4,8] {
  %param_0.1 = f32[4,8]{1,0} parameter(0)
  %multiply.3 = f32[4,8]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/dense_ffn/mul" source_file="t.py" source_line=3}
  ROOT %tanh.2 = f32[4,8]{1,0} tanh(%multiply.3), metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/dense_ffn/tanh"}
}

%fused_computation.2 (param_0.2: f32[4,8]) -> f32[4,8] {
  %param_0.2 = f32[4,8]{1,0} parameter(0)
  %exponential.1 = f32[4,8]{1,0} exponential(%param_0.2), metadata={op_name="jit(fwd_n4_t1_b4)/while/body/attn_qkv/exp"}
  %negate.1 = f32[4,8]{1,0} negate(%exponential.1), metadata={op_name="jit(fwd_n4_t1_b4)/while/body/neg"}
  ROOT %add.9 = f32[4,8]{1,0} add(%negate.1, %param_0.2), metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/add"}
}

%region_0.5 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {
  %reduce_sum.1 = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %reduce_sum.2 = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.1 = f32[] add(%reduce_sum.1, %reduce_sum.2), metadata={op_name="jit(fwd_n4_t1_b4)/head/reduce_sum"}
}

%cond.7 (arg.1: (s32[], f32[4,8])) -> pred[] {
  %arg.1 = (s32[], f32[4,8]{1,0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%arg.1), index=0
  %constant.2 = s32[] constant(2)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.1, %constant.2), direction=LT, metadata={op_name="jit(fwd_n4_t1_b4)/while/cond/lt"}
}

%body.8 (arg.2: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %arg.2 = (s32[], f32[4,8]{1,0}) parameter(0)
  %get-tuple-element.3 = f32[4,8]{1,0} get-tuple-element(%arg.2), index=1
  %kv_write.3 = f32[4,8]{1,0} custom-call(%get-tuple-element.3), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,8]{1,0}}, metadata={op_name="jit(fwd_n4_t1_b4)/while/body/kv_write/pallas_call" source_file="kv_write.py" source_line=9}, backend_config={"custom_call_config": {"body": "abc"}}
  %paged_attention.4 = f32[4,8]{1,0} custom-call(%kv_write.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(fwd_n4_t1_b4)/while/body/attn_kernel/paged_attention/pallas_call"}
  %convolution.5 = f32[4,8]{1,0} convolution(%paged_attention.4, %paged_attention.4), dim_labels=bf_io->bf, metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/moe_expert_ffn/dot_general"}
  %fusion.11 = f32[4,8]{1,0} fusion(%convolution.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/dense_ffn/tanh"}
  %add_exp_fusion = f32[4,8]{1,0} fusion(%fusion.11), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fwd_n4_t1_b4)/while/body/layer_finish/add"}
  %constant.4 = s32[] constant(1)
  ROOT %tuple.6 = (s32[], f32[4,8]{1,0}) tuple(%constant.4, %add_exp_fusion)
}

ENTRY %main.20 (tokens.1: f32[4,8]) -> f32[4,8] {
  %tokens.1 = f32[4,8]{1,0} parameter(0), metadata={op_name="tokens"}
  %constant.9 = s32[] constant(0)
  %copy.3 = f32[4,8]{1,0} copy(%tokens.1)
  %gather.2 = f32[4,8]{1,0} gather(%copy.3, %constant.9), offset_dims={1}, metadata={op_name="jit(fwd_n4_t1_b4)/embed/gather"}
  %tuple.1 = (s32[], f32[4,8]{1,0}) tuple(%constant.9, %gather.2)
  %while.1 = (s32[], f32[4,8]{1,0}) while(%tuple.1), condition=%cond.7, body=%body.8, metadata={op_name="jit(fwd_n4_t1_b4)/while"}
  %get-tuple-element.9 = f32[4,8]{1,0} get-tuple-element(%while.1), index=1, metadata={op_name="jit(fwd_n4_t1_b4)/while"}
  ROOT %reduce.3 = f32[4,8]{1,0} reduce(%get-tuple-element.9, %constant.9), dimensions={}, to_apply=%region_0.5, metadata={op_name="jit(fwd_n4_t1_b4)/head/jit(_reduce_sum)/reduce_sum"}
}
'''


# ------------------------------------------------------------------ the parser
@pytest.mark.parametrize("instruction,path,mixed,inherited", [
    ("gather.2", ("embed", ), (), False),
    ("kv_write.3", ("kv_write", ), (), False),                  # a Pallas call inside the scan's body
    ("paged_attention.4", ("attn_kernel", ), (), False),
    ("convolution.5", ("layer_finish", "moe_expert_ffn"), (), False),  # outermost first
    ("fusion.11", ("layer_finish", "dense_ffn"), (), False),    # a fusion takes its own op_name
    ("add_exp_fusion", ("layer_finish", ), ("attn_qkv", ), False),  # ...and says what else it fused
    ("reduce.3", ("head", ), (), False),                        # jit(_reduce_sum) is no scope
    ("while.1", (), (), False),                                 # a container inherits nothing
    ("compare.1", (), (), False),                               # read by nothing: it stays under none
    ("copy.3", ("embed", ), (), True),                          # the compiler's own, read by embed alone
    ("tokens.1", ("embed", ), (), True),                        # ...through it
    ("get-tuple-element.9", ("head", ), (), True),
    ("constant.9", (), (), False),                              # read under embed AND head
    ("get-tuple-element.3", ("kv_write", ), (), True),
], ids=lambda v: v if isinstance(v, str) else None)
def test_an_instructions_scopes_are_read_off_its_op_name(instruction, path, mixed, inherited):
    entry = scope_table(MODULE)[instruction]
    assert entry == path and isinstance(entry, Path)
    assert entry.mixed == mixed and entry.inherited == inherited and not entry.ambiguous


def test_what_is_fused_is_no_entry():
    table = scope_table(MODULE)
    assert not {"multiply.3", "tanh.2", "exponential.1", "negate.1", "add.9", "param_0.1"} & set(table)
    assert "add.1" in table  # a reduction's region is no fusion: its instructions stay


def test_a_layer_scans_slice_of_the_weights_is_the_products_that_reads_it():
    """No line of the source holds the slice (``lax.scan`` makes it), so no scope
    can wrap it: it is read off the executable's own dataflow."""
    text = MODULE.replace(
        "  %kv_write.3 =", '  %slice_fusion.6 = f32[4,8]{1,0} fusion(%get-tuple-element.3), kind=kLoop, '
        'calls=%fused_computation.9, metadata={op_name="jit(fwd_n4_t1_b4)/while/body/dynamic_slice"}\n'
        '  %bitcast.2 = f32[4,8]{1,0} bitcast(%slice_fusion.6)\n  %kv_write.3 =').replace(
            "convolution(%paged_attention.4, %paged_attention.4)",
            "convolution(%paged_attention.4, %bitcast.2)")
    table = scope_table(text)
    assert table["slice_fusion.6"] == ("layer_finish", "moe_expert_ffn") and table["slice_fusion.6"].inherited
    assert table["bitcast.2"].inherited and not table["convolution.5"].inherited
    # read under two scopes, it is neither's
    both = scope_table(text.replace("custom-call(%kv_write.3)", "custom-call(%kv_write.3, %bitcast.2)"))
    assert both["slice_fusion.6"] == () and not both["slice_fusion.6"].inherited


@pytest.mark.parametrize("op_name,path", [
    ("jit(train_step)/forward_backward/transpose(jvp(dense_ffn))/dot_general",
     ("forward_backward", "dense_ffn")),
    ("jit(train_step)/jvp(forward_backward)/while/body/checkpoint/mul", ("forward_backward", )),
    ("jit(train_step)/optimizer/add", ("optimizer", )),
    ("jit(head)/jit(pick)/argmax", ()),            # functions of those names, not scopes
    ("jit(f)/vmap(jit(embed))/gather", ()),
    ("jit(burst_n4_k4_b4)/while/body/pick/argmax", ("pick", )),
    ("jit(f)/mixer_layer/ssm_mixer/ssm_update/ssm_state/pallas_call",
     ("mixer_layer", "ssm_mixer", "ssm_update", "ssm_state")),
    ("jit(f)/heads/picks/embedding", ()),          # whole components only
    ("", ()),
])
def test_scope_path(op_name, path):
    assert program_scopes.scope_path(op_name) == path


def test_executables_of_one_name_agree_or_say_that_they_do_not():
    one = scope_table(MODULE)
    other = scope_table(MODULE.replace("attn_kernel/paged_attention", "attn_qkv/paged_attention"))
    both = program_scopes.merged([one, other])
    assert both["paged_attention.4"].ambiguous and both["paged_attention.4"] == ()
    assert both["kv_write.3"] == ("kv_write", ) and not both["kv_write.3"].ambiguous
    assert program_scopes.merged([one]) is one


def test_the_module_keeps_perf_pys_contract_no_jax_no_numpy():
    tree = ast.parse(open(program_scopes.__file__).read())
    imported = {alias.name.split(".")[0] for node in ast.walk(tree) if isinstance(node, ast.Import)
                for alias in node.names}
    imported |= {node.module.split(".")[0] for node in ast.walk(tree)
                 if isinstance(node, ast.ImportFrom) and node.module}
    assert not imported & {"jax", "numpy", "jaxlib"}, imported


@pytest.mark.parametrize("filename,found", [("deepspeed_tpu/monitor/program_scopes.py", 1),
                                            ("deepspeed_tpu/monitor/some_other.py", 0)])
def test_dslints_whole_file_scan_holds_the_module_to_it(filename, found):
    from tests.unit.staticcheck.test_rules import rules_of, run
    out = run("""
        import numpy as np

        def tables(names, executable):
            return np.asarray(executable)
        """, ["host-sync-in-hot-path"], filename=filename)
    assert rules_of(out) == ["host-sync-in-hot-path"] * found


# ------------------------------------------------------------- the source scan
def _named_scopes():
    """``[(file, line, name)]`` of every ``named_scope(...)`` call under the package."""
    found = []
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            for node in ast.walk(ast.parse(open(path).read())):
                if isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "named_scope":
                    arg = node.args[0] if node.args else None
                    found.append((os.path.relpath(path, PACKAGE), node.lineno,
                                  arg.value if isinstance(arg, ast.Constant) else None))
    return found


def test_every_named_scope_in_the_package_takes_a_name_of_SCOPES():
    sites = _named_scopes()
    assert len(sites) >= 40
    strangers = [site for site in sites if site[2] not in SCOPES + KINDS + INNER]
    assert not strangers, f"named_scope with a name that is not in program_scopes.SCOPES: {strangers}"


@pytest.mark.parametrize("scope", SCOPES + KINDS + INNER)
def test_every_name_of_SCOPES_is_used_somewhere(scope):
    assert any(name == scope for _, _, name in _named_scopes()), \
        f"{scope!r} is in SCOPES and no named_scope takes it: delete it"
    assert len(set(SCOPES + KINDS + INNER)) == len(SCOPES + KINDS + INNER)


# ------------------------------------------------------------------ the registry
class _Owner:
    pass


def test_nothing_is_read_until_asked_and_then_once():
    registry, owner, reads = Registry(), _Owner(), []

    class Held:  # a Compiled
        def as_text(self):
            reads.append("held")
            return MODULE

    def thunk():  # a lazily jitted program: compile again, then a Compiled
        reads.append("thunk")
        return Held()

    registry.register(owner, "fwd_n4_t1_b4", Held())
    registry.register(owner, "burst_n4_k4_b4", thunk)
    registry.register(owner, "as_text", lambda: MODULE)
    assert reads == [] and registry.names() == ["as_text", "burst_n4_k4_b4", "fwd_n4_t1_b4"]
    tables = registry.tables(["fwd_n4_t1_b4", "nobody_built_this"])
    assert list(tables) == ["fwd_n4_t1_b4"] and reads == ["held"]
    assert tables["fwd_n4_t1_b4"]["kv_write.3"] == ("kv_write", )
    assert registry.tables(owner=owner).keys() == {"as_text", "burst_n4_k4_b4", "fwd_n4_t1_b4"}
    assert reads == ["held", "thunk", "held"]
    registry.tables()
    assert reads == ["held", "thunk", "held"]  # cached a program
    assert registry.tables(owner=_Owner()) == {}


def test_a_dead_owner_leaves_and_only_the_last_to_die_is_remembered():
    registry = Registry()
    first, second = _Owner(), _Owner()
    registry.register(first, "first_program", lambda: MODULE)
    registry.register(second, "second_program", lambda: MODULE)
    alive = weakref.ref(first)
    del first
    gc.collect()
    assert alive() is None  # the registry kept it not
    # the benchmark's entries drop their engine before the trace is read: the last to die stays
    assert registry.names() == ["first_program", "second_program"]
    assert "kv_write.3" in registry.tables(["first_program"])["first_program"]
    del second
    gc.collect()
    assert registry.names() == ["second_program"]
    registry.clear()
    assert registry.names() == []


def test_a_first_call_notes_its_arguments_once_and_steps_aside():
    slot, seen = {}, []

    def program(a, b):
        return a + b
    program.lower = lambda *args: ("lowered", args)

    def note(fn, args):
        seen.append((fn, args))
        slot["program"] = fn
    slot["program"] = FirstCall(program, note)
    assert slot["program"].lower(1) == ("lowered", (1, ))  # everything else is the program's own
    assert slot["program"](1, 2) == 3 and seen == [(program, (1, 2))]
    assert slot["program"] is program and slot["program"](2, 2) == 4 and len(seen) == 1


def test_a_call_under_a_trace_is_no_dispatch_and_hands_over_nothing():
    """The FLOPs profiler lowers the train step inside another jit: tracers are no shapes to
    compile at, and the first REAL call still has to find the wrapper in place."""
    import numpy as np
    from deepspeed_tpu.monitor import compile_events
    program = jax.jit(lambda x: x + 1)
    got = []
    jax.jit(lambda x: got.append(compile_events.compile_later(program, (x, ))) or x).lower(
        np.ones(3, np.float32))
    assert got == [None]
    later = compile_events.compile_later(program, (np.ones(3, np.float32), ))
    assert "HloModule" in later().as_text()


# ------------------------------------------------------- the engines' programs
def _tiny_engine(conf=None):
    cfg = llama.LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=2, kv_heads=2, seq=256)
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    return InferenceEngineV2(llama, cfg, params, config={"dtype": "float32", **(conf or {})},
                             num_blocks=32, block_size=8, max_blocks_per_seq=8, token_budget=32,
                             max_seqs_per_step=4)


@pytest.fixture(scope="module")
def served():
    """A tiny engine that served a wave (forwards, a pick, a burst, the fast
    path's scatter), its tables and its tokens.  The process's registry begins empty:
    ``tables(names)`` with no owner merges ANYONE's programs of a name, the engine that died
    last included, and an earlier file of this worker (``test_compile_events.py``) leaves a
    ``fwd_*`` of the same bucket's name behind."""
    program_scopes.REGISTRY.clear()
    engine = _tiny_engine()
    tokens = engine.generate([[1, 2, 3], [4, 5, 6, 7], [8, 9]], max_new_tokens=8)
    return engine, engine.program_scopes(), tokens


def _scopes_in(table):
    return {name for path in table.values() for name in path}


def test_every_program_the_ledger_recorded_has_its_table(served):
    engine, tables, _ = served
    recorded = {event["name"] for event in engine.ledger.events}
    assert recorded == set(tables) and {"fwd", "burst", "pick", "scatter"} <= set(engine.ledger.by_site)
    assert all(tables.values())
    one = next(name for name in recorded if name.startswith("fwd_"))
    assert engine.program_scopes(one) == {one: tables[one]}
    # the operator's door reads this engine's programs, the module's function anyone's
    assert program_scopes.tables([one])[one] == tables[one]


@pytest.mark.parametrize("program,scopes", [
    ("fwd_", {"embed", "attn_qkv", "kv_write", "attn_kernel", "layer_finish", "dense_ffn", "head"}),
    ("burst_", {"embed", "attn_qkv", "kv_write", "attn_kernel", "layer_finish", "dense_ffn", "head",
                "pick"}),
    ("pick_", {"pick"}),
    ("_scatter_impl", set()),
])
def test_a_programs_table_holds_the_scopes_its_operations_lie_under(served, program, scopes):
    _, tables, _ = served
    mine = [table for name, table in tables.items() if name.startswith(program)]
    assert mine
    for table in mine:
        assert _scopes_in(table) == scopes
    if scopes:  # nested as the source nests them
        assert any(path[-2:] == ("layer_finish", "dense_ffn") for t in mine for path in t.values()) \
            or program == "pick_"


def test_a_lazily_jitted_program_is_bare_after_its_first_call(served):
    engine, _, _ = served
    lazy = [key for key in engine._fwd_cache if isinstance(key, tuple) and key[0] in ("burst", "pick")]
    assert lazy and not any(isinstance(engine._fwd_cache[key], FirstCall) for key in lazy)
    key = next(k for k in lazy if k[0] == "burst")
    assert f"_b{key[3]}" in next(e["name"] for e in engine.ledger.events if e["site"] == "burst")


def test_a_dead_engine_leaves_the_registry():
    program_scopes.REGISTRY.clear()
    engines = [_tiny_engine(), _tiny_engine()]
    for engine in engines:
        engine.generate([[1, 2, 3]], max_new_tokens=2)
    refs = [weakref.ref(engine) for engine in engines]
    assert program_scopes.REGISTRY.names()
    engines.clear()
    del engine
    gc.collect()
    assert all(ref() is None for ref in refs)
    assert not program_scopes.REGISTRY._owners  # the last to die alone is remembered
    program_scopes.REGISTRY.clear()
    assert program_scopes.REGISTRY.names() == []


@pytest.mark.parametrize("stage", [0, 3])
def test_the_train_step_has_its_table_after_its_first_step(stage):
    from tests.unit.simple_model import random_batch
    from tests.unit.test_engine import HIDDEN, make_engine
    engine = make_engine(stage=stage, extra_cfg={"gradient_clipping": 1.0})
    assert engine.program_scopes() == {}  # before the first step: nothing built
    assert isinstance(engine.train_step_fn, FirstCall)
    engine.train_batch(random_batch(engine.train_batch_size, hidden=HIDDEN, seed=1))
    assert not isinstance(engine.train_step_fn, FirstCall)
    table = engine.program_scopes()["train_step"]
    assert {"forward_backward", "grad_norm_clip", "optimizer"} <= _scopes_in(table)
    assert engine.program_scopes("train_step")["train_step"] is table


# ------------------------------------------- a scope is metadata: same program
def _stripped(text):
    """The module less its metadata: each instruction's ``metadata={...}`` and the
    tables of files, functions, locations and stack frames those point into."""
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", "\n", text)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


NEW_SCOPES = ("embed", "attn_qkv", "kv_write", "attn_kernel", "layer_finish", "mixer_layer", "head",
              "dense_ffn", "pick")


@pytest.fixture(scope="module")
def served_without_the_new_scopes(served):
    """The same wave by an engine for which the scopes this PR added do not exist."""
    import contextlib
    real = jax.named_scope
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext()
                  if name in NEW_SCOPES else real(name))
    try:
        engine = _tiny_engine()
        tokens = engine.generate([[1, 2, 3], [4, 5, 6, 7], [8, 9]], max_new_tokens=8)
        texts = {name: _program_text(engine, name) for name in _programs(engine)}
    finally:
        patch.undo()
    return tokens, texts


def _programs(engine):
    return sorted(e["name"] for e in engine.ledger.events if e["site"] in ("fwd", "burst"))


def _program_text(engine, name):
    """The optimized text of one of the engine's programs, as the registry would read it."""
    held = program_scopes.REGISTRY._owners[id(engine.ledger)][1][name]
    assert len(held) == 1
    return program_scopes._text_of(held[0].source)


def test_the_optimized_text_is_the_same_once_metadata_is_stripped(served_without_the_new_scopes):
    tokens_without, texts_without = served_without_the_new_scopes
    engine = _tiny_engine()
    assert engine.generate([[1, 2, 3], [4, 5, 6, 7], [8, 9]], max_new_tokens=8) == tokens_without
    programs = _programs(engine)
    assert programs == sorted(texts_without) and any(p.startswith("burst_") for p in programs)
    for name in programs:
        with_scopes = _program_text(engine, name)
        assert "layer_finish" in with_scopes and "layer_finish" not in texts_without[name]
        assert _stripped(with_scopes) == _stripped(texts_without[name]), name
