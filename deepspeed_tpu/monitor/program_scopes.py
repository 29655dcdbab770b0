"""Which scope each operation of a compiled program belongs to (ISSUE 54).

The model and the train step name their parts with ``jax.named_scope``; the
compiler keeps the names as ``metadata={op_name="jit(fwd_n32_t1_b20)/while/
body/layer_finish/moe_expert_ffn/dot_general"}`` on every instruction of the
optimized module, and a device trace names an operation by that instruction
(``%fusion.735``) inside a program event named by the module
(``jit_fwd_n32_t1_b20``).  The profile reader yields no scope of an operation
event, but the program HOLDS the executables that ran, so it can say for each
of them which scope every instruction lies under; a reader lays that table over
the trace's operation line (``chipbench/reduce/scopes.py``), and an operator
who captures a profile of a running server asks the engine for the same table
(``InferenceEngineV2.program_scopes()``, ``Engine.program_scopes()``).

- :data:`SCOPES` is the ONE list of scope names, as ``monitor.perf.PHASES`` is
  the one list of phases: a ``jax.named_scope`` anywhere under
  ``deepspeed_tpu/`` takes a name of it, of :data:`KINDS` or of :data:`INNER` (a source scan in
  ``tests/unit/monitor/test_program_scopes.py`` holds that).
- :func:`scope_table` is a pure function over the text of an optimized module
  (``jax.stages.Compiled.as_text()``).
- :data:`REGISTRY` is the process's registry, like
  ``compile_events.ACCOUNT``: an engine registers, where it builds a program,
  the program's name and a way to get its text LATER (the ``Compiled`` it
  holds, or a thunk that lowers and compiles again at the shapes it
  dispatches: a hit of JAX's caches).  Registering is a dictionary store a
  program built and nothing a step; ``as_text()`` and the parse run only when
  :func:`tables` is asked, once a program.

Same contract as ``monitor/perf.py``: nothing here imports ``jax`` or
``numpy``; the engines hand in objects with ``as_text()`` or thunks.

**Who is kept alive.**  An owner (an engine) is held by a weak reference and
its programs leave with it, so a process that builds hundreds of engines keeps
none of them, nor their executables.  One exception, bounded: the programs of
the owner that died LAST stay until the next owner dies (the benchmark's
entries drop their engine, to make room for the reference, before the trace
is read).  A source must therefore hold no engine: a ``Compiled``, or a
closure over a jitted function and abstract shapes.
"""

import functools
import re
import threading
import weakref
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

# every name a ``jax.named_scope`` under deepspeed_tpu/ may take, by the
# part of the step it marks (the groups chipbench/reduce/scopes.py sums by)
SCOPES = (
    # the paged layer driver every family shares (models/transformer.py paged_forward)
    "embed", "attn_qkv", "kv_write", "attn_kernel", "layer_finish", "head",
    # sampling, in a step's pick program and in a burst's body (engine_v2.py)
    "pick",
    # inside attention: absorbed MLA, the DSA indexer and selection, a gated output
    "mla_absorb", "dsa_index", "dsa_select", "attn_gate",
    # the dense FFN (swiglu_mlp / gelu_mlp)
    "dense_ffn",
    # the expert FFN (moe/serving.py) and LongCat's shortcut around it
    "moe_route", "moe_expert_ffn", "moe_shared_expert", "moe_shared_gate", "moe_identity",
    "scmoe_shortcut",
    # layers without attention (the driver's scope around a family's ``mix``) and their state a sequence
    "mixer_layer", "conv_mixer", "gdn_mixer", "gdn_scan", "gdn_state",
    "ssm_mixer", "ssm_scan", "ssm_update", "ssm_state", "seq_state",
    # the train step (runtime/engine.py)
    "forward_backward", "grad_norm_clip", "optimizer",
)
# A layer's KIND, where a family's attention layers differ in their window (``paged_forward``
# opens one inside ``attn_qkv`` and ``attn_kernel``): kept on an instruction's path like a scope, and
# no part of a step of its own, so in no group: the operations stay their enclosing scope's
# (the benchmark's groups are made of :data:`SCOPES`, name for name).
KINDS = ("attn_window", "attn_full")
# A family's own names INSIDE a driver's scope (Kimi Delta Attention's, inside ``mixer_layer``):
# kept on the path like a kind, so that a reader can tell the gate, the scan and the update apart;
# the operation's group is its enclosing scope's (``mixer_layer``: the mixers').
INNER = ("kda_mixer", "kda_gate", "kda_scan", "kda_update", "kda_state")
_SCOPE_SET = frozenset(SCOPES + KINDS + INNER)

_OP_NAME = 'op_name="'
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_REFERENCE = re.compile(r"%([\w.\-]+)")  # an operand (or a computation called: no instruction's name)
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
_FUSION_CALLS = re.compile(r"\sfusion\(.*?calls=%?([\w.\-]+)")
# a transformation wraps the scope's name: transpose(jvp(forward_backward))
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_NOT_A_SCOPE = ("jit", "pjit")  # jit(head) is a function of that name, not a scope


class Path(tuple):
    """The names of :data:`SCOPES`, :data:`KINDS` and :data:`INNER` on an instruction's path, outermost first.
    ``mixed``: for a fusion, the innermost scopes of the instructions fused into
    it that are not its own.  ``inherited``: the path is not the instruction's
    own but that of the only instructions that read it.  ``ambiguous``: several
    executables under one program name disagree on this instruction (no path
    then)."""

    mixed: Tuple[str, ...] = ()
    ambiguous = inherited = False

    def __new__(cls, names=(), mixed=(), ambiguous=False, inherited=False):
        self = super().__new__(cls, names)
        self.mixed, self.ambiguous, self.inherited = tuple(mixed), ambiguous, inherited
        return self


@functools.lru_cache(maxsize=1 << 16)
def scope_path(op_name: str) -> Tuple[str, ...]:
    """``("layer_finish", "moe_expert_ffn")`` of ``"jit(fwd)/while/body/
    layer_finish/moe_expert_ffn/dot_general"``."""
    found = []
    for part in op_name.split("/"):
        wrapped = _WRAPPED.match(part)
        while wrapped and wrapped.group(1) not in _NOT_A_SCOPE:
            part = wrapped.group(2)
            wrapped = _WRAPPED.match(part)
        if part in _SCOPE_SET:
            found.append(part)
    return tuple(found)


def scope_table(hlo_text: str) -> Dict[str, Path]:
    """``{instruction: Path}`` for every instruction of an optimized module
    that can be an event of a trace (the instructions INSIDE a fused
    computation cannot: they are the fusion), its path read off its
    ``op_name`` (empty where it has none, or no scope lies on it).  A
    fusion takes its own ``op_name`` (the compiler gives it its root's); where
    the instructions fused into it lie under other innermost scopes the entry
    says so (``Path.mixed``).  An instruction under no scope whose result only
    instructions of ONE path read (through others under no scope) takes that
    path, ``Path.inherited``: a layer scan's slice of the stacked weights is no
    line of the source that a scope could wrap, and belongs to the product that
    reads it.  Instruction names are as the text gives them less ``%``."""
    paths: Dict[str, Tuple[str, ...]] = {}     # instruction -> path
    readers: Dict[str, list] = {}              # instruction -> the instructions that read it
    containers = set()
    inside: Dict[str, set] = {}                # computation -> innermost scopes of its instructions
    home: Dict[str, str] = {}                  # instruction -> the computation it stands in
    fusions: Dict[str, str] = {}               # fusion instruction -> the computation it calls
    computation = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            header = _HEADER.match(line)
            computation = header.group(1) if header else None
            continue
        found = _INSTRUCTION.match(line)
        if found is None:
            continue
        name = found.group(1)
        if _CONTAINER.search(line, found.end()):
            containers.add(name)  # its time is its children's: it inherits nothing
        if " fusion(" in line:
            calls = _FUSION_CALLS.search(line)
            if calls:
                fusions[name] = calls.group(1)
        at = line.rfind(_OP_NAME)  # none: an instruction the compiler made (a copy), under no scope
        path = scope_path(line[at + len(_OP_NAME):line.index('"', at + len(_OP_NAME))]) if at >= 0 else ()
        paths[name], home[name] = path, computation
        if path:
            inside.setdefault(computation, set()).add(path[-1])
        for read in _REFERENCE.findall(line, found.end(), at if at >= 0 else len(line)):
            readers.setdefault(read, []).append(name)
    fused = set(fusions.values())

    def read_under(name):
        """The paths under which ``name``'s result is read, followed through
        readers under no scope; () among them where it leaves its computation."""
        found, trail, seen = set(), [name], {name}
        while trail and len(found) < 2:  # two are enough to know it is nobody's
            read_by = readers.get(trail.pop())
            if not read_by:
                found.add(())
                continue
            for reader in read_by:
                if paths[reader]:
                    found.add(paths[reader])
                elif reader not in seen:
                    seen.add(reader)
                    trail.append(reader)
        return found

    interned: Dict[Any, Path] = {}
    table = {}
    for name, path in paths.items():
        if home[name] in fused:
            continue
        mixed, inherited = (), False
        if name in fusions:
            mixed = tuple(sorted(inside.get(fusions[name], set()) - set(path[-1:])))
        if not path and name not in containers:
            under = read_under(name)
            if len(under) == 1 and () not in under:
                (path, ), inherited = under, True
        key = (path, mixed, inherited)
        if key not in interned:
            interned[key] = Path(path, mixed, inherited=inherited)
        table[name] = interned[key]
    return table


def merged(tables: Iterable[Dict[str, Path]]) -> Dict[str, Path]:
    """One table of several executables under one program name (a jitted
    function met at several shapes): an instruction on which they agree keeps
    its path, one on which they do not is ``Path.ambiguous``."""
    tables = list(tables)
    if len(tables) == 1:
        return tables[0]
    out: Dict[str, Path] = {}
    unsure = Path(ambiguous=True)
    for table in tables:
        for name, path in table.items():
            known = out.setdefault(name, path)
            if known is not path and (tuple(known) != tuple(path) or known.mixed != path.mixed
                                      or known.inherited != path.inherited):
                out[name] = unsure
    return out


def _text_of(source) -> str:
    """A source is a thing with ``as_text()`` (a ``Compiled``), or a thunk that
    returns one or the text itself."""
    got = source if hasattr(source, "as_text") else source()
    return got if isinstance(got, str) else got.as_text()


class _Program:
    """One registered executable: how to get its text, and its table once asked."""

    __slots__ = ("source", "table")

    def __init__(self, source):
        self.source, self.table = source, None

    def read(self) -> Dict[str, Path]:
        if self.table is None:
            self.table = scope_table(_text_of(self.source))
            self.source = None  # the text is read once: let the executable go
        return self.table


class Registry:
    """``{owner: {program name: [executables]}}``, owners held weakly."""

    def __init__(self):
        self._lock = threading.RLock()  # a weak reference's callback may fire inside register
        self._owners: Dict[int, Tuple[Any, Dict[str, list]]] = {}
        self._departed: Dict[str, list] = {}

    def register(self, owner, name: str, source) -> None:
        """``owner`` built or first cached the program ``name``; ``source``
        gives its optimized text when asked (:func:`_text_of`)."""
        key = id(owner)
        with self._lock:
            if key not in self._owners:
                self._owners[key] = (weakref.ref(owner, functools.partial(self._left, key)), {})
            self._owners[key][1].setdefault(name, []).append(_Program(source))

    def _left(self, key, _ref=None) -> None:
        with self._lock:
            _, programs = self._owners.pop(key, (None, None))
            if programs:
                self._departed = programs

    def _programs(self, owner=None):
        with self._lock:
            if owner is not None:
                found = self._owners.get(id(owner))
                return [found[1]] if found else []
            return [programs for _, programs in self._owners.values()] + [self._departed]

    def names(self, owner=None):
        return sorted({name for programs in self._programs(owner) for name in programs})

    def tables(self, names: Optional[Iterable[str]] = None, owner=None) -> Dict[str, Dict[str, Path]]:
        """``{program name: {instruction: Path}}`` of the programs ``names``
        (None: all) that ``owner`` registered (None: anyone, the owner that
        died last included); a name nobody registered is left out.  This is
        where the texts are read and parsed, once a program."""
        wanted = None if names is None else set(names)
        held: Dict[str, list] = {}
        for programs in self._programs(owner):
            for name, executables in programs.items():
                if wanted is None or name in wanted:
                    held.setdefault(name, []).extend(executables)
        return {name: merged(p.read() for p in executables) for name, executables in held.items()}

    def clear(self) -> None:
        with self._lock:
            self._owners.clear()
            self._departed = {}


REGISTRY = Registry()
register = REGISTRY.register
tables = REGISTRY.tables


class FirstCall:
    """A lazily jitted program until its first call: it notes the call's
    arguments' shapes for the registry (``seen(fn, args)``, which also puts the
    bare program in this wrapper's place; it declines a call made under a trace)
    and steps aside, so every later call is the jitted function's own.  Everything else (``lower``) is the
    function's."""

    def __init__(self, fn: Callable, seen: Callable):
        self._fn, self._seen = fn, seen

    def __call__(self, *args):
        self._seen(self._fn, args)
        return self._fn(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)
