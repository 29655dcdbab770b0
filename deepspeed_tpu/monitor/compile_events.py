"""The set-up account (ISSUE 36): where the time before the first warm step went.

JAX times every stage of getting a program onto the device and hands the
time to whoever listens (``jax.monitoring``): tracing the Python function to a
jaxpr, lowering the jaxpr to an MLIR module, and the backend's part, which is
an XLA compile where the persistent cache misses and key + read + decompress
+ deserialise where it hits.  This module is the ONE place that listens
(``monitor/perf.py`` keeps its contract: no ``jax``, no ``numpy``).  Each
outermost event becomes a :class:`Row` under the program's own name, the name
the serving programs are jitted under (``fwd_n32_t1_b20``) and the
``CompileLedger`` stores, so the ledger joins its records to these rows by
name (``CompileLedger.snapshot``) and the benchmark's ``setup.*`` metrics read
them cut at the window's start (``chipbench/reduce/setup_account.py``).

- **One account a process.**  JAX's listener registry is process-wide, so the
  account is: :func:`install` registers once however many engines a process
  builds, and every engine's ledger reads the same :data:`ACCOUNT`.
- **Clock.**  ``time.perf_counter()``, read when an event arrives (an event
  arrives as its stage ends; its start is the arrival less the duration JAX
  reports).  It is the clock ``chipbench/run.py`` starts ``setup_s`` on, so
  stages sum against ``setup_s``.  JAX's own span stamps are ``time.time()``
  and are not kept.  No device trace covers set-up, so there is no device
  clock to share; nothing here is read on a step.
- **Nesting.**  Every ``jnp`` operator on a traced value is itself a ``jit``
  and fires its own trace event, which closes inside the span of the program
  being traced and arrives before it.  Seconds are summed over OUTERMOST spans
  only: an arriving span swallows the rows of its thread that started inside
  it, and keeps their number as ``inner_traces``.  JAX fires the event where
  its own trace cache misses, so an operator at a shape is counted once a
  process, on the program that met it first: ``inner_traces`` is the count of
  operator-level traces a set-up paid in full (chat-burst on the chip: 3,532
  under 162 programs), not of operators traced.
- **Threads.**  A thread writes only its own book (rows, totals), found
  through a ``threading.local``: the callback takes no lock, so a compile
  thread never waits on another.  Readers sum the books.
- **Cost.**  The callback reads the clock twice and keeps the difference
  (``totals()["callback_s"]`` over ``totals()["events"]``): what listening
  costs is part of the account.  No event fires on a cached dispatch, so a
  warm step pays nothing.
"""

import collections
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
from jax import monitoring

TRACE, LOWER, LOAD = "trace", "lower", "load"
ENGINE_INIT = "engine_init"  # a program span: an engine's construction

_STAGE_OF = {"/jax/core/compile/jaxpr_trace_duration": TRACE,
             "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
             "/jax/core/compile/backend_compile_duration": LOAD}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

MAX_ROWS = 4096  # a thread's newest rows; a chat-burst set-up is ~170 executables, ~700 rows


def program_name(fun_name: str) -> str:
    """One name for a program however a stage spells it: the trace event's
    ``fwd_n32_t1_b20``, the lowering and backend events' ``jit(fwd_n32_t1_b20)``
    and the device trace's module ``jit_fwd_n32_t1_b20``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    if fun_name.startswith("jit_"):
        return fun_name[4:]
    return fun_name


class Row:
    """One outermost span.  ``cache_hit`` and ``retrieval_s`` are a ``load``
    row's: whether the persistent cache answered with the executable inside
    it on the same thread, and how long the read took.  A load without a hit
    was an XLA compile, whatever the reason (no cache directory, a program the
    cache does not take, an entry not there): JAX's own ``cache_misses`` event
    fires only where it then WRITES an entry (a compile over
    ``jax_persistent_cache_min_compile_time_secs``, with a directory set), so
    it is not what is counted.  Nothing writes a row once its thread has kept
    it."""

    __slots__ = ("stage", "program", "start", "end", "inner_traces", "cache_hit",
                 "retrieval_s", "thread")

    def __init__(self, stage, program, start, end, inner_traces, thread):
        self.stage, self.program, self.start, self.end = stage, program, start, end
        self.inner_traces, self.thread = inner_traces, thread
        self.cache_hit, self.retrieval_s = False, 0.0


def _no_totals() -> Dict[str, Any]:
    return {"trace_s": 0.0, "lower_s": 0.0, "load_s": 0.0, "engine_init_s": 0.0,
            "traces": 0, "lowers": 0, "loads": 0, "inner_traces": 0,
            "cache_hits": 0, "cache_misses": 0, "retrieval_s": 0.0}


def _add(into: Dict[str, Any], row: Row) -> None:
    into[row.stage + "_s"] += row.end - row.start
    if row.stage == ENGINE_INIT:
        return
    into[row.stage + "s"] += 1
    into["inner_traces"] += row.inner_traces
    if row.stage == LOAD:
        into["cache_hits" if row.cache_hit else "cache_misses"] += 1
        into["retrieval_s"] += row.retrieval_s


class _Book:
    """One thread's rows; only that thread writes it.  A row that leaves the
    bounded list is folded into ``dropped_totals`` / ``dropped_programs``, so
    the sums outlive the bound."""

    __slots__ = ("thread", "rows", "cache", "arrivals", "callback_s", "dropped",
                 "dropped_totals", "dropped_programs")

    def __init__(self, thread: int):
        self.thread = thread
        self.rows: collections.deque = collections.deque()
        self.cache = None  # the persistent cache's hits since the last load row, and when first
        self.arrivals = 0  # every row ever made here, the swallowed ones too
        self.callback_s = 0.0
        self.dropped = 0
        self.dropped_totals = _no_totals()
        self.dropped_programs: Dict[str, Dict[str, Any]] = {}

    def drop_oldest(self) -> None:
        oldest = self.rows.popleft()
        self.dropped += 1
        _add(self.dropped_totals, oldest)
        _add(self.dropped_programs.setdefault(oldest.program, _no_totals()), oldest)


class Account:
    """The rows of every thread, and their sums by stage and by program.
    ``install()`` feeds the process's one (:data:`ACCOUNT`) from JAX's events;
    a test builds its own and feeds it by :meth:`arrive` and :meth:`span`.

    An arrival does a constant amount of work (a row, an append, and a pop
    for each row it swallows): thousands of operator-level traces fire in a
    set-up.  The sums are made when somebody reads (a few times a process),
    over the kept rows and what the dropped ones left."""

    def __init__(self):
        self._local = threading.local()
        self._books: List[_Book] = []

    # ------------------------------------------------------------- writing
    def _book(self) -> _Book:
        try:
            return self._local.book
        except AttributeError:
            book = self._local.book = _Book(threading.get_ident())
            self._books.append(book)  # one append a thread: atomic under the GIL
            return book

    def arrive(self, stage: str, fun_name: str, start: float, end: float) -> _Book:
        """A stage of a program ended on this thread.  The rows of the thread
        that started inside it are its own work seen twice: they go, and the
        traces among them are counted on the new row."""
        try:  # the one hot path: no call but the row's own (frames cost 2 us each on a serving host)
            book = self._local.book
        except AttributeError:
            book = self._book()
        rows = book.rows
        inner, twice = 0, 2 * start
        # by a row's middle: both ends are read a few microseconds late
        while rows and rows[-1].start + rows[-1].end >= twice:
            gone = rows.pop()
            inner += gone.inner_traces + (gone.stage == TRACE)
        # a trace event bears the function's own name, and most arrivals are
        # operator-level traces the next span swallows: only the later stages'
        # ``jit(...)`` is unwrapped
        program = fun_name if stage == TRACE else program_name(fun_name)
        row = Row(stage, program, start, end, inner, book.thread)
        if stage == LOAD and book.cache is not None:
            said, book.cache = book.cache, None
            if said["first"] >= start and said["hits"]:
                row.cache_hit, row.retrieval_s = True, said["retrieval_s"]
        if len(rows) >= MAX_ROWS:
            book.drop_oldest()
        rows.append(row)
        book.arrivals += 1
        return book

    def span(self, stage: str, program: str, start: float, end: float) -> None:
        """A span the program measured itself (``engine_init``).  It covers
        JAX's rows inside it and swallows none: a reader takes them out."""
        book = self._book()
        if len(book.rows) >= MAX_ROWS:
            book.drop_oldest()
        book.rows.append(Row(stage, program, start, end, 0, book.thread))
        book.arrivals += 1

    def on_duration(self, event: str, duration: float, fun_name: str = "", **_) -> None:
        """``jax.monitoring``'s duration listener."""
        now = time.perf_counter()
        stage = _STAGE_OF.get(event)
        if stage is not None:
            book = self.arrive(stage, fun_name, now - duration, now)
            book.callback_s += time.perf_counter() - now
        elif event == _CACHE_RETRIEVAL:
            self._cache_said(now, "retrieval_s", duration)

    def on_event(self, event: str, **_) -> None:
        """``jax.monitoring``'s plain listener: the persistent cache's hits."""
        if event == _CACHE_HIT:
            self._cache_said(time.perf_counter(), "hits", 1)

    def _cache_said(self, now: float, what: str, amount) -> None:
        book = self._book()
        if book.cache is None:
            book.cache = {"first": now, "hits": 0, "retrieval_s": 0.0}
        book.cache[what] += amount

    # ------------------------------------------------------------- reading
    def rows(self, until: Optional[float] = None) -> List[Row]:
        """Every thread's kept rows that ended at or before ``until``, by end."""
        found = [row for book in list(self._books) for row in _settled(book.rows)
                 if until is None or row.end <= until]
        return sorted(found, key=lambda row: row.end)

    def _sum(self, until: Optional[float]):
        """(totals, the same by program) over the kept rows that ended at or
        before ``until`` and over every dropped row (the oldest there were)."""
        books = list(self._books)
        total, programs = _no_totals(), {}
        for book in books:
            _merge(total, book.dropped_totals)
            for name, program in _settled(book.dropped_programs, dict).items():
                _merge(programs.setdefault(name, _no_totals()), program)
        for row in self.rows(until):
            _add(total, row)
            _add(programs.setdefault(row.program, _no_totals()), row)
        total.update(events=sum(book.arrivals for book in books),
                     dropped=sum(book.dropped for book in books),
                     callback_s=sum(book.callback_s for book in books))
        return total, programs

    def totals(self, until: Optional[float] = None) -> Dict[str, Any]:
        """Seconds and counts by stage: ``trace_s``, ``lower_s``, ``load_s``,
        ``engine_init_s``, ``traces``, ``lowers``, ``loads``, ``inner_traces``,
        ``cache_hits``, ``cache_misses`` (loads with and without a hit: the
        two sum to ``loads``), ``retrieval_s``; and of the account
        itself ``events`` (arrivals), ``callback_s`` (spent in the listener)
        and ``dropped`` (rows past the bound).  With ``until``, of the rows
        that ended at or before it: a reader cuts at its window's start."""
        return self._sum(until)[0]

    def by_program(self, until: Optional[float] = None) -> Dict[str, Dict[str, Any]]:
        """The same seconds and counts under each program's name."""
        return self._sum(until)[1]


def _merge(into: Dict[str, Any], other: Dict[str, Any]) -> None:
    for key, value in other.items():
        into[key] += value


def _settled(shared, copy: Callable = list):
    """A copy of something its own thread may be writing: ``list(deque)`` and
    ``dict(d)`` raise if it changes size under them, which is rare and brief."""
    while True:
        try:
            return copy(shared)
        except RuntimeError:
            continue


ACCOUNT = Account()
_INSTALL = threading.Lock()  # held only to register: no callback takes it
_installed = False


def install() -> Account:
    """Register the account's two listeners with ``jax.monitoring``, once a
    process however often it is called, and return the account."""
    global _installed
    with _INSTALL:
        if not _installed:
            monitoring.register_event_duration_secs_listener(ACCOUNT.on_duration)
            monitoring.register_event_listener(ACCOUNT.on_event)
            _installed = True
    return ACCOUNT


def compile_later(fn: Callable, args) -> Optional[Callable]:
    """For ``monitor/program_scopes.py``: a thunk that lowers and compiles the
    jitted ``fn`` again at the shapes, dtypes and placements of ``args`` (read
    here, so a donated argument may go), which hits JAX's caches where the
    program ran.  It holds ``fn`` and shapes, never an array.  None where
    ``args`` are tracers: ``fn`` is being traced into another program (the FLOPs
    profiler lowers the train step so), which is no dispatch of it."""
    leaves = jax.tree_util.tree_leaves(args)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return None

    def abstract(x):
        if not hasattr(x, "shape"):
            return x
        placed = x.sharding if isinstance(x, jax.Array) and x.committed else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=placed)

    avals = jax.tree_util.tree_map(abstract, tuple(args))
    return lambda: fn.lower(*avals).compile()


def engine_init(init: Callable) -> Callable:
    """Around an engine's ``__init__``: installs the account before the
    engine's first program and records the construction as an ``engine_init``
    row under the class's name.  Two reads of ``perf_counter``, never the
    engine's injectable clock (a FakeClock must not see them)."""

    @functools.wraps(init)
    def timed(self, *args, **kwargs):
        account = install()
        start = time.perf_counter()
        try:
            return init(self, *args, **kwargs)
        finally:
            account.span(ENGINE_INIT, type(self).__name__, start, time.perf_counter())

    return timed
