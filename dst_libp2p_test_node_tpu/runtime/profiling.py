"""Host-side profiling harness: XLA cost accounting + retrace counting.

The ROADMAP's exact-mode item needs the microbenchmark-first methodology of
arXiv:1912.03413 — measure where each compiled program sits on the
roofline before optimizing it. This module derives that, per registered
EntrypointContract (analysis/registry.py), from XLA's own compile-time
analyses:

  entrypoint_cost   FLOPs / HBM bytes / peak-memory estimate via
                    jit(...).lower(...).compile().cost_analysis() and
                    .memory_analysis() — version-gated (the analysis
                    surfaces moved across jax releases; absent fields
                    come back None, never a crash)
  count_retraces    a context manager counting jit cache misses (one
                    backend_compile_duration event of jax.monitoring a
                    miss, from the compile ledger's listener) — the PR 1/
                    PR 3 carry bugs were exactly silent per-iteration
                    retraces
  measure_retraces  calls a contract's representative spec twice with
                    same-aval inputs and returns the SECOND call's
                    retrace count; EntrypointContract.retrace_budget
                    (default 0) turns any excess into a tier-1 failure
                    (tests/test_profiling.py)
  chrome_trace      flight-recorder curves (ops/telemetry.py) rendered as
                    Chrome-trace/perfetto JSON — one "X" slice per
                    heartbeat with the channel values in args, plus "C"
                    counter tracks for the scalar channels
  profiler_trace    optional jax.profiler capture around a block (the
                    `trace` CLI's --profile-dir)
  span / turn       the program's own host spans: `turn(...)` opens the
                    recorder of one `run` turn, `span(name)` notes a span
                    in it and opens a "sim:" TraceAnnotation, so a running
                    profiler session puts the span on the device trace's
                    clock; `counters` is the zero-length annotation that
                    carries a publish's device-side counters
  device_read       one device->host read (`jax.device_get`, a pytree in one
                    call is one read), counted a process
                    (`count_device_read` notes a read made by `np.asarray`):
                    a phase that wants to know how many reads it made takes
                    `device_reads()` before and after (runtime/campaign.py
                    does, for `sim:attack/counters`)
  process_record    what the process did up to the end of its first turn:
                    marks (package imported, cli.main, backend ready, the
                    first turn's start and end), the spans opened outside
                    a turn (`setup/backend`) and the compile ledger: every
                    program jax traced, lowered, compiled or loaded from
                    the persistent cache, from jax.monitoring's events
                    (`register_compile_listeners`, which
                    `enable_compile_cache()` calls), aggregated by
                    fun_name; a turn's own share, of every turn, goes out
                    as `stats<i>.json` "compile"
"""

from __future__ import annotations

import contextvars
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np


class RetraceCounter:
    """Mutable counter handed out by count_retraces(); `events` holds the
    `fun_name` of each miss ("jit(disseminate)")."""

    def __init__(self):
        self.count = 0
        self.events: list[str] = []


_RETRACE_COUNTERS: list[RetraceCounter] = []


@contextmanager
def count_retraces():
    """Count jit cache misses (trace+compile events) inside the block: one
    backend_compile_duration event of the compile ledger's listener a miss.
    Persistent-compile-cache hits still count — they are in-memory cache
    MISSES (a full retrace happened; only the XLA backend compile was
    skipped), which is exactly what a retrace budget is about."""
    register_compile_listeners()
    counter = RetraceCounter()
    _RETRACE_COUNTERS.append(counter)
    try:
        yield counter
    finally:
        _RETRACE_COUNTERS.remove(counter)


def _dynamic(x) -> bool:
    """True when a spec argument is a device-traceable pytree (all leaves
    arrays): those stay jit parameters; everything else (params dataclasses,
    ints, None) is closed over as a static constant."""
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    return bool(leaves) and all(
        isinstance(leaf, (jax.Array, np.ndarray)) for leaf in leaves)


def lower_spec(spec, return_dynamic: bool = False,
               keep_unused: bool = False):
    """Lower a contract's TraceSpec to an XLA program: dynamic (array)
    arguments become jit parameters, static arguments are closure
    constants — the same split every registered entrypoint's own jit
    makes, so the compiled program is the one production calls run.

    With `return_dynamic` also returns the (dyn_args, dyn_kwargs) pytree
    the program was lowered against — the sharding auditor pairs its
    flattened leaves with `compiled.input_shardings` to name each operand
    when attributing replication and per-leaf footprints. That pairing
    needs `keep_unused=True`: by default jit PRUNES parameters the program
    never reads from the compiled executable, which would misalign the
    sharding leaves with the argument pytree."""
    import jax

    arg_dyn = [i for i, a in enumerate(spec.args) if _dynamic(a)]
    kw_dyn = sorted(k for k, v in spec.kwargs.items() if _dynamic(v))
    dyn_args = tuple(spec.args[i] for i in arg_dyn)
    dyn_kwargs = {k: spec.kwargs[k] for k in kw_dyn}

    def call(dyn_pos, dyn_kw):
        full = list(spec.args)
        for i, v in zip(arg_dyn, dyn_pos):
            full[i] = v
        kw = dict(spec.kwargs)
        kw.update(dyn_kw)
        return spec.fn(*full, **kw)

    lowered = jax.jit(call, keep_unused=keep_unused).lower(
        dyn_args, dyn_kwargs)
    if return_dynamic:
        return lowered, (dyn_args, dyn_kwargs)
    return lowered


def entrypoint_cost(contract) -> dict:
    """{flops, hbm_bytes, peak_memory_bytes} for the contract's
    representative program, from XLA's compile-time analyses. A count the
    backend's cost analysis does not report comes back None (strict-JSON
    null); an analysis call that fails raises."""
    compiled = lower_spec(contract.build()).compile()
    ca = compiled.cost_analysis() or {}

    def count(key):
        v = ca.get(key)
        return float(v) if v is not None and float(v) >= 0 else None

    ma = compiled.memory_analysis()
    return {
        "flops": count("flops"),
        "hbm_bytes": count("bytes accessed"),
        "peak_memory_bytes": (
            int(ma.argument_size_in_bytes) + int(ma.output_size_in_bytes)
            + int(ma.temp_size_in_bytes) - int(ma.alias_size_in_bytes)),
    }


def measure_retraces(contract) -> int:
    """Retrace count of a SECOND same-aval call of the contract's
    representative spec. The first call (fresh spec from contract.build())
    warms every jit cache on the path; the second builds the spec again —
    same shapes, same statics — and must hit every cache, so any count
    above contract.retrace_budget is aval drift at a call boundary."""
    import jax

    warm = contract.build()
    jax.block_until_ready(warm.thunk()())
    spec = contract.build()
    with count_retraces() as counter:
        jax.block_until_ready(spec.thunk()())
    return counter.count


@contextmanager
def profiler_trace(log_dir: str | None):
    """jax.profiler capture around the block when `log_dir` is set; a
    plain passthrough otherwise (and when the profiler is unavailable,
    e.g. a stripped jax build)."""
    if not log_dir:
        yield
        return
    try:
        import jax.profiler
        ctx = jax.profiler.trace(log_dir)
    except Exception:
        yield
        return
    with ctx:
        yield


# ------------------------------------------------------- host spans

# every annotation of the program starts with this, so a reader of the
# profile tells the program's spans from anything else on the host plane
SPAN_PREFIX = "sim:"


@dataclass
class Span:
    name: str
    start: float              # time.perf_counter()
    end: float | None         # None while open
    parent: int | None        # index of the enclosing span in the turn
    attrs: dict = field(default_factory=dict)


class TurnSpans:
    """The spans of one `run` turn, in order of opening, and what jax
    compiled in it. One turn owns one of these and drops it when the turn
    ends, so a process that loops over experiments keeps nothing."""

    def __init__(self, **attrs):
        self.attrs = attrs    # the turn's identifier, shared by its spans
        self.number = 0       # which turn of the process: 1, 2, ...
        self.spans: list[Span] = []
        self._open: list[int] = []
        self.compile = CompileTotals()

    def seconds(self, name: str) -> float:
        """Summed duration of the closed spans of that name."""
        return sum(s.end - s.start for s in self.spans
                   if s.name == name and s.end is not None)

    def totals(self) -> dict:
        """{name: {"count", "total_s"}}, what `stats<i>.json` "spans" holds;
        a span still open counts up to now."""
        now = time.perf_counter()
        out: dict = {}
        for s in self.spans:
            entry = out.setdefault(s.name, {"count": 0, "total_s": 0.0})
            entry["count"] += 1
            entry["total_s"] += (now if s.end is None else s.end) - s.start
        return out


_TURN: contextvars.ContextVar[TurnSpans | None] = contextvars.ContextVar(
    "dst_sim_turn_spans", default=None)
# the innermost span open outside any turn (`setup/backend`)
_OUTSIDE: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "dst_sim_span_outside_a_turn", default=None)


@contextmanager
def span(name: str, **attrs):
    """One host span of the program. Always a `jax.profiler.TraceAnnotation`
    named "sim:<name>" (a no-op costing well under a microsecond while no
    profiler session runs; on the device trace's clock while one does: the
    session is the switch), carrying the turn's identifier and `attrs`.
    Inside a `turn()` it is also noted in the turn's recorder; outside one
    only its count and seconds are added to the process record's "spans"."""
    from jax.profiler import TraceAnnotation

    turn_spans = _TURN.get()
    if turn_spans is None:
        token = _OUTSIDE.set(name)
        start = time.perf_counter()
        try:
            with TraceAnnotation(SPAN_PREFIX + name, **attrs):
                yield
        finally:
            entry = _PROCESS.spans.setdefault(
                name, {"count": 0, "total_s": 0.0})
            entry["count"] += 1
            entry["total_s"] += time.perf_counter() - start
            _OUTSIDE.reset(token)
        return
    index = len(turn_spans.spans)
    opened = turn_spans._open
    turn_spans.spans.append(
        Span(name, 0.0, None, opened[-1] if opened else None, attrs))
    opened.append(index)
    with TraceAnnotation(SPAN_PREFIX + name, **turn_spans.attrs, **attrs):
        turn_spans.spans[index].start = time.perf_counter()
        try:
            yield
        finally:
            turn_spans.spans[index].end = time.perf_counter()
            opened.pop()


@contextmanager
def turn(**attrs):
    """One `run` turn: a fresh recorder, current for the block, under one
    root span "run". Yields the recorder. Turns are numbered a process; the
    first leaves its start and end in the process record's marks, and its
    end closes the compile ledger."""
    turn_spans = TurnSpans(**attrs)
    _PROCESS.turns += 1
    turn_spans.number = _PROCESS.turns
    first = turn_spans.number == 1
    token = _TURN.set(turn_spans)
    try:
        if first:
            mark("turn1_start")
        with span("run"):
            yield turn_spans
    finally:
        if first:
            mark("turn1_end")
        _TURN.reset(token)


def counters(name: str, **values) -> None:
    """A zero-length "sim:<name>" annotation whose attributes are counters,
    for a reader of the profile; nothing is recorded on the host."""
    from jax.profiler import TraceAnnotation

    turn_spans = _TURN.get()
    ident = turn_spans.attrs if turn_spans is not None else {}
    with TraceAnnotation(SPAN_PREFIX + name, **ident, **values):
        pass


def count_device_read() -> None:
    """Note one device->host read that is made some other way than through
    `device_read` (an `np.asarray` of a device array, as
    `simulator.record_from_result` reads a publish's leaves)."""
    _PROCESS.device_reads += 1


def device_read(tree):
    """One device->host read, counted: `jax.device_get(tree)`, the arrays
    of a pytree fetched in the one call. What the host waits for, it waits
    for here; `device_reads()` says how many the process has made."""
    import jax

    count_device_read()
    return jax.device_get(tree)


def device_reads() -> int:
    """The device->host reads the process has counted so far."""
    return _PROCESS.device_reads


# ----------------------------------- process record and compile ledger

# jax.monitoring's names (jax._src.dispatch, jax._src.compiler): the three
# stages of one program, each delivered as a time span on time.time() with
# `fun_name`, and what the persistent cache did inside the third
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# recorded where an entry is written: the program took the store threshold
_CACHE_STORE_EVENT = "/jax/compilation_cache/cache_misses"

SLOWEST_KEPT = 5
# the disjoint trace intervals still open to a merge
_INTERVALS_KEPT = 256


class CompileTotals:
    """What jax traced, lowered, compiled and loaded in one stretch of the
    process: a turn, or with `by_fun` the ledger (up to the end of the
    first turn). Sums, a few bounded lists and there one entry a function's
    name, never a list of events."""

    def __init__(self, by_fun: bool = False):
        self.compiled = self.loaded = 0
        self.stored = self.compiled_under_threshold = 0
        self.compile_s = self.load_s = 0.0
        self.by_span: dict[str, float] = {}
        self.slowest: list[tuple] = []      # (seconds, fun_name, kind, ...)
        self.by_fun: dict[str, dict] | None = {} if by_fun else None
        # trace and lowering: the union of their intervals, so that a jit
        # traced inside another counts once. Disjoint, in order of end;
        # the oldest are folded into a sum
        self._intervals: list[tuple[float, float]] = []
        self._folded_s = 0.0

    def _fun(self, fun_name: str) -> dict | None:
        if self.by_fun is None:
            return None
        return self.by_fun.setdefault(fun_name, {
            "programs": 0, "compiled": 0, "loaded": 0, "stored": 0,
            "trace_lower_s": 0.0, "compile_s": 0.0, "load_s": 0.0,
            "span": None})

    def add_trace(self, fun_name: str, start: float, end: float) -> None:
        entry = self._fun(fun_name)
        if entry is not None:
            entry["trace_lower_s"] += end - start
        kept = self._intervals
        while kept and kept[-1][1] > start:     # inside or overlapping
            a, b = kept.pop()
            start, end = min(start, a), max(end, b)
        kept.append((start, end))
        if len(kept) > _INTERVALS_KEPT:
            half = _INTERVALS_KEPT // 2
            self._folded_s += sum(b - a for a, b in kept[:half])
            del kept[:half]

    def add_program(self, fun_name: str, kind: str, seconds: float,
                    stored: bool, threshold_s: float,
                    turn_number: int, span_name: str | None) -> None:
        if kind == "loaded":
            self.loaded += 1
            self.load_s += seconds
        else:
            self.compiled += 1
            self.compile_s += seconds
            self.stored += stored
            self.compiled_under_threshold += seconds < threshold_s
        where = span_name or "(no span)"
        self.by_span[where] = self.by_span.get(where, 0.0) + seconds
        self.slowest.append((seconds, fun_name, kind, turn_number, span_name))
        if len(self.slowest) > SLOWEST_KEPT:
            self.slowest.remove(min(self.slowest, key=lambda e: e[0]))
        entry = self._fun(fun_name)
        if entry is not None:
            entry["programs"] += 1
            entry[kind] += 1
            entry["stored"] += stored
            entry["load_s" if kind == "loaded" else "compile_s"] += seconds
            if entry["span"] is None:
                entry["span"] = span_name

    @property
    def trace_lower_s(self) -> float:
        return self._folded_s + sum(b - a for a, b in self._intervals)

    def as_dict(self) -> dict:
        """`stats<i>.json` "compile" (and, with "by_fun" and the longer
        "slowest" rows, the process record's "compile")."""
        ledger = self.by_fun is not None
        out = {
            "programs": self.compiled + self.loaded,
            "compiled": self.compiled,
            "loaded": self.loaded,
            "stored": self.stored,
            "trace_lower_s": self.trace_lower_s,
            "compile_s": self.compile_s,
            "load_s": self.load_s,
            "stored_threshold_s": store_threshold_s(),
            "compiled_under_threshold": self.compiled_under_threshold,
            "by_span": dict(self.by_span),
            "slowest": [
                [fun, kind, sec] + ([turn_number, where] if ledger else [])
                for sec, fun, kind, turn_number, where
                in sorted(self.slowest, key=lambda e: -e[0])],
        }
        if ledger:
            out["by_fun"] = {k: dict(v) for k, v in self.by_fun.items()}
        return out


class ProcessRecord:
    """What `process_record()` is made from. One a process (`_PROCESS`)."""

    def __init__(self):
        from .. import IMPORTED_AT

        # time.perf_counter(), the clock of Span; the first call of a name
        # stays: "imported", "main", "backend_ready", "turn1_start",
        # "turn1_end"
        self.marks: dict[str, float] = {"imported": IMPORTED_AT}
        self.turns = 0
        self.device_reads = 0               # `device_read` calls
        self.spans: dict[str, dict] = {}    # those opened outside a turn
        # the compile ledger: open up to the end of the first turn
        self.setup = CompileTotals(by_fun=True)


_PROCESS = ProcessRecord()
# what the cache did since the thread's last backend event
_PENDING = threading.local()
_LISTENING = False


def mark(name: str, at: float | None = None) -> None:
    """Note a moment of the process, once: a later call of the same name
    changes nothing."""
    _PROCESS.marks.setdefault(name, time.perf_counter() if at is None else at)


def marked(name: str) -> bool:
    return name in _PROCESS.marks


def store_threshold_s() -> float:
    import jax

    return float(jax.config.jax_persistent_cache_min_compile_time_secs)


def _between(marks: dict, a: str, b: str) -> float | None:
    return marks[b] - marks[a] if a in marks and b in marks else None


def process_summary() -> dict:
    """`stats1.json` "process": seconds from the package's import to
    cli.main and to the first turn, and the device backend's start between
    them (span `setup/backend`); None where the process has no such mark."""
    marks = _PROCESS.marks
    backend = _PROCESS.spans.get("setup/backend")
    return {
        "import_to_main_s": _between(marks, "imported", "main"),
        "backend_s": backend["total_s"] if backend else None,
        "import_to_first_turn_s": _between(marks, "imported", "turn1_start"),
    }


def process_record() -> dict:
    """The process so far, strict-JSON safe: marks on time.perf_counter(),
    turns started, the spans opened outside a turn, `process_summary()`, and
    the compile ledger ("setup": up to the end of the first turn)."""
    return {
        "marks": dict(_PROCESS.marks),
        "turns": _PROCESS.turns,
        "spans": {k: dict(v) for k, v in _PROCESS.spans.items()},
        "process": process_summary(),
        "compile": {"setup": _PROCESS.setup.as_dict()},
    }


def _stretches() -> list[CompileTotals]:
    """Where an event of now is added: the ledger while it is open, and the
    turn."""
    turn_spans = _TURN.get()
    return (([] if "turn1_end" in _PROCESS.marks else [_PROCESS.setup])
            + ([] if turn_spans is None else [turn_spans.compile]))


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _PENDING.hit = True
    elif event == _CACHE_STORE_EVENT:
        _PENDING.stored = True


def _on_time_span(event: str, start: float, end: float, fun_name: str = "",
                  **_) -> None:
    if event in _TRACE_EVENTS:
        # the trace is of "f", its lowering and compile of "jit(f)"
        key = fun_name if "(" in fun_name else f"jit({fun_name})"
        for totals in _stretches():
            totals.add_trace(key, start, end)
        return
    if event != _BACKEND_EVENT:
        return
    # one program: the persistent cache answered, or XLA compiled
    kind = "loaded" if getattr(_PENDING, "hit", False) else "compiled"
    stored = getattr(_PENDING, "stored", False)
    _PENDING.hit = _PENDING.stored = False
    seconds = end - start
    turn_spans = _TURN.get()
    if turn_spans is None:
        number, where = 0, _OUTSIDE.get()
    else:
        number = turn_spans.number
        opened = turn_spans._open
        where = turn_spans.spans[opened[-1]].name if opened else None
    threshold_s = store_threshold_s()
    for totals in _stretches():
        totals.add_program(fun_name, kind, seconds, stored, threshold_s,
                           number, where)
    for counter in _RETRACE_COUNTERS:
        counter.count += 1
        counter.events.append(fun_name)
    # on the device trace's clock while a profiler session runs: a
    # recompilation stands beside the idle gap it made
    counters("compile", fun_name=fun_name, kind=kind, seconds=seconds)


def register_compile_listeners() -> None:
    """Listen to jax.monitoring for the compile ledger, once a process:
    `enable_compile_cache()` calls this, and every entry point calls that
    before its first compile."""
    global _LISTENING
    if _LISTENING:
        return
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_time_span_listener(_on_time_span)
    _LISTENING = True


# ------------------------------------------------------- trace export


def chrome_trace(curves: dict, heartbeat_ms: float, t0_ms: float = 0.0,
                 pid: int = 0, name: str = "trial") -> dict:
    """Render flight-recorder curves as Chrome-trace JSON (perfetto loads
    it directly). One "X" (complete) slice per heartbeat carries every
    channel value in args; scalar channels additionally get "C" counter
    tracks so perfetto draws them as time series. `ts`/`dur` are
    microseconds per the trace-event spec; sim time is milliseconds."""
    curves = {k: np.asarray(v) for k, v in curves.items()}
    steps = min((c.shape[0] for c in curves.values()), default=0)
    events: list[dict] = [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": name}},
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": "heartbeats"}},
    ]
    for i in range(steps):
        ts = (t0_ms + i * heartbeat_ms) * 1000.0
        args = {}
        for k, c in curves.items():
            v = c[i]
            args[k] = (float(v) if np.ndim(v) == 0
                       else [float(x) for x in np.ravel(v)])
        events.append({
            "name": "heartbeat", "ph": "X", "ts": ts,
            "dur": heartbeat_ms * 1000.0, "pid": pid, "tid": 0,
            "args": {"hb": i, **args},
        })
        for k, c in curves.items():
            if np.ndim(c[i]) == 0:
                events.append({
                    "name": k, "ph": "C", "ts": ts, "pid": pid,
                    "args": {"value": float(c[i])},
                })
    return {"traceEvents": events, "displayTimeUnit": "ms"}
