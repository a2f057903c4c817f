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
  count_retraces    a context manager counting jit cache misses (the
                    "Finished tracing + compiling" log events that
                    jax_log_compiles exposes) — the PR 1/PR 3 carry bugs
                    were exactly silent per-iteration retraces
  measure_retraces  calls a contract's representative spec twice with
                    same-aval inputs and returns the SECOND call's
                    retrace count; EntrypointContract.retrace_budget
                    (default 0) turns any excess into a tier-1 failure
                    (tests/test_profiling.py)
  roofline          the strict-JSON per-entrypoint block: {flops,
                    hbm_bytes, peak_memory_bytes, retraces,
                    retrace_budget}
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
"""

from __future__ import annotations

import contextvars
import logging
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

# the pjit cache-miss log lines. jax 0.4.3x logs "Compiling <fn> with
# global shapes and types" (jax._src.interpreters.pxla) once per in-memory
# cache miss; earlier releases logged "Finished tracing + compiling"
# (jax._src.dispatch). A version emits exactly one of the two per miss, so
# matching either counts each miss once. Counting log events instead of
# private cache sizes keeps the counter working through jit-internals
# refactors. (NOT "Finished tracing + transforming": that fires once per
# sub-transform and would overcount a single compile.)
_COMPILE_MARKERS = ("Finished tracing + compiling",
                    "with global shapes and types")


class RetraceCounter:
    """Mutable counter handed out by count_retraces()."""

    def __init__(self):
        self.count = 0
        self.events: list[str] = []


class _CountingHandler(logging.Handler):
    def __init__(self, counter: RetraceCounter):
        super().__init__(level=logging.DEBUG)
        self._counter = counter

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        if any(m in msg for m in _COMPILE_MARKERS):
            self._counter.count += 1
            self._counter.events.append(msg[:200])


@contextmanager
def count_retraces():
    """Count jit cache misses (trace+compile events) inside the block.

    Flips jax_log_compiles on for the duration so the events are emitted at
    WARNING, attaches a counting handler to the "jax" logger (every
    jax._src.* module logger propagates into it), and restores both on
    exit. Persistent-compile-cache hits still count — they are in-memory
    cache MISSES (a full retrace happened; only the XLA backend compile was
    skipped), which is exactly what a retrace budget is about."""
    import jax

    counter = RetraceCounter()
    handler = _CountingHandler(counter)
    jlog = logging.getLogger("jax")
    prev = bool(getattr(jax.config, "jax_log_compiles", False))
    jax.config.update("jax_log_compiles", True)
    jlog.addHandler(handler)
    try:
        yield counter
    finally:
        jlog.removeHandler(handler)
        jax.config.update("jax_log_compiles", prev)


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


def roofline(contracts=None, with_retraces: bool = True,
             name_prefix: str | None = None) -> dict:
    """The per-entrypoint roofline block: contract name -> {flops,
    hbm_bytes, peak_memory_bytes, retraces, retrace_budget} (strict-JSON
    safe; a contract that cannot lower on this backend reports an `error`
    string instead of crashing the caller).

    `name_prefix` restricts the sweep to contracts whose name starts with
    it (e.g. "disseminate/" for the publish-entrypoint CI artifact — the
    full registry costs minutes of compiles, the publish family seconds).
    Also honored via the BENCH_ROOFLINE_ONLY env var when the caller does
    not pass one."""
    if name_prefix is None:
        name_prefix = os.environ.get("BENCH_ROOFLINE_ONLY") or None
    if contracts is None:
        from ..analysis.registry import default_contracts

        contracts = default_contracts()
    if name_prefix:
        contracts = [c for c in contracts if c.name.startswith(name_prefix)]
    block: dict = {}
    for c in contracts:
        entry: dict = {}
        try:
            entry.update(entrypoint_cost(c))
        except Exception as e:  # noqa: BLE001 — per-entry degradation
            entry["error"] = repr(e)[:200]
        if with_retraces and "error" not in entry:
            try:
                entry["retraces"] = measure_retraces(c)
                entry["retrace_budget"] = int(c.retrace_budget)
            except Exception as e:  # noqa: BLE001
                entry["error"] = repr(e)[:200]
        block[c.name] = entry
    return block


def check_retrace_budgets(contracts=None) -> list[dict]:
    """[{name, retraces, budget}] for every contract whose second call
    retraces above its declared budget (empty = all clean). The tier-1
    gate (tests/test_profiling.py) asserts this is empty."""
    if contracts is None:
        from ..analysis.registry import default_contracts

        contracts = default_contracts()
    bad = []
    for c in contracts:
        got = measure_retraces(c)
        if got > c.retrace_budget:
            bad.append({"name": c.name, "retraces": got,
                        "budget": int(c.retrace_budget)})
    return bad


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
    """The spans of one `run` turn, in order of opening. One turn owns one
    of these and drops it when the turn ends, so a process that loops over
    experiments keeps nothing."""

    def __init__(self, **attrs):
        self.attrs = attrs    # the turn's identifier, shared by its spans
        self.spans: list[Span] = []
        self._open: list[int] = []

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


@contextmanager
def span(name: str, **attrs):
    """One host span of the program. Always a `jax.profiler.TraceAnnotation`
    named "sim:<name>" (a no-op costing well under a microsecond while no
    profiler session runs; on the device trace's clock while one does: the
    session is the switch), carrying the turn's identifier and `attrs`.
    Inside a `turn()` it is also noted in the turn's recorder; outside one
    nothing is recorded."""
    from jax.profiler import TraceAnnotation

    turn_spans = _TURN.get()
    if turn_spans is None:
        with TraceAnnotation(SPAN_PREFIX + name, **attrs):
            yield
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
    root span "run". Yields the recorder."""
    turn_spans = TurnSpans(**attrs)
    token = _TURN.set(turn_spans)
    try:
        with span("run"):
            yield turn_spans
    finally:
        _TURN.reset(token)


def counters(name: str, **values) -> None:
    """A zero-length "sim:<name>" annotation whose attributes are counters,
    for a reader of the profile; nothing is recorded on the host."""
    from jax.profiler import TraceAnnotation

    turn_spans = _TURN.get()
    ident = turn_spans.attrs if turn_spans is not None else {}
    with TraceAnnotation(SPAN_PREFIX + name, **ident, **values):
        pass


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
