"""Host spans around the program's own callables, set from here.

The program has no spans of its own yet (PERF.md section 3). In the traced
run only, the benchmark replaces each callable that one of the cell's
per-layer metric files names under `params.spans`, as
"<module>:<Class>.<attribute>", by a wrapper that times it on the host
clock and also opens a `jax.profiler.TraceAnnotation`, so that the same span
lies on the device trace's clock (benchmark/harness/trace.py names idle gaps
by them). The untraced runs call the program untouched.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import time
from dataclasses import dataclass

ANNOTATION_PREFIX = "bench:"


@dataclass
class Span:
    name: str
    start: float
    end: float
    parent: int | None      # index of the enclosing span, if any


class Recorder:
    """Spans kept in memory, in order of opening."""

    def __init__(self):
        self.spans: list[Span] = []
        self._open: list[int] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        index = len(self.spans)
        self.spans.append(Span(name, 0.0, 0.0,
                               self._open[-1] if self._open else None))
        self._open.append(index)
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            self.spans[index].start = time.perf_counter()
            try:
                yield
            finally:
                self.spans[index].end = time.perf_counter()
                self._open.pop()

    def seconds(self, names, within: tuple[float, float]) -> float:
        """Summed duration of the spans named, inside the interval, leaving
        out any nested in another of them (warmup calls advance)."""
        total = 0.0
        for s in self.spans:
            if s.name not in names or not (within[0] <= s.start
                                           and s.end <= within[1]):
                continue
            up = s.parent
            while up is not None and self.spans[up].name not in names:
                up = self.spans[up].parent
            if up is None:
                total += s.end - s.start
        return total


def span_name(spec: str) -> str:
    """The span of "<module>:<Class>.<attribute>" is "<Class>.<attribute>"."""
    return spec.partition(":")[2]


@contextlib.contextmanager
def installed(recorder: Recorder, specs):
    """Wrap every callable named in `specs` for the length of the block."""
    saved = []
    for spec in specs:
        module, _, name = spec.partition(":")
        cls_name, _, attr = name.partition(".")
        cls = getattr(importlib.import_module(module), cls_name)
        raw = cls.__dict__[attr]
        saved.append((cls, attr, raw))
        inner = raw.__func__ if isinstance(raw, classmethod) else raw

        def wrapper(*a, _inner=inner, _name=name, **kw):
            with recorder.span(_name):
                return _inner(*a, **kw)

        functools.update_wrapper(wrapper, inner)
        setattr(cls, attr,
                classmethod(wrapper) if isinstance(raw, classmethod)
                else wrapper)
    try:
        yield recorder
    finally:
        for cls, attr, raw in saved:
            setattr(cls, attr, raw)
