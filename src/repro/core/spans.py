"""Program spans: named, nested intervals of one run, on the profiler's clock.

``span(name, **counts)`` opens a ``jax.profiler.TraceAnnotation`` of that
name, so the interval lands in any running profiler trace beside the device
operations, and appends a :class:`SpanRecord` to the :class:`Recorder`
bound to the current thread. ``count(name, n)`` adds to the innermost open
span of the thread. With no recorder bound a span still times itself and
writes the annotation; it records nothing.

:func:`repro.core.dckcore.dc_kcore` binds a fresh recorder for each run
(:func:`recording`) and hands it to its worker threads (:func:`bound`), so
the prefetch worker's and the wave slices' spans land in the same record
under their own thread names. The records ride on ``DCKCoreReport.spans``;
:func:`stage_seconds` reduces them to total seconds, self seconds (duration
minus the time the span's children cover) and a count per span name.

A span opened with a ``compiles`` count receives the executables JAX builds
on its thread while it is the innermost such span: ``compiles`` and
``compile_ms``, from JAX's ``/jax/core/compile/backend_compile_duration``
event, which fires for a fresh compile and for a load from the persistent
compile cache alike.

Times are ``time.perf_counter_ns`` (monotonic). Spans are always on: an
annotation with no profiler running and a record each cost about a
microsecond.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence

from jax import monitoring
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class SpanRecord:
    name: str
    start_ns: int
    end_ns: int                 # 0 while the span is open
    parent: int                 # index of the enclosing span of the same
    #                             thread in Recorder.records; -1 at a root
    thread: str
    counts: Dict[str, float]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


@dataclasses.dataclass(frozen=True)
class StageTime:
    total_s: float   # summed durations of the spans of one name
    self_s: float    # the same, less the time their child spans cover
    count: int


class Recorder:
    """The spans of one run, from every thread bound to it, in open order."""

    def __init__(self):
        self.records: List[SpanRecord] = []
        self._lock = threading.Lock()
        _listen_for_compiles()

    def _add(self, record: SpanRecord) -> int:
        with self._lock:
            self.records.append(record)
            return len(self.records) - 1


# Per thread: the bound recorder and the stack of its open spans, as
# (index in recorder.records, record) pairs.
_local = threading.local()


def current() -> Optional[Recorder]:
    """The recorder bound to this thread, or None."""
    return getattr(_local, "recorder", None)


@contextlib.contextmanager
def bound(recorder: Optional[Recorder]) -> Iterator[Optional[Recorder]]:
    """Bind ``recorder`` to this thread for the block: a worker thread of
    the run that made it. Rebinding the recorder a thread already has keeps
    its open spans as the parents of the block's."""
    if recorder is current():
        yield recorder
        return
    prev = (current(), getattr(_local, "stack", None))
    _local.recorder, _local.stack = recorder, []
    try:
        yield recorder
    finally:
        _local.recorder, _local.stack = prev


def recording() -> contextlib.AbstractContextManager:
    """Bind a fresh :class:`Recorder` to this thread for the block."""
    return bound(Recorder())


@contextlib.contextmanager
def span(name: str, **counts: float) -> Iterator[SpanRecord]:
    """Time the block as ``name``; yields its record, whose ``seconds`` is
    set when the block ends. Also usable as a function decorator."""
    recorder = current()
    stack = _local.stack if recorder is not None else None
    record = SpanRecord(name, 0, 0, stack[-1][0] if stack else -1,
                        threading.current_thread().name, dict(counts))
    with TraceAnnotation(name):
        record.start_ns = time.perf_counter_ns()
        if stack is not None:
            stack.append((recorder._add(record), record))
        try:
            yield record
        finally:
            record.end_ns = time.perf_counter_ns()
            if stack is not None:
                stack.pop()


def count(name: str, n: float) -> None:
    """Add ``n`` to the count ``name`` of this thread's innermost open span
    (nothing without a recorder or an open span)."""
    stack = getattr(_local, "stack", None) if current() is not None else None
    if stack:
        counts = stack[-1][1].counts
        counts[name] = counts.get(name, 0) + n


def stage_seconds(records: Sequence[SpanRecord]) -> Dict[str, StageTime]:
    """Total seconds, self seconds and count of the closed spans, by name.

    A span's children are on its own thread and nest inside it, so the
    time they cover is the sum of their durations."""
    child_ns = [0] * len(records)
    for r in records:
        if r.parent >= 0 and r.end_ns:
            child_ns[r.parent] += r.end_ns - r.start_ns
    sums: Dict[str, List[int]] = {}
    for r, covered in zip(records, child_ns):
        if not r.end_ns:
            continue
        d = r.end_ns - r.start_ns
        acc = sums.setdefault(r.name, [0, 0, 0])
        acc[0] += d
        acc[1] += d - covered
        acc[2] += 1
    return {k: StageTime(t / 1e9, s / 1e9, n) for k, (t, s, n) in sums.items()}


_listener_lock = threading.Lock()
_listening = False


def _listen_for_compiles() -> None:
    """Register the compile listener with JAX, once per process."""
    global _listening
    with _listener_lock:
        if not _listening:
            monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True


def _on_event(event: str, duration: float, **_kw) -> None:
    if event != COMPILE_EVENT or current() is None:
        return
    for _i, record in reversed(getattr(_local, "stack", ())):
        if "compiles" in record.counts:
            record.counts["compiles"] += 1
            record.counts["compile_ms"] = (
                record.counts.get("compile_ms", 0.0) + 1e3 * duration)
            return
