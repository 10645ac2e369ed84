"""Spans, counters and device-completion stamps, kept in process.

The program marks its layer boundaries with `span` (names
``repro.<layer>.<stage>``), attaches counts to the span where the work
happens with `count`, and hands the outputs of a device dispatch to
`stamp_when_ready`, which records when the device finished them without
making the dispatching thread wait. Readers take `records()`.

    with obs.span("repro.sweep.dispatch", chunk=c) as sp:
        out = program(...)
        obs.stamp_when_ready("repro.sweep.device", out)
    ...                                  # later, once the outputs are on
    sp.count("lane_events", n)           # the host

Every span also opens a `jax.profiler.TraceAnnotation` of the same name,
so that a profiler trace shows it on its host plane. The recorder is
always on: a span costs a few microseconds of host time, and nothing runs
on the device for it. Records go to a ring buffer of `MAX_RECORDS`, so a
process that runs for days holds a fixed amount.

**Clock.** Times are `time.time_ns()`: the wall clock (CLOCK_REALTIME),
which is what the profiler's host events are stamped with on JAX 0.9
(TSL's ``GetCurrentTimeNanos``). `ProfileData` gives an event's
``start_ns`` relative to the ``profile_start_time`` stat of its ``Task
Environment`` plane, so ``profile_start_time + start_ns`` is on this
clock (`tests/test_obs.py` holds the two within a millisecond on the CPU).

**Stamps.** `stamp_when_ready` queues each addressable shard of the arrays
to a daemon thread for that shard's device (one per device, so that a
chip that finishes early is not read late behind a slower one). The
thread blocks until the shard is ready and records a child of the span
that was open at the call: it runs from the later of the call and the
end of the previous stamp on that device, to the moment the wait
returned. Dispatches queued back to back on one device therefore tile its
busy time without overlapping. The moment is read once the thread holds
the interpreter lock again, so a stamp can end late by as long as another
thread keeps that lock.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from typing import NamedTuple

import jax

#: records the ring buffer keeps (the oldest are dropped first)
MAX_RECORDS = 8192


class Record(NamedTuple):
    """One closed span or device stamp."""
    id: int
    parent: int | None      # the span open around it on its thread
    name: str
    t0: int                 # ns, `time.time_ns()`
    t1: int
    attrs: dict
    counts: dict            # counter name -> summed value


class Span:
    """A span: ``with`` opens it under the innermost open span of its
    thread and records it when it closes; `count` adds to its counters."""

    __slots__ = ("id", "parent", "name", "t0", "t1", "attrs", "counts",
                 "_rec", "_ann")

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.id, self.parent, self.name = next(rec._ids), None, name
        self.attrs, self.counts = attrs, {}
        self.t0 = self.t1 = None
        self._rec, self._ann = rec, jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "Span":
        outer = self._rec._innermost()
        self.parent = None if outer is None else outer.id
        self._rec._stack().append(self)
        self._ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.time_ns()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._buf.append(Record(self.id, self.parent, self.name,
                                     self.t0, self.t1, self.attrs,
                                     self.counts))
        return False

    def count(self, name: str, value) -> None:
        """Add `value` to counter `name`; also after the span has closed,
        where the count is known only once its outputs reach the host."""
        self.counts[name] = self.counts.get(name, 0) + value

    @property
    def ms(self) -> float:
        """The closed span's duration in milliseconds."""
        return (self.t1 - self.t0) * 1e-6


class Recorder:
    """Spans of every thread, stamps of every device, in one ring buffer."""

    def __init__(self, max_records: int = MAX_RECORDS):
        self._buf = collections.deque(maxlen=max_records)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._queues = {}           # device id -> queue of pending stamps
        self._pending = 0
        self._settled = threading.Condition(self._lock)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _innermost(self) -> Span | None:
        stack = self._stack()
        return stack[-1] if stack else None

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def count(self, name: str, value) -> None:
        """Add to a counter of the innermost open span (none open: no-op)."""
        sp = self._innermost()
        if sp is not None:
            sp.count(name, value)

    def stamp_when_ready(self, name: str, arrays, **attrs) -> None:
        """Record, per device, when the device finished `arrays` (a
        pytree of `jax.Array`), without waiting here."""
        since = time.time_ns()
        sp = self._innermost()
        parent = None if sp is None else sp.id
        by_device = {}
        for leaf in jax.tree.leaves(arrays):
            for shard in leaf.addressable_shards:
                by_device.setdefault(shard.device, []).append(shard.data)
        for device, shards in by_device.items():
            with self._lock:
                self._pending += 1
                q = self._queues.get(device.id)
                if q is None:
                    q = self._queues[device.id] = queue.SimpleQueue()
                    threading.Thread(target=self._watch, args=(q,),
                                     name=f"repro.obs.device{device.id}",
                                     daemon=True).start()
            q.put((name, parent, since, shards,
                   dict(attrs, device=device.id)))

    def _watch(self, q) -> None:
        last_end = 0
        while True:
            name, parent, since, shards, attrs = q.get()
            try:
                for s in shards:
                    s.block_until_ready()
            except Exception as e:  # the computation failed: say so, go on
                attrs["error"] = repr(e)
            t1 = time.time_ns()
            t0 = max(since, last_end)
            last_end = t1
            self._buf.append(Record(next(self._ids), parent, name, t0, t1,
                                    attrs, {}))
            with self._lock:
                self._pending -= 1
                self._settled.notify_all()

    def records(self, timeout: float = 60.0) -> list[Record]:
        """Every record in the buffer, oldest first, once the stamps queued
        so far are recorded (or `timeout` seconds have passed)."""
        with self._lock:
            self._settled.wait_for(lambda: self._pending == 0, timeout)
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()


#: the process's recorder, which the module-level functions use
RECORDER = Recorder()


def span(name: str, **attrs):
    """``with span(name, **attrs) as sp:`` — a span named
    ``repro.<layer>.<stage>``, child of the span open around it."""
    return RECORDER.span(name, **attrs)


def count(name: str, value) -> None:
    """Add `value` to counter `name` of the innermost open span."""
    RECORDER.count(name, value)


def stamp_when_ready(name: str, arrays, **attrs) -> None:
    """Stamp, per device, when the device has finished `arrays`."""
    RECORDER.stamp_when_ready(name, arrays, **attrs)


def records(timeout: float = 60.0) -> list[Record]:
    """The process recorder's records, oldest first."""
    return RECORDER.records(timeout)


def clear() -> None:
    RECORDER.clear()


def self_ns(rec: Record, recs) -> int:
    """A span's self time: its duration minus the part of it that its
    direct children cover."""
    kids = sorted((max(r.t0, rec.t0), min(r.t1, rec.t1)) for r in recs
                  if r.parent == rec.id)
    covered, end = 0, rec.t0
    for s, e in kids:
        s = max(s, end)
        if e > s:
            covered += e - s
            end = e
    return rec.t1 - rec.t0 - covered
