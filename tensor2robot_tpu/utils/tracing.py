"""Flight recorder of the learner's host path: spans and cumulative counters.

`span(name, ordinal=None, **counts)` is a context manager that records
name, start and end in epoch nanoseconds (`time.time_ns()`, the clock
`jax.profiler` dates its session by, so a span lies on the device trace's
axis once `profile_start_time` is subtracted), the thread, the enclosing
span of that thread, the ordinal of the batch it works on (inherited from
the enclosing span when not given) and the counts known at the boundary.
`add(**counts)` adds to the innermost open span of the calling thread and
does nothing outside one; `count(name, n)` adds to a cumulative counter.
`since(name, start_ns, ...)` records a span that another thread opened at
`start_ns` and the calling thread closes now: what starts on the train
thread and ends in a runtime's own threads (a transfer) is seen to its end.
A closed span also adds its duration and 1 to the counters `<name>.ns`
and `<name>.n`, so a rate over any interval is a difference of two
`counters()` reads and does not depend on what the ring still holds.

The recorder is a bounded ring that is always armed: the newest `CAPACITY`
closed spans are kept, older ones fall out, and there is no switch. One
span costs two clock reads and one append under a lock; put none around a
single record or image.

Standard library only: the data layer's worker processes import this.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

#: Closed spans kept: the last minutes of a learner at ten steps a second.
CAPACITY = 16384


class Span:
    """One interval of one thread; `counts` grow while it is open."""

    __slots__ = (
        "_recorder", "name", "ident", "parent", "thread", "ordinal",
        "start_ns", "end_ns", "counts", "label",
    )

    def __init__(self, recorder, name, ordinal, counts, label=None):
        self._recorder = recorder
        self.name = name
        self.ordinal = ordinal
        self.counts = counts
        self.label = label
        self.parent = None
        self.end_ns = None

    def __enter__(self) -> "Span":
        recorder = self._recorder
        stack = recorder._stack()
        if stack:
            enclosing = stack[-1]
            self.parent = enclosing.ident
            if self.ordinal is None:
                self.ordinal = enclosing.ordinal
        self.ident = next(recorder._idents)
        self.thread = threading.get_ident()
        stack.append(self)
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.time_ns()
        self._recorder._stack().pop()
        self._recorder._close(self)
        return False

    def add(self, **counts) -> None:
        for key, value in counts.items():
            self.counts[key] = self.counts.get(key, 0) + value

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "id": self.ident, "parent": self.parent,
            "thread": self.thread, "ordinal": self.ordinal,
            "start_ns": self.start_ns, "end_ns": self.end_ns,
            "counts": dict(self.counts), "label": self.label,
        }


class Recorder:
    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._ring: "collections.deque[Span]" = collections.deque(maxlen=capacity)
        self._counters: Dict[str, int] = collections.defaultdict(int)
        self._local = threading.local()
        self._idents = itertools.count(1)

    def _stack(self) -> List[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _close(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)
            self._counters[span.name + ".ns"] += span.end_ns - span.start_ns
            self._counters[span.name + ".n"] += 1

    def span(self, name: str, ordinal: Optional[int] = None, **counts) -> Span:
        return Span(self, name, ordinal, counts)

    def add(self, **counts) -> None:
        stack = self._stack()
        if stack:
            stack[-1].add(**counts)

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def _record(
        self, name, ordinal, counts, thread, start_ns, end_ns,
        label=None, parent=None,
    ) -> None:
        span = Span(self, name, ordinal, counts, label)
        span.ident = next(self._idents)
        span.parent = parent
        span.thread = thread
        span.start_ns, span.end_ns = start_ns, end_ns
        self._close(span)

    def adopt(self, span: Dict[str, Any]) -> None:
        """Records a span that closed in another process (a parse worker
        ships `Span.as_dict()` home); its id and parent stay behind."""
        self._record(
            span["name"], span["ordinal"], dict(span["counts"]),
            span["thread"], span["start_ns"], span["end_ns"],
            label=span.get("label"),
        )

    def since(
        self, name: str, start_ns: int, ordinal: Optional[int] = None,
        label: Optional[str] = None, **counts
    ) -> None:
        """Records a span from `start_ns` (a `time.time_ns()` stamp, taken
        by whichever thread began the work) to now, under the calling
        thread and no parent."""
        self._record(
            name, ordinal, counts, threading.get_ident(), start_ns,
            time.time_ns(), label=label,
        )

    def between(
        self, name: str, start_ns: int, end_ns: int,
        label: Optional[str] = None, **counts
    ) -> None:
        """Records a span whose two ends were stamped elsewhere on the epoch
        clock, under the calling thread, as a child of that thread's
        innermost open span (whose ordinal it takes)."""
        stack = self._stack()
        enclosing = stack[-1] if stack else None
        self._record(
            name, enclosing.ordinal if enclosing else None, counts,
            threading.get_ident(), start_ns, end_ns, label=label,
            parent=enclosing.ident if enclosing else None,
        )

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def snapshot(self, since_ns: int = 0) -> Dict[str, Any]:
        """{"spans": [dict, ...] oldest first, "counters": {name: int}};
        only spans that ended at or after `since_ns`."""
        with self._lock:
            spans = list(self._ring)
            counters = dict(self._counters)
        return {
            "spans": [s.as_dict() for s in spans if s.end_ns >= since_ns],
            "counters": counters,
        }


#: The process's recorder; the functions below are its methods.
RECORDER = Recorder()
span = RECORDER.span
add = RECORDER.add
count = RECORDER.count
adopt = RECORDER.adopt
since = RECORDER.since
between = RECORDER.between
counters = RECORDER.counters
snapshot = RECORDER.snapshot
