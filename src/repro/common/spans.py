"""In-memory spans and counters of the federated round.

A span is ``(id, parent id, name, start_ns, end_ns, round, attrs)`` on
``time.time_ns()``: the wall clock a profiler trace's
``profile_start_time`` is read on, so spans line up with the device
trace by a subtraction.  Nothing goes through the profiler's host
tracer, which a trace of the chip runs with host tracing off.

The recorder is off by default.  Off, ``span`` costs one flag check and
records nothing; so do ``count``, ``dispatch`` and ``fetch``.  The round
span is the exception: it reads the clock at both ends either way,
because its duration is the round's ``RoundRecord.seconds``.  ``enable``
turns recording on, ``drain`` hands out everything recorded since and
clears it, ``disable`` turns it off again.

The round loop records (the engines, ``core/server.py``,
``core/cohort.py``, ``core/async_agg.py``; the feed,
``core/session.py``)::

    fl.round          one per round or flush; ``round`` = its index
      fl.chunk        the cohort engine: one per chunk (attr ``chunk``)
      fl.feed         batches for a round or a chunk (a sync round's:
                      attr ``staged``, true when the round before built
                      and copied them, and its ``h2d`` child only
                      reshapes them on the device)
        fl.feed.build   built on the host by the loader
        fl.feed.h2d     copied to the device
      fl.feed.ahead   a sync round: the next round's batches, built and
                      copied while this one computes (attr ``round``);
                      children ``fl.feed.build`` and ``fl.feed.h2d``
      fl.dispatch     a call of a compiled round program (attr
                      ``program``: the CompileGuard's name)
      fl.sync         a device-to-host read (attr ``what``); each also
                      counts one ``host_syncs``
      fl.hooks        the start or end hooks (attr ``phase``), with the
                      selection-state update at the end

A span's round is that of the innermost open ``fl.round``; a counter
counts under the same round.  The recorder serves one thread: the round
loop.

This module imports nothing from ``repro``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["Span", "Records", "enable", "disable", "drain", "span",
           "count", "round_span", "dispatch", "fetch"]


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    parent: Optional[int]
    name: str
    start_ns: int
    end_ns: int
    round: Optional[int]
    attrs: Dict[str, Any]


@dataclasses.dataclass
class Records:
    """What ``drain`` hands out: the closed spans in the order they
    closed, the counters by ``(round, name)``, and the compiled
    programs dispatched while recording, by name (each a CompileGuard,
    whose ``stage_maps()`` gives the stage of every HLO instruction)."""
    spans: List[Span]
    counters: Dict[Tuple[Optional[int], str], int]
    programs: Dict[str, Any]


class _Recorder:
    def __init__(self):
        self.on = False
        self.next_id = 0
        self.stack: List[Tuple[int, Optional[int]]] = []   # (id, round)
        self.spans: List[Span] = []
        self.counters: Dict[Tuple[Optional[int], str], int] = {}
        self.programs: Dict[str, Any] = {}

    def round(self) -> Optional[int]:
        return self.stack[-1][1] if self.stack else None

    def open(self, rnd: Optional[int]) -> Tuple[int, Optional[int],
                                                Optional[int]]:
        """A new span's id, its parent and its round."""
        sid = self.next_id
        self.next_id += 1
        parent = self.stack[-1][0] if self.stack else None
        rnd = self.round() if rnd is None else rnd
        self.stack.append((sid, rnd))
        return sid, parent, rnd

    def close(self, sid: int, parent: Optional[int], name: str,
              start: int, end: int, rnd: Optional[int],
              attrs: Dict[str, Any]) -> None:
        # pop the span and anything left open above it (a span whose
        # owner raised before closing it)
        for i in range(len(self.stack) - 1, -1, -1):
            if self.stack[i][0] == sid:
                del self.stack[i:]
                break
        if self.on:
            self.spans.append(Span(sid, parent, name, start, end, rnd,
                                   attrs))


_REC = _Recorder()


def enable() -> None:
    """Record spans and counters from now on."""
    _REC.on = True


def disable() -> None:
    """Stop recording; what was recorded stays until ``drain``."""
    _REC.on = False


def drain() -> Records:
    """Everything recorded since the last drain; clears it."""
    out = Records(_REC.spans, _REC.counters, _REC.programs)
    _REC.spans, _REC.counters, _REC.programs = [], {}, {}
    return out


class _Open:
    __slots__ = ("name", "attrs", "rnd", "sid", "parent", "start")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.sid, self.parent, self.rnd = _REC.open(None)
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc):
        _REC.close(self.sid, self.parent, self.name, self.start,
                   time.time_ns(), self.rnd, self.attrs)


_NULL = contextlib.nullcontext()


def span(name: str, **attrs):
    """A context manager that records ``name`` while the recorder is on
    (and does nothing otherwise)."""
    if not _REC.on:
        return _NULL
    return _Open(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` of the current round."""
    if _REC.on:
        key = (_REC.round(), name)
        _REC.counters[key] = _REC.counters.get(key, 0) + n


class round_span:
    """``fl.round`` of round ``rnd``: open from construction to
    ``close()`` (or the end of a ``with`` block), which returns the
    seconds between.  The clock is read whether or not the recorder is
    on; the span is recorded only if it was on when the round began."""

    def __init__(self, rnd: int):
        self.round = rnd
        self.sid = None
        self.seconds: Optional[float] = None
        if _REC.on:
            self.sid, self.parent, _ = _REC.open(rnd)
        self.start = time.time_ns()

    def elapsed(self) -> float:
        """Seconds since the round began (the round still open)."""
        return (time.time_ns() - self.start) * 1e-9

    def close(self) -> float:
        if self.seconds is None:
            end = time.time_ns()
            self.seconds = (end - self.start) * 1e-9
            if self.sid is not None:
                _REC.close(self.sid, self.parent, "fl.round", self.start,
                           end, self.round, {})
        return self.seconds

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def dispatch(program, *args, **kwargs):
    """Call the compiled round program ``program`` (a CompileGuard)
    under ``fl.dispatch``."""
    if not _REC.on:
        return program(*args, **kwargs)
    _REC.programs.setdefault(program.name, program)
    with _Open("fl.dispatch", {"program": program.name}):
        return program(*args, **kwargs)


_HOST = (np.ndarray, np.generic, int, float)


def fetch(x, what: str) -> np.ndarray:
    """``np.asarray(x)``: the round loop's device-to-host read of ``x``,
    under ``fl.sync`` and counted in ``host_syncs``.  A value already on
    the host costs no read and records nothing."""
    if not _REC.on or isinstance(x, _HOST):
        return np.asarray(x)
    count("host_syncs")
    with _Open("fl.sync", {"what": what}):
        return np.asarray(x)
