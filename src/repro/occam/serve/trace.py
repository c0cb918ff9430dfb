"""Spans of the serving path, recorded from inside the program.

An :class:`~repro.occam.serve.AsyncEngine` owns one
:class:`SpanRecorder` (``engine.spans``), off by default. While it is
off every recording site tests one flag and does nothing else: no clock
is read and nothing is allocated. ``enable()`` starts a bounded
in-memory record, ``disable()`` stops it, and ``drain()`` hands the
caller what was recorded; there is no file writer or exporter.

The spans, at the engine's layer boundaries (``id`` is the request's
uid for the first three, the round's for the rest):

* ``occam.request``: ``submit`` entry -> the ticket resolved;
* ``occam.submit``: the body of ``AsyncEngine.submit``, the copy of the
  images to the device included;
* ``occam.queued``: admission -> the request's last lane taken into a
  round;
* ``occam.stage``: take, concatenate and ``device_put`` of one round;
* ``occam.dispatch``: the round's jitted step or ring tick, until it
  returns;
* ``occam.device``: dispatch return -> the round's outputs ready on the
  device;
* ``occam.deliver``: the engine's bookkeeping for a delivered round.

``parent`` names the span that caused one: a request's ``occam.submit``
and ``occam.queued`` have the request's uid (its ``occam.request``); a
round's dispatch, device and deliver spans have the round's uid (its
``occam.stage``); the two roots have -1. ``requests`` lists the
request uids an ``occam.stage`` round carries, and ``round`` on an
``occam.request`` is the round that completed the request's last lane.

Times are read with ``time.perf_counter_ns``; ``drain`` returns them as
nanoseconds since the epoch (``time.time_ns``, anchored once at
``enable``), the clock a profiler trace's ``profile_start_time`` is on.
"""
from __future__ import annotations

import time
from typing import NamedTuple

now = time.perf_counter_ns

# the most spans one record keeps; later ones are counted in ``dropped``
CAPACITY = 1 << 18


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int = -1
    images: int = 0
    round: int = -1
    requests: tuple[int, ...] = ()


class SpanRecorder:
    """A bounded in-memory record of spans, off until :meth:`enable`.

    Recording sites read ``on`` and, only while it is set, call
    :func:`now` and :meth:`record`. Spans past :data:`CAPACITY` are
    counted in ``dropped``, not kept."""

    def __init__(self):
        self.on = False
        self.dropped = 0
        self._spans: list[Span] = []
        self._capacity = 0
        self._anchor_ns = 0     # time.time_ns() - time.perf_counter_ns()

    def enable(self) -> None:
        """Start a new record of at most :data:`CAPACITY` spans (what an
        earlier one left undrained is discarded)."""
        self._spans = []
        self._capacity = CAPACITY
        self.dropped = 0
        self._anchor_ns = time.time_ns() - now()
        self.on = True

    def disable(self) -> None:
        """Stop recording; what was recorded stays until drained."""
        self.on = False

    def record(self, name: str, start_ns: int, end_ns: int, id: int,
               parent: int = -1, images: int = 0, round: int = -1,
               requests: tuple[int, ...] = ()) -> None:
        """One span, its times from :func:`now`."""
        if len(self._spans) < self._capacity:
            self._spans.append(Span(name, start_ns, end_ns, id, parent,
                                    images, round, requests))
        else:
            self.dropped += 1

    def drain(self) -> list[Span]:
        """The spans recorded so far, in the order they ended, with
        times in ``time.time_ns`` nanoseconds; the record is emptied."""
        spans, self._spans = self._spans, []
        a = self._anchor_ns
        return [s._replace(start_ns=s.start_ns + a, end_ns=s.end_ns + a)
                for s in spans]
