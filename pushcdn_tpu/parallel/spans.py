"""Host spans on the profiler's clock: ``span(name, **stats)``.

The span *is* ``jax.profiler.TraceAnnotation``: a profiler session (the
benchmark's launchers run one, ``jax.profiler.start_trace``) collects it
on the device trace's own clock, one line per thread, the name bare and
the stats as the event's stats; with no session running it records
nothing and costs one small object and two calls into the profiler's
"is a session active" check. There is no ring, exporter, flag or timer
here. Until :func:`pushcdn_tpu.parallel.runtime.init` has run, ``span``
hands out one shared do-nothing context manager, so a host-only broker
imports no JAX because of spans.

Two rules (``tests/test_plane_spans.py`` holds both on a real trace):

- **Flat.** No span encloses another of the program's spans on the same
  thread, and no span contains an ``await``. A span's duration is then
  its self time, a span on the event-loop thread never swallows another
  task's time, and a reduction that names an idle gap after the host
  event covering most of it finds the phase, not a wrapper.
- **Per batch and per step, never per frame.** Stats are plain ints
  already at hand; one that is known only at the end of the span goes in
  through ``set_metadata`` on what ``with`` returns.

The spans (the names are what ``benchmark/span_reduce.py`` reads):

====================  ==========  ===========================================
``ingress.scan``      event loop  ``user_receive_loop``'s scan of one receive
                                  batch; ``frames``
``ingress.stage``     event loop  its one ``stage_batch`` call; ``frames``,
                                  ``staged``
``links.scan``        event loop  ``broker_receive_loop``'s scan of one receive
                                  batch from a peer broker; ``frames``
``links.stage``       event loop  its one ``stage_batch`` call; ``frames``,
                                  ``staged``
``links.forward``     event loop  ``user_receive_loop``'s pass over a staged
                                  batch (the peers' copies of its broadcasts,
                                  the host route of what the device left),
                                  only on a broker with a peer link;
                                  ``frames``, ``forwards`` (the (frame, peer)
                                  sends it appended)
``plane.take``        event loop  the pump's snapshot of rings and mirrors;
                                  ``step``, ``frames``, ``ring_wait_us``,
                                  ``users`` (the step's user dimension;
                                  single-shard plane)
``plane.h2d``         worker      state and lane batches to the device; ``step``;
                                  the mesh group's also ``puts`` and ``bytes``
                                  (its ``device_put`` calls and the host bytes
                                  handed to them: one put a settled tick)
``plane.dispatch``    worker      the jitted step's call; ``step``
``plane.d2h``         worker      each read-back of a decision; ``step``
``plane.encode``      worker      decisions to egress streams; ``step``
``plane.egress``      event loop  the pump's hand-off of the users' streams:
                                  written there on an idle link, else queued
                                  for the writer; ``step``, ``deliveries``,
                                  ``inline``, ``queued``, ``batched`` (of
                                  ``inline``, sent by one native call: a
                                  ``DevicePlane`` step that was back-pressured)
====================  ==========  ===========================================

``step`` is the plane's own step number; the two thread hops of a step
are the gaps between ``plane.take`` and the first ``plane.h2d``, and
between the last worker span and ``plane.egress``.
"""

from __future__ import annotations


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_NO_SPAN = _NoSpan()


def none(name: str, **stats) -> _NoSpan:
    """The span that records nothing (a compile-only warm-up step)."""
    return _NO_SPAN


_open = none


def span(name: str, **stats):
    """A context manager around one phase of a batch or a step."""
    return _open(name, **stats)


def bind() -> None:
    """Point :func:`span` at the profiler (``runtime.init`` calls this)."""
    global _open
    from jax.profiler import TraceAnnotation
    _open = TraceAnnotation
