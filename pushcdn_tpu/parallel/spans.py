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
                                  single-shard plane), ``parked_us``,
                                  ``gate_us``, ``drain_us`` (the pump's time
                                  in those three states since the take
                                  before: with them a traced period closes,
                                  take + worker + egress + these three =
                                  take to take)
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
                                  step or tick that was back-pressured,
                                  or a step whose sends were the period),
                                  ``short`` (of ``batched``, settled one by
                                  one after a short send; the rest in one pass),
                                  ``tls`` (of ``inline`` + ``queued``, those
                                  over a stream that encrypts above its
                                  socket: a user on TCP+TLS),
                                  ``tls_batched`` (of ``tls``, sent sealed
                                  by one native call; not in ``batched``),
                                  ``oversize`` (the streams longer than one
                                  flush unit, which go to the writers)
====================  ==========  ===========================================

``step`` is the plane's own step number; the two thread hops of a step
are the gaps between ``plane.take`` and the first ``plane.h2d``, and
between the last worker span and ``plane.egress``.

**Counters.** A trace covers seconds; the window is read off cumulative
counters in the planes' ``describe()`` (``/debug/topology``'s
``device_plane``), each a sum of plain ints that only grows (events,
microseconds on ``time.monotonic_ns()``), never a gauge or a mean: the
difference between two readings is the interval's, the sum over brokers
the deployment's. Beside the older ones (``steps``, ``frames_staged``,
``messages_routed``, ``egress_*``, ``frames_drained``, ``link_frames_*``,
``h2d_*``), with where each is incremented and the per-layer metric of
``benchmark/layer_metrics/`` that reads it:

==========================  ==============================================
``pump_parked_us`` ...      ``pump_common.PumpAccount.enter``, called by
``pump_egress_us`` (six)    both pumps at every change of state (parked,
                            gate, drain, take, worker, egress): they
                            partition the pump task's wall time;
                            ``pump_parked_share``
``worker_busy_us``          ``PumpAccount.run``, on the worker thread,
                            around the step: ``pump_worker_us`` less this
                            is the two hops; ``step_hop_ms``,
                            ``sat_step_hop_ms``
``pump_paced_us``,          ``PumpAccount.paced``, called by
``pump_paced_steps``        ``DevicePlane._pace``: the waits of the takes
                            paced after a step that sent in the native
                            batch off saturation, a part of
                            ``pump_gate_us``, and how many; no reader
``egress_offsat_batched``   ``DevicePlane._pump``, once a step: of
                            ``egress_batched`` + ``egress_tls_batched``,
                            the hand-offs of a step whose take was not
                            back-pressured (0 in the group); no reader
``stage_full_results``      ``try_stage`` / ``stage_batch`` of both
                            planes, every ``FULL`` handed back (a retry's
                            too); no reader
``stage_full_frames``       ``stage_batch``, the frames it held back (each
                            then retries alone on a 2 ms poll);
                            ``ring_full_share``
``writer_dequeues``,        read off ``cdn_writer_queue_delay_seconds``
``writer_wait_us``,         (``Connection._account_entry`` observes every
``writer_wait_over_500ms``  dequeue's wait) when asked; ``writer_wait_ms``
``writer_writes``,          ``AsyncioStream.write`` / ``writev`` (the
``writer_write_us``,        transport's synchronous ``write``, never the
``writer_write_bytes``      drain, never ``write_nowait``);
                            ``writer_us_per_write``
``egress_tls``,             ``senders.try_send_encoded_to_user_nowait``,
``egress_tls_inline``,      the one-by-one hand-off of ``egress_streams``,
``egress_tls_write_us``,    and ``senders._egress_batched``, only on a
``egress_tls_batched``      link whose stream encrypts above its socket
                            (``RawStream.encrypts``: users on TCP+TLS):
                            such hand-offs, inline or queued; of those,
                            the ones the pump wrote itself; the clock
                            around what the loop did for those (one by
                            one: the link's checks, ``write_nowait``'s
                            ``bytes()`` copy, record layer and
                            ``send()``; in a back-pressured step's batch:
                            the checks and the seal, from
                            ``Connection.seal_idle``'s checks to the
                            outgoing BIO's read, the ``send()`` being
                            the native call's), which lies inside
                            ``pump_egress_us``; and of the inline ones
                            those the batch sent. A queued one's write is
                            ``writer_write_us``'s. 0 with plain users,
                            who cost one attribute read and no clock;
                            ``tls_write_us_per_handoff``,
                            ``tls_write_share``
``egress_oversize``,        ``senders.egress_streams``, once a step: the
``egress_oversize_bytes``   hand-offs whose stream is longer than one
                            flush unit (``Connection.
                            _BATCH_COALESCE_LIMIT``), and their bytes;
                            ``egress_oversize_per_step``
``egress_pool_takes``,      ``native._egress_take``, process-wide: every
``egress_pool_fresh``,      take of a step's egress buffer, those that
``egress_pool_fresh_bytes`` found none pooled that fits and allocated,
                            and the bytes allocated;
                            ``egress_pool_fresh_share``
``loop_lag_us``,            ``proto/metrics.py:_loop_lag_sampler``, a
``loop_lag_samples``        sample a 0.25 s; None where no sampler runs;
                            ``loop_lag_ms``
``profiler_ticks``,         ``proto/metrics.py:_task_profiler``, a tick a
``profiler_tick_us``,       0.25 s: what its walk of ``all_tasks()`` held
``profiler_tick_tasks``     the loop for, over how many tasks; None where
                            no profiler runs; read by PERF.md
==========================  ==============================================

The writers' write and the profiler's tick are counters and no spans
(``writer.write``, ``profiler.tick``): the benchmark's own test holds the
span names of a traced ``global-steady`` dry run to a lone broker's
eight (``ingress.*``, ``plane.*``: ``tests/benchmark/test_span_reduce.py``),
and both would appear there.
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
