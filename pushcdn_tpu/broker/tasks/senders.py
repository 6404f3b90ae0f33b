"""Send helpers. **Failure ⇒ removal**: a failed send is the fault
detector — the peer is removed and its tasks aborted (parity
cdn-broker/src/tasks/broker/sender.rs:17-58, tasks/user/sender.rs:16-32;
SURVEY.md §5 "failure *is* an I/O error").

All senders take refcounted :class:`Bytes` frames and clone per recipient —
fan-out shares one payload buffer (Arc-clone parity, handler.rs hot path).
"""

from __future__ import annotations

import logging
import time
from typing import TYPE_CHECKING, Iterable, List, Optional

import numpy as np

from pushcdn_tpu import native as native_mod
from pushcdn_tpu.proto import flowclass
from pushcdn_tpu.proto import ledger as ledger_mod
from pushcdn_tpu.proto import metrics as metrics_mod
from pushcdn_tpu.proto.limiter import Bytes
from pushcdn_tpu.proto.transport.base import Connection
from pushcdn_tpu.proto.util import mnemonic

if TYPE_CHECKING:
    from pushcdn_tpu.broker.broker import Broker

logger = logging.getLogger("pushcdn.broker")


def _pumped(connection) -> str:
    """Failure-log tag for peers the fused pump (transport/pump.py) had
    natively engaged: the removal an operator sees here is the Python
    rediscovery of an error the pump already counted
    (``cdn_pump_escalations{reason="peer_error"}``) — the tag makes the
    two log/metric trails correlate."""
    stream = getattr(connection, "_stream", None)
    if getattr(stream, "_pump_binding", None) is not None:
        return " [natively pumped peer]"
    return ""

# pre-encode shape bounds: the fast path covers fan-out batches of small
# frames (the hot regime); anything bigger rides the writer's own
# coalescer, which chunks large flushes per timeout window
_PRE_ENCODE_MAX_FRAME = 64 * 1024
_PRE_ENCODE_MAX_TOTAL = 1 << 20

# how try_send_encoded_to_user_nowait handed a stream over (both truthy)
INLINE = 1
QUEUED = 2


def pre_encode_frames(raws) -> Optional[bytearray]:
    """Length-delimit a batch of small ``bytes`` frames into ONE owned
    buffer via the native batch encoder (one C call, one copy — the same
    copy count as the writer-side coalescer, moved off the writer task so
    the flush is verbatim and the frames' pool permits release at encode
    time). None when the native library is unavailable or the batch
    doesn't fit the fast-path shape (callers fall back to
    ``send_raw_many``)."""
    encoder = native_mod.shared_encoder()
    if encoder is None or len(raws) < 2:
        return None
    total = 0
    payloads = []
    for r in raws:
        data = r.data if isinstance(r, Bytes) else r
        if type(data) is not bytes or len(data) > _PRE_ENCODE_MAX_FRAME:
            return None
        total += len(data) + 4
        if total > _PRE_ENCODE_MAX_TOTAL:
            return None
        payloads.append(data)
    t0 = time.perf_counter()
    out = encoder.encode_detached(payloads)
    # batch-level native-seam accounting: one perf_counter pair per
    # fan-out batch (cdn_native_seconds{kernel="egress_encode"})
    metrics_mod.NATIVE_EGRESS_SECONDS.inc(time.perf_counter() - t0)
    return out


async def try_send_to_user(broker: "Broker", public_key: bytes,
                           raw: Bytes, cls: int = 2) -> bool:
    """Queue ``raw`` (one clone) to a local user; remove the user on
    failure. The clone is released by the writer task after the frame hits
    the stream, or by us on failure. ``cls`` is the flow class counted at
    the writer (default ``live`` — this is a data-frame path)."""
    connection = broker.connections.get_user_connection(public_key)
    if connection is None:
        return False
    clone = raw.clone()
    try:
        await connection.send_raw(clone, cls=cls)
        return True
    except Exception as exc:
        clone.release()
        logger.info("send to user %s failed (%r)%s; removing",
                    mnemonic(public_key), exc, _pumped(connection))
        broker.connections.remove_user(public_key, reason="send failed")
        broker.update_metrics()
        return False


def try_send_frames_to_user_nowait(broker: "Broker", public_key: bytes,
                                   raws: Iterable[Bytes]) -> int:
    """Queue a whole batch of frames to one user as ONE send queue entry
    (single connection lookup, single writer wakeup — the device-plane
    egress delivers per-user groups). Returns the number queued; a failure
    removes the user."""
    connection = broker.connections.get_user_connection(public_key)
    if connection is None:
        return 0
    raws = list(raws)
    if not raws:
        return 0
    # Pre-encoded fast path: the whole batch becomes one verbatim writer
    # flush, and the borrowed frames need no clones at all (the encode
    # copies; the caller keeps ownership of the originals).
    encoded = pre_encode_frames(raws)
    try:
        if encoded is not None:
            # nframes carries the batch's frame count into the writer's
            # class accounting (an encoded stream is otherwise opaque)
            connection.send_encoded_nowait(encoded, nframes=len(raws))
        else:
            # the connection owns the clones from here (released on
            # failure too)
            connection.send_raw_many_nowait([raw.clone() for raw in raws])
        return len(raws)
    except Exception as exc:
        logger.info("nowait send to user %s failed (%r)%s; removing",
                    mnemonic(public_key), exc, _pumped(connection))
        broker.connections.remove_user(public_key, reason="send failed")
        broker.update_metrics()
        return 0


def try_send_encoded_to_user_nowait(plane, broker: "Broker",
                                    public_key: bytes, data, owner=None,
                                    nframes: int = 0) -> int:
    """Hand a pre-framed egress stream (native.egress_encode output) to
    one user — zero per-frame work here or in the writer. An idle link
    takes it there and then, from the caller's task
    (``Connection.try_send_encoded_inline``: no writer-task wake-up);
    any other link queues it for its writer, behind what is queued.
    Returns ``INLINE`` or ``QUEUED``, or 0 after a failure, which removes
    the user (failure-is-removal, as everywhere). ``owner`` keeps a
    pooled egress buffer alive until a queued flush completes.
    ``nframes`` feeds the class accounting (the stream itself is
    opaque).

    A link whose stream encrypts above its socket (a user on TCP+TLS:
    ``Connection.encrypts``) is tallied on ``plane`` besides:
    ``egress_tls`` a hand-off, ``egress_tls_inline`` one the caller
    wrote itself, and ``egress_tls_write_ns``, the clock around that
    inline call: the link's checks, ``write_nowait`` (the ``bytes()``
    copy of the pooled buffer, the record layer, the transport's
    ``send()``) and its accounting. A write that went to the writer
    task is timed there (``writer_write_us``), one that failed nowhere;
    a plain link reads the attribute and no clock."""
    connection = broker.connections.get_user_connection(public_key)
    if connection is None:
        return 0
    encrypts = connection.encrypts
    t0 = time.monotonic_ns() if encrypts else 0
    try:
        inline = connection.try_send_encoded_inline(data, nframes=nframes)
        if not inline:
            connection.send_encoded_nowait(data, owner, nframes=nframes)
    except Exception as exc:
        _send_failed(broker, public_key, connection, exc)
        return 0
    if encrypts:
        plane.egress_tls += 1
        if inline:  # nothing ran between the inline call and this clock
            plane.egress_tls_inline += 1
            plane.egress_tls_write_ns += time.monotonic_ns() - t0
    return INLINE if inline else QUEUED


def _send_failed(broker: "Broker", public_key: bytes, connection,
                 exc: Exception) -> None:
    logger.info("encoded send to user %s failed (%r)%s; removing",
                mnemonic(public_key), exc, _pumped(connection))
    broker.connections.remove_user(public_key, reason="send failed")
    broker.update_metrics()


def _egress_batched(plane, broker: "Broker", streams, sizes) -> list:
    """Send the streams of one batched step whose links are idle by
    native calls (``native.send_batch``: the sends fanned over a few
    threads, joined before it returns); the slots they did not take, for
    the caller's loop. A plain link gives its socket
    (``Connection.idle_fd``) and is sent straight from the step's pooled
    buffer; a link whose stream encrypts gives it with its stream sealed
    there and then by that link's own record layer
    (``Connection.seal_idle``: a plain link pays the one attribute read),
    and its records go in a second call. The event loop stands still
    from the first check to the last settling, as it does for that
    loop's ``send()``s, so nothing else can write to, close or reuse a
    socket, or seal on its link, in between; a user has one stream in
    ``streams``, so one send: nothing can reorder. ``sizes`` is
    ``streams.nbytes`` of its users, in their order.

    The tallies: a plain link's hand-off counts in ``egress_inline`` and
    ``egress_batched``; a sealed one in ``egress_inline``, ``egress_tls``,
    ``egress_tls_inline`` and ``egress_tls_batched``, and its seal (from
    the link's checks to the records) in ``egress_tls_write_ns``: what the
    loop still does for it, where the one-by-one path times the whole
    write (a seal whose send then failed is in that clock alone: the loop
    did it). ``egress_batched`` stays the plain links' count, as the
    benchmark's tests of a TLS deployment read it (``egress_batched == 0``
    there)."""
    slots = plane.slots
    user_connection = broker.connections.get_user_connection
    users = streams.users
    plain, sealed, rest, seal_ns = _Batch(), _Batch(), [], 0
    # the plain links' appends as locals: theirs is the step's long loop
    taken, keys, links, fds = plain.slots, plain.keys, plain.links, plain.fds
    for slot, size in zip(users, sizes.tolist()):
        key = slots.key_of(slot)
        connection = None if key is None else user_connection(key)
        if connection is None:
            rest.append(slot)
        elif connection.encrypts:
            t0 = time.monotonic_ns()
            try:
                got = connection.seal_idle(streams.stream(slot))
            except Exception as exc:
                _send_failed(broker, key, connection, exc)
                continue
            if got is None:
                rest.append(slot)
            else:
                seal_ns += time.monotonic_ns() - t0
                sealed.add(slot, key, connection, *got)
        else:
            fd = connection.idle_fd(size)
            if fd is None:
                rest.append(slot)
            else:
                taken.append(slot)
                keys.append(key)
                links.append(connection)
                fds.append(fd)
    if taken:
        n, short = _send_and_settle(plane, broker, streams, plain)
        plane.egress_batched += n
        plane.egress_batched_short += short
    if sealed.slots:
        n, _short = _send_and_settle(plane, broker, streams, sealed)
        plane.egress_tls += n
        plane.egress_tls_inline += n
        plane.egress_tls_batched += n
        plane.egress_tls_write_ns += seal_ns
    return rest


class _Batch:
    """One native call's entries, in batch order: a slot, its user's key,
    connection and fd, and, for sealed links, the records (plain links
    have none: they are sent from the step's buffer)."""

    def __init__(self):
        self.slots, self.keys, self.links, self.fds = [], [], [], []
        self.records = []

    def add(self, slot, key, link, fd, records):
        self.slots.append(slot)
        self.keys.append(key)
        self.links.append(link)
        self.fds.append(fd)
        self.records.append(records)


def _send_and_settle(plane, broker: "Broker", streams, batch) -> tuple:
    """Send ``batch`` by one native call (the step's buffer, or the
    sealed records) and settle it. The sends that took their whole
    stream (all of them, where the readers keep up) are settled in one
    pass, not once a link: ``Connection.sent_whole_on_fds`` credits the
    transport's byte count, the class counters and the ledger's transit
    once with the totals of the streams (a sealed link's plaintext, as
    its one-by-one write is credited). Every other entry goes through
    ``Connection.sent_on_fd`` as a send of its own, in batch order: a
    short one (``EAGAIN``: of no bytes), whose remainder its transport
    holds from then on, is settled like a whole one; any other errno
    removes that user only. ``messages_routed`` and ``egress_inline``
    grow by what was settled; the hand-offs settled, and the short ones
    of them."""
    at = np.array(batch.slots, np.int64)
    nbytes, nframes = streams.nbytes[at], streams.msgs[at]
    fds = np.array(batch.fds, np.int32)
    records = batch.records or None
    if records is None:
        wire = nbytes
        sent = native_mod.send_batch(streams.buf, fds, streams.offsets[at],
                                     nbytes)
    else:
        wire = np.fromiter(map(len, records), np.int64, len(records))
        sent = native_mod.send_batch_each(records, fds)
    whole = sent == wire
    links, short = batch.links, 0
    for i in np.flatnonzero(~whole).tolist():
        try:
            links[i].sent_on_fd(
                streams.stream(batch.slots[i]), int(sent[i]),
                nframes=int(nframes[i]),
                records=None if records is None else records[i])
        except Exception as exc:
            _send_failed(broker, batch.keys[i], links[i], exc)
            continue
        plane.messages_routed += int(nframes[i])
        short += 1
    if not whole.all():
        links = [links[i] for i in np.flatnonzero(whole).tolist()]
        nbytes, nframes = nbytes[whole], nframes[whole]
    if links:
        Connection.sent_whole_on_fds(links, nbytes, nframes)
        plane.messages_routed += int(nframes.sum())
    plane.egress_inline += len(links) + short
    return len(links) + short, short


def egress_streams(plane, broker: "Broker", streams,
                   batch: bool = False) -> None:
    """Deliver one step's native egress (:class:`native.EgressStreams`):
    one pre-framed stream hand-off per user with deliveries, tallied on
    ``plane`` (a ``DevicePlane`` or a broker group): ``messages_routed``,
    and how each hand-off went, ``egress_inline`` or ``egress_queued``
    (of both, ``egress_tls`` over a link that encrypts:
    :func:`try_send_encoded_to_user_nowait`, :func:`_egress_batched`).

    ``batch`` is the pump's decision that the idle links' sends leave
    together over several threads, a TLS link's sealed on the loop first
    (:func:`_egress_batched`, which also accounts for them: the whole
    sends of the batch in one pass, the plane's tallies by their sums);
    every other hand-off, and every one of a step not batched, goes one
    by one below, each accounted by the connection's own call. Both pumps
    batch a step whose take found the base lane full: its publishers
    wait on the step, so the next one carries as many frames and makes as
    many sends however short this one is, and the time of the sends is
    the rate. ``DevicePlane``'s also batches, off saturation, a step
    whose sends are the period (``pump_common.CpuPacer``), and paces the
    take after it by the CPU the step cost, where shorter sends would
    else buy a faster cadence of smaller steps with cores: the same
    sends at the same cadence, each user's stream out sooner.

    A stream longer than one flush unit (``Connection._BATCH_COALESCE_LIMIT``)
    is taken by no idle link, in the batch or one by one: it goes to the
    user's writer task, and later streams of that user queue behind it.
    Such hand-offs and their bytes are counted here, once a step and
    whichever way the step goes (``egress_oversize``,
    ``egress_oversize_bytes``)."""
    slots = plane.slots
    users = streams.users
    sizes = streams.nbytes[users]
    over = sizes > Connection._BATCH_COALESCE_LIMIT
    if over.any():
        plane.egress_oversize += int(np.count_nonzero(over))
        plane.egress_oversize_bytes += int(sizes[over].sum())
    if batch:
        users = _egress_batched(plane, broker, streams, sizes)
    for slot in users:
        key = slots.key_of(int(slot))
        if key is None:  # released mid-step: user is gone, drop
            continue
        nframes = int(streams.msgs[slot])
        how = try_send_encoded_to_user_nowait(
            plane, broker, key, streams.stream(slot), owner=streams,
            nframes=nframes)
        if how:
            plane.messages_routed += nframes
            if how == INLINE:
                plane.egress_inline += 1
            else:
                plane.egress_queued += 1


def egress_delivery_rows(broker: "Broker", slots, users, frame_idx,
                         frame_of) -> int:
    """Shared device-plane egress walk: deliver a (users, frame_idx)
    nonzero listing grouped per user (np.nonzero is row-major, so each
    user's frames are contiguous — one connection lookup per user).
    ``frame_of(f)`` materializes/caches the frame's Bytes; ``slots`` maps
    user slot → public key. Returns the number queued."""
    routed = 0
    start = 0
    n = len(users)
    while start < n:
        u = users[start]
        end = start
        while end < n and users[end] == u:
            end += 1
        key = slots.key_of(int(u))
        if key is not None:  # released mid-step: drop (user is gone)
            routed += try_send_frames_to_user_nowait(
                broker, key, [frame_of(int(f)) for f in frame_idx[start:end]])
        start = end
    return routed


async def try_send_to_broker(broker: "Broker", identifier: str,
                             raw: Bytes) -> bool:
    connection = broker.connections.get_broker_connection(identifier)
    if connection is None:
        return False
    clone = raw.clone()
    try:
        await connection.send_raw(clone)
        # control-plane mesh frames (topic/ledger sync) ride this path
        # rather than the routed egress batches — count them into the
        # per-link conservation table with the same wire-byte rule the
        # receiving end uses, or every mesh link reads recv > sent
        ledger_mod.note_link_sent(identifier, flowclass.frame_class(raw.data))
        return True
    except Exception as exc:
        clone.release()
        logger.info("send to broker %s failed (%r); removing", identifier, exc)
        broker.connections.remove_broker(identifier, reason="send failed")
        broker.update_metrics()
        return False


async def try_send_to_brokers(broker: "Broker", identifiers: Iterable[str],
                              raw: Bytes) -> int:
    """Fan a frame out to many peers (sender.rs try_send_to_brokers)."""
    sent = 0
    for ident in list(identifiers):
        if await try_send_to_broker(broker, ident, raw):
            sent += 1
    return sent
