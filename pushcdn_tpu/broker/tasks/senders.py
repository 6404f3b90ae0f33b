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


def _egress_batched(plane, broker: "Broker", streams) -> list:
    """Send the streams of one back-pressured step whose links are idle
    plain sockets (``Connection.idle_fd``) by ONE native call
    (``native.send_batch``: the sends fanned over a few threads, joined
    before it returns), straight from the step's pooled buffer; the slots
    it did not take, for the caller's loop. The event loop stands still
    for the call, as it does for that loop's ``send()``s, so between a
    link's check, its send and its settling nothing else can write to,
    close or reuse its socket, and a user has one stream in ``streams``,
    so one send: nothing can reorder.

    The sends that took their whole stream (all of them, where the
    readers keep up) are settled here in one pass, not once a link:
    the plane's tallies grow by their sums (a stream the pump wrote
    itself, ``egress_inline``, and ``egress_batched``), and
    ``Connection.sent_whole_on_fds`` credits the transport's byte count,
    the class counters and the ledger's transit once with the totals.
    Every other entry goes through ``Connection.sent_on_fd`` as a send
    of its own, in batch order: a short one (``EAGAIN``: of no bytes),
    whose remainder its transport holds from then on, is tallied like a
    full one and in ``egress_batched_short``; any other errno removes
    that user only, and is in no tally."""
    slots = plane.slots
    user_connection = broker.connections.get_user_connection
    users = streams.users
    taken, keys, links, fds, rest = [], [], [], [], []
    for slot, size in zip(users, streams.nbytes[users].tolist()):
        key = slots.key_of(slot)
        connection = None if key is None else user_connection(key)
        fd = None if connection is None else connection.idle_fd(size)
        if fd is None:
            rest.append(slot)
        else:
            taken.append(slot)
            keys.append(key)
            links.append(connection)
            fds.append(fd)
    if not taken:
        return rest
    at = np.array(taken, np.int64)
    nbytes, nframes = streams.nbytes[at], streams.msgs[at]
    sent = native_mod.send_batch(streams.buf, np.array(fds, np.int32),
                                 streams.offsets[at], nbytes)
    whole = sent == nbytes
    for i in np.flatnonzero(~whole).tolist():
        try:
            links[i].sent_on_fd(streams.stream(taken[i]), int(sent[i]),
                                nframes=int(nframes[i]))
        except Exception as exc:
            _send_failed(broker, keys[i], links[i], exc)
            continue
        plane.messages_routed += int(nframes[i])
        plane.egress_inline += 1
        plane.egress_batched += 1
        plane.egress_batched_short += 1
    if whole.all():
        settled = links
    else:
        settled = [links[i] for i in np.flatnonzero(whole).tolist()]
        nbytes, nframes = nbytes[whole], nframes[whole]
    if settled:
        Connection.sent_whole_on_fds(settled, nbytes, nframes)
        plane.messages_routed += int(nframes.sum())
        plane.egress_inline += len(settled)
        plane.egress_batched += len(settled)
    return rest


def egress_streams(plane, broker: "Broker", streams,
                   back_pressured: bool = False) -> None:
    """Deliver one step's native egress (:class:`native.EgressStreams`):
    one pre-framed stream hand-off per user with deliveries, tallied on
    ``plane`` (a ``DevicePlane`` or a broker group): ``messages_routed``,
    and how each hand-off went, ``egress_inline`` or ``egress_queued``
    (of both, ``egress_tls`` over a link that encrypts:
    :func:`try_send_encoded_to_user_nowait`).

    ``back_pressured`` is the pump's observation that the step's take
    found the base lane full: its publishers wait on the step, so the
    next one carries as many frames and makes as many sends however
    short this one is, and the time of the sends is the rate. Only then
    do the idle links' sends leave together over several threads
    (:func:`_egress_batched`, which also accounts for them: the whole
    sends of the batch in one pass, the plane's tallies by their sums);
    every other hand-off, and every one of a step that is not
    back-pressured (where shorter sends would buy a faster cadence of
    smaller steps with cores), goes one by one below, each accounted by
    the connection's own call."""
    slots = plane.slots
    users = streams.users
    if back_pressured:
        users = _egress_batched(plane, broker, streams)
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
