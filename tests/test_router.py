"""Device-router tests: single-chip semantics, 8-shard mesh routing,
eviction propagation, and Pallas-kernel equivalence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pushcdn_tpu.ops.delivery_kernel import (
    delivery_matrix_pallas,
    delivery_matrix_reference,
)
from pushcdn_tpu.parallel.crdt import ABSENT, CrdtState, local_claim
from pushcdn_tpu.parallel.frames import FrameRing
from pushcdn_tpu.parallel.mesh import make_broker_mesh
from pushcdn_tpu.parallel.router import (
    BROKER_AXIS,
    DirectIngress,
    IngressBatch,
    LaneWords,
    RouterState,
    empty_router_state,
    make_mesh_lane_step,
    make_mesh_routing_step,
    routing_step_lanes_single,
    routing_step_single,
)
from pushcdn_tpu.proto.message import KIND_BROADCAST, KIND_DIRECT

U, S, F = 16, 8, 64


def _batch_from_ring(ring: FrameRing) -> IngressBatch:
    b = ring.take_batch()
    return IngressBatch(
        jnp.asarray(b.bytes_), jnp.asarray(b.kind), jnp.asarray(b.length),
        jnp.asarray(b.topic_mask.astype(np.uint32)), jnp.asarray(b.dest),
        jnp.asarray(b.valid))


def _claim(state: RouterState, slot: int, broker: int,
           topic_mask: int) -> RouterState:
    mask = jnp.zeros(U, bool).at[slot].set(True)
    return RouterState(
        local_claim(state.crdt, mask, jnp.int32(broker)),
        state.topic_masks.at[slot].set(topic_mask))


def test_single_chip_broadcast_and_direct():
    state = empty_router_state(U)
    state = _claim(state, 0, 0, 0b01)   # user 0: topic 0
    state = _claim(state, 1, 0, 0b10)   # user 1: topic 1
    ring = FrameRing(slots=S, frame_bytes=F)
    assert ring.push_broadcast(b"topic0 msg", topic_mask=0b01)
    assert ring.push_direct(b"direct to 1", dest_slot=1)
    res = routing_step_single(state, _batch_from_ring(ring))
    d = np.asarray(res.deliver)
    assert d[0, 0] and not d[0, 1]      # user0 gets the broadcast only
    assert d[1, 1] and not d[1, 0]      # user1 gets the direct only
    assert not np.asarray(res.evictions).any()
    # frame bytes surfaced for the egress pump
    assert bytes(np.asarray(res.gathered_bytes)[0][:10]) == b"topic0 msg"


def test_single_chip_unowned_user_gets_nothing():
    state = empty_router_state(U)
    state = _claim(state, 0, 3, 0b01)   # owned by broker 3, we are broker 0
    ring = FrameRing(slots=S, frame_bytes=F)
    ring.push_broadcast(b"x", topic_mask=0b01)
    ring.push_direct(b"y", dest_slot=0)
    res = routing_step_single(state, _batch_from_ring(ring))
    assert not np.asarray(res.deliver).any()  # delivery-iff-owner


def test_invalid_slots_never_deliver():
    state = _claim(empty_router_state(U), 0, 0, 0xFFFFFFFF)
    ring = FrameRing(slots=S, frame_bytes=F)
    ring.push_broadcast(b"real", topic_mask=0b1)
    batch = _batch_from_ring(ring)
    # poison the metadata of an EMPTY slot: must still not deliver
    batch = batch._replace(
        topic_mask=batch.topic_mask.at[5].set(0xFFFFFFFF),
        kind=batch.kind.at[5].set(KIND_BROADCAST))
    res = routing_step_single(state, batch)
    assert np.asarray(res.deliver)[0].sum() == 1  # only the real frame


def test_mesh_routing_8_shards():
    """Each of 8 broker shards owns one user on topic 0; a broadcast from
    every shard reaches every user exactly once; a direct lands only at its
    owner (the multichip fan-out path over the virtual CPU mesh)."""
    mesh = make_broker_mesh()
    B = mesh.devices.size
    assert B == 8, "conftest must provide 8 virtual CPU devices"
    step = make_mesh_routing_step(mesh)

    owners = np.full((B, U), ABSENT, np.int32)
    versions = np.zeros((B, U), np.uint32)
    ids = np.full((B, U), ABSENT, np.int32)
    masks = np.zeros((B, U), np.uint32)
    for i in range(B):
        owners[i, i] = i; versions[i, i] = 1; ids[i, i] = i; masks[i, i] = 0b1
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions), jnp.asarray(ids)),
        jnp.asarray(masks))

    parts = []
    for i in range(B):
        ring = FrameRing(slots=S, frame_bytes=F)
        ring.push_broadcast(f"from-{i}".encode(), topic_mask=0b1)
        if i == 2:
            ring.push_direct(b"direct to user 5", dest_slot=5)
        parts.append(ring.take_batch())
    batch = IngressBatch(
        jnp.asarray(np.stack([x.bytes_ for x in parts])),
        jnp.asarray(np.stack([x.kind for x in parts])),
        jnp.asarray(np.stack([x.length for x in parts])),
        jnp.asarray(np.stack([x.topic_mask for x in parts]).astype(np.uint32)),
        jnp.asarray(np.stack([x.dest for x in parts])),
        jnp.asarray(np.stack([x.valid for x in parts])))

    out = step(state, batch)
    d = np.asarray(out.deliver)  # [B, U, B*S]
    for b in range(B):
        expected = B + (1 if b == 5 else 0)  # all broadcasts (+1 direct)
        assert d[b, b].sum() == expected, (b, int(d[b, b].sum()))
        # no shard delivers to users it doesn't own
        others = [u for u in range(U) if u != b]
        assert d[b][others].sum() == 0


def test_mesh_eviction_on_ownership_change():
    """Shard 0 and shard 1 both claim user 0; shard 1's claim dominates
    (higher version) → shard 0 reports the eviction, parity with
    apply_user_sync's kick (connections/mod.rs:154-162)."""
    mesh = make_broker_mesh()
    B = mesh.devices.size
    step = make_mesh_routing_step(mesh)

    owners = np.full((B, U), ABSENT, np.int32)
    versions = np.zeros((B, U), np.uint32)
    ids = np.full((B, U), ABSENT, np.int32)
    masks = np.zeros((B, U), np.uint32)
    owners[0, 0], versions[0, 0], ids[0, 0] = 0, 1, 0   # shard0 claim v1
    owners[1, 0], versions[1, 0], ids[1, 0] = 1, 2, 1   # shard1 claim v2
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions), jnp.asarray(ids)),
        jnp.asarray(masks))
    empty = FrameRing(slots=S, frame_bytes=F).take_batch()
    batch = IngressBatch(*[jnp.asarray(np.stack([getattr(empty, f)] * B))
                           for f in ("bytes_", "kind", "length")],
                         jnp.asarray(np.stack([empty.topic_mask] * B).astype(np.uint32)),
                         jnp.asarray(np.stack([empty.dest] * B)),
                         jnp.asarray(np.stack([empty.valid] * B)))
    out = step(state, batch)
    ev = np.asarray(out.evictions)   # [B, U]
    assert ev[0, 0]                  # shard 0 must kick its local session
    assert not ev[1:, :].any()
    merged_owners = np.asarray(out.state.crdt.owners)
    assert (merged_owners[:, 0] == 1).all()  # everyone converged on shard 1


def test_mask_rides_ownership_handoff():
    """When a dominating ownership claim is adopted, the claimant's topic
    mask is adopted with it — stale masks after a handoff would misroute
    broadcasts (merge_all_gathered_with_payload's whole purpose)."""
    mesh = make_broker_mesh()
    B = mesh.devices.size
    step = make_mesh_routing_step(mesh)

    owners = np.full((B, U), ABSENT, np.int32)
    versions = np.zeros((B, U), np.uint32)
    ids = np.full((B, U), ABSENT, np.int32)
    masks = np.zeros((B, U), np.uint32)
    # every shard has a STALE view: user 0 owned by shard 0 with mask 0b01
    owners[:, 0] = 0; versions[:, 0] = 1; ids[:, 0] = 0; masks[:, 0] = 0b01
    # shard 1 takes user 0 over with a NEW mask 0b10 (version 2 dominates)
    owners[1, 0], versions[1, 0], ids[1, 0], masks[1, 0] = 1, 2, 1, 0b10
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions), jnp.asarray(ids)),
        jnp.asarray(masks))

    # a broadcast on topic 1 (mask 0b10) from shard 3
    parts = []
    for i in range(B):
        ring = FrameRing(slots=S, frame_bytes=F)
        if i == 3:
            ring.push_broadcast(b"new-topic msg", topic_mask=0b10)
        parts.append(ring.take_batch())
    batch = IngressBatch(
        jnp.asarray(np.stack([x.bytes_ for x in parts])),
        jnp.asarray(np.stack([x.kind for x in parts])),
        jnp.asarray(np.stack([x.length for x in parts])),
        jnp.asarray(np.stack([x.topic_mask for x in parts]).astype(np.uint32)),
        jnp.asarray(np.stack([x.dest for x in parts])),
        jnp.asarray(np.stack([x.valid for x in parts])))
    out = step(state, batch)
    # every shard converged on the new mask...
    np.testing.assert_array_equal(np.asarray(out.state.topic_masks)[:, 0],
                                  np.full(B, 0b10, np.uint32))
    # ...and the new owner (shard 1) delivered the topic-1 broadcast using
    # the adopted mask, in the SAME step as the handoff
    d = np.asarray(out.deliver)
    assert d[1, 0].sum() == 1
    assert d[0, 0].sum() == 0  # the old owner no longer delivers


def test_pallas_kernel_matches_reference():
    rng = np.random.default_rng(0)
    Uk, Nk = 64, 256
    user_masks = jnp.asarray(rng.integers(0, 2**16, Uk).astype(np.uint32))
    local = jnp.asarray(rng.random(Uk) < 0.5)
    tmask = jnp.asarray(rng.integers(0, 2**16, Nk).astype(np.uint32))
    kind = jnp.asarray(rng.choice([0, KIND_BROADCAST, KIND_DIRECT], Nk).astype(np.int32))
    dest = jnp.asarray(rng.integers(-1, Uk, Nk).astype(np.int32))
    ref = delivery_matrix_reference(user_masks, local, tmask, kind, dest)
    pal = delivery_matrix_pallas(user_masks, local, tmask, kind, dest,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))


def test_mesh_direct_all_to_all():
    """The one-hop direct path: frames staged into per-destination-shard
    buckets cross the mesh with ONE all_to_all and deliver only at the
    owner (SURVEY.md §2e: point-to-point collective keyed by owner shard),
    never riding the broadcast all_gather."""
    from pushcdn_tpu.parallel.frames import DirectBuckets
    from pushcdn_tpu.parallel.router import DirectIngress

    mesh = make_broker_mesh()
    B = mesh.devices.size
    C = 4
    step = make_mesh_routing_step(mesh, with_direct=True)

    # shard i owns user slot i, topic mask irrelevant here
    owners = np.full((B, U), ABSENT, np.int32)
    versions = np.zeros((B, U), np.uint32)
    ids = np.full((B, U), ABSENT, np.int32)
    masks = np.zeros((B, U), np.uint32)
    for i in range(B):
        owners[i, i] = i; versions[i, i] = 1; ids[i, i] = i
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions), jnp.asarray(ids)),
        jnp.asarray(masks))

    # empty broadcast ingress; shard 2 sends directs to users 5 and 7
    # (owned by shards 5 and 7), shard 6 sends to user 0
    parts = [FrameRing(slots=S, frame_bytes=F).take_batch() for _ in range(B)]
    batch = IngressBatch(
        jnp.asarray(np.stack([x.bytes_ for x in parts])),
        jnp.asarray(np.stack([x.kind for x in parts])),
        jnp.asarray(np.stack([x.length for x in parts])),
        jnp.asarray(np.stack([x.topic_mask for x in parts]).astype(np.uint32)),
        jnp.asarray(np.stack([x.dest for x in parts])),
        jnp.asarray(np.stack([x.valid for x in parts])))

    buckets = [DirectBuckets(B, capacity=C, frame_bytes=F) for _ in range(B)]
    assert buckets[2].push(5, b"to user 5", dest_slot=5)
    assert buckets[2].push(7, b"to user 7", dest_slot=7)
    assert buckets[6].push(0, b"to user 0", dest_slot=0)
    parts_d = [b.take_batch() for b in buckets]
    direct = DirectIngress(
        jnp.asarray(np.stack([x.bytes_ for x in parts_d])),
        jnp.asarray(np.stack([x.length for x in parts_d])),
        jnp.asarray(np.stack([x.dest for x in parts_d])),
        jnp.asarray(np.stack([x.valid for x in parts_d])))

    out = step(state, batch, direct)
    assert np.asarray(out.deliver).sum() == 0       # nothing on the broadcast path
    dd = np.asarray(out.direct_deliver)             # [B, U, B*C]
    db = np.asarray(out.direct_bytes)               # [B, B*C, F]
    dl = np.asarray(out.direct_length)
    # exactly the three deliveries, each at its owner shard only
    assert dd.sum() == 3
    for shard, user, payload in [(5, 5, b"to user 5"), (7, 7, b"to user 7"),
                                 (0, 0, b"to user 0")]:
        hits = np.nonzero(dd[shard, user])[0]
        assert len(hits) == 1, (shard, user, hits)
        f = hits[0]
        assert db[shard, f, :dl[shard, f]].tobytes() == payload
        # no other shard delivers this frame
        assert dd[:, user].sum() == 1

    # bucket overflow is per-link backpressure
    small = DirectBuckets(B, capacity=1, frame_bytes=F)
    assert small.push(3, b"x", 3)
    assert not small.push(3, b"y", 3)   # that link is full
    assert small.push(4, b"z", 4)       # other links unaffected


def test_lane_step_single_and_mesh():
    """Size-bucketed lanes (hard-part #1): one step routes several
    independently-shaped rings with ONE shared CRDT merge — single-chip
    and over the 8-shard mesh with a direct all_to_all lane."""
    state = empty_router_state(U)
    state = _claim(state, 0, 0, 0b1)
    small = FrameRing(slots=8, frame_bytes=64)
    small.push_broadcast(b"small", 0b1)
    big = FrameRing(slots=4, frame_bytes=512)
    big.push_broadcast(b"B" * 300, 0b1)
    big.push_direct(b"D" * 200, dest_slot=0)
    res = routing_step_lanes_single(
        state, (_batch_from_ring(small), _batch_from_ring(big)))
    assert np.asarray(res.lanes[0].deliver)[0].sum() == 1
    assert np.asarray(res.lanes[1].deliver)[0].sum() == 2
    assert bytes(np.asarray(res.lanes[1].gathered_bytes)[0][:3]) == b"BBB"

    n = 8
    mesh = make_broker_mesh(n)
    step = make_mesh_lane_step(mesh)
    owners = np.full((n, U), ABSENT, np.int32)
    versions = np.zeros((n, U), np.uint32)
    ids = np.full((n, U), ABSENT, np.int32)
    masks = np.zeros((n, U), np.uint32)
    for i in range(n):
        owners[i, i] = i
        versions[i, i] = 1
        ids[i, i] = i
        masks[i, i] = 0b1
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(ids)), jnp.asarray(masks))

    def stack_rings(make_ring):
        parts = []
        for i in range(n):
            parts.append(make_ring(i).take_batch())
        return IngressBatch(
            jnp.asarray(np.stack([p.bytes_ for p in parts])),
            jnp.asarray(np.stack([p.kind for p in parts])),
            jnp.asarray(np.stack([p.length for p in parts])),
            jnp.asarray(np.stack([p.topic_mask for p in parts])),
            jnp.asarray(np.stack([p.dest for p in parts])),
            jnp.asarray(np.stack([p.valid for p in parts])))

    def small_ring(i):
        r = FrameRing(slots=4, frame_bytes=64)
        r.push_broadcast(b"s%d" % i, 0b1)
        return r

    def big_ring(i):
        r = FrameRing(slots=2, frame_bytes=512)
        r.push_broadcast(b"L" * 400, 0b1)
        return r

    from pushcdn_tpu.parallel.frames import DirectBuckets
    from pushcdn_tpu.parallel.router import DirectIngress
    dparts = []
    for i in range(n):
        d = DirectBuckets(n, capacity=2, frame_bytes=256)
        d.push((i + 1) % n, b"d%d" % i, dest_slot=(i + 1) % n)
        dparts.append(d.take_batch())
    direct = DirectIngress(
        jnp.asarray(np.stack([p.bytes_ for p in dparts])),
        jnp.asarray(np.stack([p.length for p in dparts])),
        jnp.asarray(np.stack([p.dest for p in dparts])),
        jnp.asarray(np.stack([p.valid for p in dparts])))

    out = step(state, (stack_rings(small_ring), stack_rings(big_ring)),
               (direct,))
    # each shard's broadcast (per lane) reaches every owned user once
    assert np.asarray(out.lanes[0].deliver).sum() == n * n
    assert np.asarray(out.lanes[1].deliver).sum() == n * n
    # each all_to_all direct frame lands exactly once at its owner shard
    assert np.asarray(out.direct_lanes[0].deliver).sum() == n
    # CRDT converged identically on every shard
    merged = np.asarray(out.state.crdt.owners)
    assert (merged[0] == merged).all()


def test_liveness_mask_dead_shard():
    """Hard-part #3 (dynamic membership on a static mesh): a shard marked
    dead contributes no deliveries, and slots it owned are tombstoned by an
    identical deterministic release on every live shard."""
    import jax.numpy as jnp
    from pushcdn_tpu.parallel.router import make_mesh_lane_step

    n = 8
    dead = 3
    mesh = make_broker_mesh(n)
    step = make_mesh_lane_step(mesh)
    owners = np.full((n, U), ABSENT, np.int32)
    versions = np.zeros((n, U), np.uint32)
    ids = np.full((n, U), ABSENT, np.int32)
    masks = np.zeros((n, U), np.uint32)
    for i in range(n):
        owners[i, i] = i
        versions[i, i] = 1
        ids[i, i] = i
        masks[i, i] = 0b1
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(ids)), jnp.asarray(masks))
    parts = []
    for i in range(n):
        r = FrameRing(slots=4, frame_bytes=64)
        r.push_broadcast(b"from %d" % i, 0b1)
        parts.append(r.take_batch())
    batch = IngressBatch(
        jnp.asarray(np.stack([p.bytes_ for p in parts])),
        jnp.asarray(np.stack([p.kind for p in parts])),
        jnp.asarray(np.stack([p.length for p in parts])),
        jnp.asarray(np.stack([p.topic_mask for p in parts])),
        jnp.asarray(np.stack([p.dest for p in parts])),
        jnp.asarray(np.stack([p.valid for p in parts])))
    live = np.ones(n, bool)
    live[dead] = False
    out = step(state, (batch,), (),
               jnp.asarray(np.broadcast_to(live, (n, n))))
    deliver = np.asarray(out.lanes[0].deliver)
    # the dead shard's broadcast delivers nowhere; everyone else's reaches
    # the n-1 live owned users (the dead shard's user slot was released)
    merged_owners = np.asarray(out.state.crdt.owners)
    assert (merged_owners[0] == merged_owners).all()  # still convergent
    assert (merged_owners[:, dead] == ABSENT).all()   # tombstoned
    # per shard: slots delivered = live users x live frames
    for shard in range(n):
        d = deliver[shard]
        # frames are ordered [src_shard * slots + slot]
        dead_frame_cols = d[:, dead * 4:(dead + 1) * 4]
        assert not dead_frame_cols.any(), "dead shard's frames delivered"
    total = deliver.sum()
    assert total == (n - 1) * (n - 1), total  # 7 live frames x 7 live users
    # released slots' masks were cleared with the claim
    assert (np.asarray(out.state.topic_masks)[:, dead] == 0).all()


def test_multiword_topic_masks():
    """8×u32 masks cover the reference's full u8 topic space: delivery on
    topics ≥ 32, Pallas kernel ≡ jnp reference at W=8, and masks riding
    the lane step."""
    from pushcdn_tpu.parallel.frames import (
        TOPIC_WORDS_FULL, mask_of_topics, split_mask)

    rng = np.random.default_rng(7)
    Uw, Nw, W = 16, 256, TOPIC_WORDS_FULL
    umask = rng.integers(0, 2**32, (Uw, W), dtype=np.uint32)
    tmask = rng.integers(0, 2**32, (Nw, W), dtype=np.uint32)
    local = rng.random(Uw) < 0.7
    kind = rng.choice([0, KIND_BROADCAST, KIND_DIRECT], Nw).astype(np.int32)
    dest = rng.integers(-1, Uw, Nw).astype(np.int32)
    ref = delivery_matrix_reference(
        jnp.asarray(umask), jnp.asarray(local), jnp.asarray(tmask),
        jnp.asarray(kind), jnp.asarray(dest))
    pal = delivery_matrix_pallas(
        jnp.asarray(umask), jnp.asarray(local), jnp.asarray(tmask),
        jnp.asarray(kind), jnp.asarray(dest), interpret=True)
    assert (np.asarray(ref) == np.asarray(pal)).all()

    # semantic check on a high topic through the full lane step
    state = empty_router_state(U, topic_words=W)
    mask200 = mask_of_topics([200], W)
    claim = jnp.zeros(U, bool).at[0].set(True)
    from pushcdn_tpu.parallel.crdt import local_claim
    state = RouterState(
        local_claim(state.crdt, claim, jnp.int32(0)),
        state.topic_masks.at[0].set(jnp.asarray(split_mask(mask200, W))))
    ring = FrameRing(slots=8, frame_bytes=64, topic_words=W)
    ring.push_broadcast(b"topic 200", topic_mask=mask200)
    ring.push_broadcast(b"topic 7", topic_mask=mask_of_topics([7], W))
    res = routing_step_lanes_single(state, (_batch_from_ring(ring),))
    d = np.asarray(res.lanes[0].deliver)
    assert d[0, 0] and not d[0, 1]  # subscribed to 200, not to 7


def _seeded_mesh_inputs(n=8, seed=0, with_direct=True):
    """Stacked state + traffic for an n-shard mesh (helper for the fused
    one-collective tests)."""
    from pushcdn_tpu.parallel.frames import DirectBuckets
    from pushcdn_tpu.parallel.router import DirectIngress

    rng = np.random.default_rng(seed)
    owners = np.full((n, U), ABSENT, np.int32)
    versions = np.zeros((n, U), np.uint32)
    ids = np.full((n, U), ABSENT, np.int32)
    masks = np.zeros((n, U), np.uint32)
    for i in range(n):
        owners[i, i] = i
        versions[i, i] = 1
        ids[i, i] = i
        masks[i, i] = rng.integers(1, 8)
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(ids)), jnp.asarray(masks))
    parts = []
    for i in range(n):
        ring = FrameRing(slots=S, frame_bytes=F)
        for j in range(int(rng.integers(1, 4))):
            ring.push_broadcast(b"b%d-%d" % (i, j),
                                int(rng.integers(1, 8)))
        parts.append(ring.take_batch())
    batch = IngressBatch(
        *[jnp.asarray(np.stack([getattr(p, f) for p in parts]))
          for f in ("bytes_", "kind", "length", "topic_mask", "dest",
                    "valid")])
    direct = None
    if with_direct:
        dparts = []
        for i in range(n):
            d = DirectBuckets(n, capacity=4, frame_bytes=F)
            d.push((i + 1) % n, b"d%d" % i, dest_slot=(i + 1) % n)
            d.push((i + 3) % n, b"e%d" % i, dest_slot=(i + 3) % n)
            dparts.append(d.take_batch())
        direct = DirectIngress(
            *[jnp.asarray(np.stack([getattr(p, f) for p in dparts]))
              for f in ("bytes_", "length", "dest", "valid")])
    return state, batch, direct


def test_fused_tick_matches_per_array_and_counts_one_collective():
    """ISSUE 8 tentpole: the fused mesh tick (one packed all_gather) is
    bit-identical to the per-array collective schedule, and the lowered
    program contains EXACTLY one collective op (vs a dozen-plus for the
    per-array form) — the counted one-collective-per-tick invariant."""
    import jax

    from pushcdn_tpu.parallel import router as router_mod
    from pushcdn_tpu.parallel.router import count_collectives

    n = 8
    mesh = make_broker_mesh(n)
    state, batch, direct = _seeded_mesh_inputs(n, seed=3)
    live = jnp.ones((n, n), bool)

    step_f = make_mesh_lane_step(mesh, fused=True)
    step_u = make_mesh_lane_step(mesh, fused=False)
    out_f = step_f(state, (batch,), (direct,), live)
    out_u = step_u(state, (batch,), (direct,), live)
    for get in (lambda o: o.lanes[0].deliver,
                lambda o: o.lanes[0].gathered_bytes,
                lambda o: o.lanes[0].gathered_length,
                lambda o: o.direct_lanes[0].deliver,
                lambda o: o.direct_lanes[0].gathered_bytes,
                lambda o: o.state.crdt.owners,
                lambda o: o.state.topic_masks,
                lambda o: o.evictions):
        np.testing.assert_array_equal(np.asarray(get(out_f)),
                                      np.asarray(get(out_u)))

    # lowered-program collective count: fused == 1, per-array >> 1
    low_f = jax.jit(step_f).lower(state, (batch,), (direct,),
                                  live).as_text()
    low_u = jax.jit(step_u).lower(state, (batch,), (direct,),
                                  live).as_text()
    assert count_collectives(low_f) == 1, low_f.count("all_gather")
    assert count_collectives(low_u) > 1

    # trace-time counter agrees: tracing a fresh fused program adds
    # exactly one collective call site
    before = router_mod.trace_collectives()
    state2, batch2, direct2 = _seeded_mesh_inputs(n, seed=4)
    step_f2 = make_mesh_lane_step(mesh, fused=True, gather_bytes=False)
    step_f2(state2, (batch2,), (direct2,), live)
    assert router_mod.trace_collectives() - before == 1


def test_fused_tick_liveness_and_eviction_equivalence():
    """Dead-shard masking and ownership-eviction semantics survive the
    fused packing unchanged."""
    n = 8
    mesh = make_broker_mesh(n)
    state, batch, direct = _seeded_mesh_inputs(n, seed=9)
    # shard 2 and 5 dead; shard 1 re-claims user 0 at a higher version
    owners = np.asarray(state.crdt.owners).copy()
    versions = np.asarray(state.crdt.versions).copy()
    ids = np.asarray(state.crdt.identities).copy()
    owners[1, 0], versions[1, 0], ids[1, 0] = 1, 5, 1
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(ids)), state.topic_masks)
    live = np.ones((n, n), bool)
    live[:, 2] = False
    live[:, 5] = False
    live = jnp.asarray(live)
    out_f = make_mesh_lane_step(mesh, fused=True)(
        state, (batch,), (direct,), live)
    out_u = make_mesh_lane_step(mesh, fused=False)(
        state, (batch,), (direct,), live)
    for get in (lambda o: o.lanes[0].deliver,
                lambda o: o.direct_lanes[0].deliver,
                lambda o: o.state.crdt.owners,
                lambda o: o.state.crdt.versions,
                lambda o: o.state.topic_masks,
                lambda o: o.evictions):
        np.testing.assert_array_equal(np.asarray(get(out_f)),
                                      np.asarray(get(out_u)))
    # the dead shards' slots tombstoned, eviction reported at shard 0
    merged = np.asarray(out_f.state.crdt.owners)
    assert (merged[:, 2] == ABSENT).all()
    assert (merged[:, 5] == ABSENT).all()
    assert np.asarray(out_f.evictions)[0, 0]


# ---------------------------------------------------------------------------
# the packed entry of the mesh lane step (ISSUE 36): every lane's metadata
# in one u32 buffer (``LaneWords``), a zero-width stub for each lane's
# bytes where nothing gathers them — bit for bit the per-array entry, at
# the shapes and traffic mixes ``MeshBrokerGroup`` runs it with.
# ---------------------------------------------------------------------------

_PB = 4                                   # shards
_PLAT = 4                                 # the latency slice
_PBASE, _PWIDE = (64, 16, 8), (256, 4, 2)  # (frame bytes, ring, bucket)


@functools.lru_cache(maxsize=None)
def _lane_steps(gather_bytes: bool):
    mesh = make_broker_mesh(_PB, devices=jax.devices()[:_PB])
    return mesh, make_mesh_lane_step(mesh, gather_bytes=gather_bytes,
                                     fused=True)


def _packed_case(shape, mix, topic_words, dead, seed):
    """Host snapshots ([lane][shard]) and stacked state of one tick: the
    lanes of ``shape`` ("full": base + wide; "sliced": the base lane's
    first ``_PLAT`` slots), traffic on the lanes ``mix`` names, every
    shard publishing, a re-claim that evicts, and ``dead`` masked out."""
    from pushcdn_tpu.parallel.frames import (
        DirectBuckets, mask_mirror_shape, slice_batch, slice_direct_batch)
    rng = np.random.default_rng(seed)
    W = topic_words
    lanes = [_PBASE, _PWIDE] if shape == "full" else [_PBASE]
    busy = {"base": [0], "wide": [1], "both": [0, 1], "none": []}[mix]
    batches, directs = [], []
    for li, (fb, ring_slots, bucket) in enumerate(lanes):
        room = _PLAT if shape == "sliced" else ring_slots
        room_d = _PLAT if shape == "sliced" else bucket
        lane_b, lane_d = [], []
        for shard in range(_PB):
            ring = FrameRing(slots=ring_slots, frame_bytes=fb, topic_words=W)
            bkts = DirectBuckets(_PB, capacity=bucket, frame_bytes=fb)
            if li in busy:
                for j in range(int(rng.integers(1, room + 1))):
                    # topics on both sides of the first mask word
                    mask = (1 << int(rng.integers(0, 32 * W))) | 1
                    assert ring.push_broadcast(b"b%d.%d.%d" % (li, shard, j),
                                               mask)
                for j in range(int(rng.integers(1, room_d + 1))):
                    to = int(rng.integers(0, _PB))
                    assert bkts.push(to, b"d%d.%d.%d" % (li, shard, j),
                                     dest_slot=int(rng.integers(0, U)))
            b, d = ring.take_batch(), bkts.take_batch()
            if shape == "sliced":
                b, d = slice_batch(b, _PLAT), slice_direct_batch(d, _PLAT)
            lane_b.append(b)
            lane_d.append(d)
        batches.append(lane_b)
        directs.append(lane_d)
    owners = np.full((_PB, U), ABSENT, np.int32)
    versions = np.zeros((_PB, U), np.uint32)
    masks = np.zeros(
        (_PB,) + np.zeros(mask_mirror_shape(U, W)).shape, np.uint32)
    for slot in range(U):
        owners[:, slot] = slot % _PB
        versions[:, slot] = 1
        masks[:, slot] = rng.integers(0, 2**32, masks.shape[2:],
                                      dtype=np.uint32) | 1
    owners[1, 0], versions[1, 0] = 1, 5   # shard 1 takes user 0 from shard 0
    state = RouterState(
        CrdtState(jnp.asarray(owners), jnp.asarray(versions),
                  jnp.asarray(owners)), jnp.asarray(masks))
    live = np.ones((_PB, _PB), bool)
    if dead is not None:
        live[:, dead] = False
    return batches, directs, state, jnp.asarray(live)


def _stacked(lane, cls, fields):
    return cls(*[jnp.asarray(np.stack([getattr(s, f) for s in lane]))
                 for f in fields])


def _packed_args(batches, directs, state, gather_bytes):
    """What the packed entry takes for these snapshots: each lane's bytes
    leaf (zero-width where nothing gathers bytes) and the one buffer,
    packed over a used one."""
    layout = LaneWords([lane[0].valid.shape[0] for lane in batches],
                       [lane[0].valid.shape for lane in directs],
                       state.topic_masks.shape[2:])
    buf = np.full((_PB, layout.width), 0xDEADBEEF, np.uint32)
    layout.pack(buf, batches, directs)

    def bytes_leaf(lane):
        rows = np.stack([s.bytes_ for s in lane])
        return jnp.asarray(rows if gather_bytes else rows[..., :0])

    return (tuple(bytes_leaf(lane) for lane in batches),
            tuple(bytes_leaf(lane) for lane in directs), jnp.asarray(buf))


@pytest.mark.parametrize("shape, mix, topic_words, dead, gather_bytes", [
    ("full", "base", 8, None, False),
    ("full", "wide", 8, None, False),
    ("full", "both", 8, 2, False),
    ("full", "none", 8, None, False),
    ("sliced", "base", 8, None, False),
    ("sliced", "none", 8, 1, False),
    ("full", "both", 1, None, False),
    ("sliced", "base", 1, 3, False),
    ("full", "both", 8, 2, True),
    ("sliced", "base", 1, None, True),
], ids=lambda v: str(v))
def test_packed_lane_step_matches_per_array(shape, mix, topic_words, dead,
                                            gather_bytes):
    batches, directs, state, live = _packed_case(
        shape, mix, topic_words, dead, seed=36)
    _mesh, step = _lane_steps(gather_bytes)
    want = step(
        state,
        tuple(_stacked(lane, IngressBatch, ("bytes_", "kind", "length",
                                            "topic_mask", "dest", "valid"))
              for lane in batches),
        tuple(_stacked(lane, DirectIngress, ("bytes_", "length", "dest",
                                             "valid")) for lane in directs),
        live)

    lane_bytes, direct_bytes, words = _packed_args(
        batches, directs, state, gather_bytes)
    got = step(state, lane_bytes, direct_bytes, live, words)

    def same(a, b):
        if a is None or b is None:
            assert a is None and b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    assert len(got.lanes) == len(batches) == len(want.lanes)
    assert len(got.direct_lanes) == len(directs) == len(want.direct_lanes)
    for g, w in zip(got.lanes + got.direct_lanes,
                    want.lanes + want.direct_lanes):
        same(g.deliver, w.deliver)
        same(g.gathered_length, w.gathered_length)
        same(g.gathered_bytes, w.gathered_bytes)
        assert (g.gathered_bytes is not None) == gather_bytes
    for leaf in ("owners", "versions", "identities"):
        same(getattr(got.state.crdt, leaf), getattr(want.state.crdt, leaf))
    same(got.state.topic_masks, want.state.topic_masks)
    same(got.evictions, want.evictions)
    # the case says something: the re-claim evicts, busy lanes deliver
    assert np.asarray(got.evictions)[0, 0]
    delivered = sum(int(np.asarray(l.deliver).sum())
                    for l in got.lanes + got.direct_lanes)
    assert (delivered > 0) == (mix != "none")


def test_packed_lane_step_is_one_collective():
    """The packed form of the fused tick is still ONE collective: counted
    at trace time and in the lowered text."""
    from pushcdn_tpu.parallel import router as router_mod
    from pushcdn_tpu.parallel.router import count_collectives
    batches, directs, state, live = _packed_case("full", "both", 8, None,
                                                 seed=37)
    mesh = make_broker_mesh(_PB, devices=jax.devices()[:_PB])
    step = make_mesh_lane_step(mesh, gather_bytes=False, fused=True)
    lane_bytes, direct_bytes, words = _packed_args(
        batches, directs, state, gather_bytes=False)
    args = (state, lane_bytes, direct_bytes, live, words)
    before = router_mod.trace_collectives()
    step(*args)
    assert router_mod.trace_collectives() - before == 1
    assert count_collectives(step.lower(*args).as_text()) == 1
