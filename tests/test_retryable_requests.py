"""Exactly-once writes: retryable-request dedup (tablet/retryable_requests).

The load-bearing scenario (round-2 Weak #6): a write whose first attempt
replicated but whose ack was lost (OperationOutcomeUnknown) is retried by
the client — it must apply exactly once, across leader changes and WAL
replay (ref: src/yb/consensus/retryable_requests.cc).
"""

import pytest

from yugabyte_tpu.consensus.raft import OperationOutcomeUnknown
from yugabyte_tpu.tablet.tablet_peer import TabletPeer
from yugabyte_tpu.utils.status import StatusError

import sys
import os
sys.path.insert(0, os.path.dirname(__file__))
from test_consensus import (  # noqa: E402
    LocalTransport, PeerHarness, make_schema, wait_for, write_op)

CID = b"client-0123456789"[:16]


def _entry_count(peer):
    """Exact count of raw KV entries (every version) in the regular DB."""
    return sum(1 for _ in peer.tablet.regular_db.iter_from(b""))


def test_duplicate_request_returns_original_result(tmp_path):
    h = PeerHarness(tmp_path)
    try:
        leader = h.elect("ts0")
        ht1 = leader.write([write_op(h.schema, "k1", 1)],
                           request=(CID, 7))
        n = _entry_count(leader)
        ht2 = leader.write([write_op(h.schema, "k1", 1)],
                           request=(CID, 7))
        assert ht2.value == ht1.value
        assert _entry_count(leader) == n  # nothing re-applied
        # a different request id applies normally
        ht3 = leader.write([write_op(h.schema, "k1", 2)],
                           request=(CID, 8))
        assert ht3.value != ht1.value
        assert _entry_count(leader) == n + 2  # liveness + column
    finally:
        h.shutdown()


def test_unknown_outcome_retry_applies_once(tmp_path):
    """Replicate succeeds but the ack is lost: the retry must dedup."""
    h = PeerHarness(tmp_path)
    try:
        leader = h.elect("ts0")
        real_submit = leader.tablet.consensus.submit

        def flaky_submit(*a, **kw):
            real_submit(*a, **kw)
            raise OperationOutcomeUnknown("ack lost after replication")

        leader.tablet.consensus.submit = flaky_submit
        with pytest.raises(OperationOutcomeUnknown):
            leader.write([write_op(h.schema, "kx", 5)], request=(CID, 20))
        leader.tablet.consensus.submit = real_submit
        n = _entry_count(leader)
        # the client's retry loop re-sends the SAME request id
        ht = leader.write([write_op(h.schema, "kx", 5)], request=(CID, 20))
        assert ht.value > 0
        assert _entry_count(leader) == n  # zero additional application
    finally:
        h.shutdown()


def test_in_flight_duplicate_is_pushed_back(tmp_path):
    h = PeerHarness(tmp_path)
    try:
        leader = h.elect("ts0")
        reg = leader.tablet.retryable
        assert reg.check_or_track(CID, 33)[0] == "new"
        assert reg.check_or_track(CID, 33)[0] == "in_flight"
        with pytest.raises(StatusError):
            leader.write([write_op(h.schema, "ky", 1)], request=(CID, 33))
        reg.failed(CID, 33)
        leader.write([write_op(h.schema, "ky", 1)], request=(CID, 33))
    finally:
        h.shutdown()


def test_dedup_survives_leader_change(tmp_path):
    h = PeerHarness(tmp_path)
    try:
        leader = h.elect("ts0")
        ht1 = leader.write([write_op(h.schema, "kz", 9)], request=(CID, 40))
        # every follower applied the batch (and its request tag)
        wait_for(lambda: all(
            len(p.tablet.retryable) == 1 for p in h.peers.values()),
            msg="registry replicated everywhere")
        new_leader = h.elect("ts1")
        n = _entry_count(new_leader)
        ht2 = new_leader.write([write_op(h.schema, "kz", 9)],
                               request=(CID, 40))
        assert ht2.value == ht1.value
        assert _entry_count(new_leader) == n
    finally:
        h.shutdown()


def test_dedup_survives_restart_replay(tmp_path):
    transport = LocalTransport()
    schema = make_schema()
    peer = TabletPeer("t1", str(tmp_path / "solo"), schema, "ts0", ("ts0",),
                      transport).start(election_timer=False)
    peer.raft.start_election(ignore_lease=True)
    wait_for(lambda: peer.raft.is_leader(), msg="leader")
    ht1 = peer.write([write_op(schema, "kr", 3)], request=(CID, 55))
    peer.shutdown()

    peer2 = TabletPeer("t1", str(tmp_path / "solo"), schema, "ts0",
                       ("ts0",), transport).start(election_timer=False)
    try:
        peer2.raft.start_election(ignore_lease=True)
        wait_for(lambda: peer2.raft.is_leader(), msg="leader after restart")
        assert len(peer2.tablet.retryable) == 1  # rebuilt from WAL replay
        n = _entry_count(peer2)
        ht2 = peer2.write([write_op(schema, "kr", 3)], request=(CID, 55))
        assert ht2.value == ht1.value
        assert _entry_count(peer2) == n
    finally:
        peer2.shutdown()


def test_request_tag_from_a_sidecar_payload_is_hashable():
    """A follower's raft payload arrives as the RPC sidecar's bytearray
    (batches of 32 KB and up): the decoded (client_id, request_id) tag
    keys the dedup registry's dicts, so it must come back as bytes — a
    bytearray tag made every follower's apply raise TypeError and defer
    forever (found by chip_smoke.py's three-replica read-back)."""
    from yugabyte_tpu.tablet.retryable_requests import RetryableRequests
    from yugabyte_tpu.tablet.tablet_peer import (decode_write_batch,
                                                 encode_write_batch)
    payload = bytearray(encode_write_batch(
        [(b"k", b"v")], request=(b"c" * 16, 7)))
    _pairs, _intents, request = decode_write_batch(payload)
    assert request == (b"c" * 16, 7) and isinstance(request[0], bytes)
    reg = RetryableRequests()
    reg.track_appended(*request)
    reg.replicated(request[0], request[1], 123)
