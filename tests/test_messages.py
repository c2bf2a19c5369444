"""Message schema round-trips, envelope encoding, transaction hashing.

Mirrors the reference's hash tests (``UtilsTest.java:11-33``: identical
transactions hash equal, different ones differ).
"""

import pytest

from mochi_tpu.protocol import (
    Action,
    Envelope,
    FailType,
    Grant,
    HelloFromServer,
    HelloToServer,
    MultiGrant,
    Operation,
    OperationResult,
    ReadFromServer,
    ReadToServer,
    RequestFailedFromServer,
    Status,
    SyncEntry,
    Transaction,
    TransactionResult,
    Write1OkFromServer,
    Write1RefusedFromServer,
    Write1ToServer,
    Write2AnsFromServer,
    Write2ToServer,
    WriteCertificate,
    certificates_deferred,
    decode_envelope,
    encode_envelope,
    transaction_hash,
)
from mochi_tpu.protocol.codec import decode, encode


def sample_txn() -> Transaction:
    return Transaction(
        (
            Operation(Action.WRITE, "k1", b"v1"),
            Operation(Action.READ, "k2"),
            Operation(Action.DELETE, "k3"),
        )
    )


def sample_multigrant(signed: bool = False) -> MultiGrant:
    txh = transaction_hash(sample_txn())
    mg = MultiGrant(
        grants={
            "k1": Grant("k1", 1042, 1, txh, Status.OK),
            "k3": Grant("k3", 1042, 1, txh, Status.OK),
        },
        client_id="client-abc",
        server_id="server-1",
    )
    if signed:
        mg = mg.with_signature(b"\x01" * 64)
    return mg


def sample_certificate() -> WriteCertificate:
    return WriteCertificate(
        {f"server-{i}": sample_multigrant(signed=True) for i in range(3)}
    )


PAYLOADS = [
    ReadToServer("client-1", sample_txn(), "nonce-1"),
    ReadFromServer(
        TransactionResult(
            (
                OperationResult(b"v", sample_certificate(), True, Status.OK),
                OperationResult(None, None, False, Status.WRONG_SHARD),
            )
        ),
        "nonce-1",
        "rid-1",
    ),
    Write1ToServer("client-1", sample_txn(), 517, transaction_hash(sample_txn())),
    Write1OkFromServer(sample_multigrant(signed=True), {"k1": sample_certificate()}),
    Write1RefusedFromServer(sample_multigrant(), {"k1": sample_certificate()}, "client-1"),
    Write2ToServer(sample_certificate(), sample_txn()),
    Write2AnsFromServer(TransactionResult((OperationResult(b"v"),)), "rid-2"),
    RequestFailedFromServer(FailType.BAD_SIGNATURE, "forged"),
    HelloToServer("hi"),
    HelloFromServer("hi back"),
]


def test_envelope_roundtrip_all_payload_types():
    for payload in PAYLOADS:
        env = Envelope(
            payload,
            msg_id="msg-123",
            sender_id="client-1",
            reply_to="msg-122",
            timestamp_ms=1712345678901,
            signature=b"\x02" * 64,
        )
        decoded = decode_envelope(encode_envelope(env))
        assert decoded == env, type(payload).__name__


def test_transaction_hash_stable_and_distinct():
    t1, t2 = sample_txn(), sample_txn()
    assert transaction_hash(t1) == transaction_hash(t2)
    assert len(transaction_hash(t1)) == 64
    t3 = Transaction((Operation(Action.WRITE, "k1", b"DIFFERENT"),))
    assert transaction_hash(t1) != transaction_hash(t3)


def test_signing_bytes_exclude_signature():
    mg = sample_multigrant()
    assert mg.signing_bytes() == mg.with_signature(b"\x05" * 64).signing_bytes()
    env = Envelope(HelloToServer(), "m1", "s1")
    assert env.signing_bytes() == env.with_signature(b"\x06" * 64).signing_bytes()


def test_signing_bytes_cover_content():
    mg = sample_multigrant()
    mutated = MultiGrant(
        grants={**mg.grants, "k9": Grant("k9", 7, 1, b"\x00" * 64, Status.OK)},
        client_id=mg.client_id,
        server_id=mg.server_id,
    )
    assert mg.signing_bytes() != mutated.signing_bytes()


def test_six_bytes_splice_is_byte_identical():
    """The payload-level mcode cache (round 5) splices cached payload bytes
    between a freshly encoded tag and tail; the result must be byte-equal
    to encoding the whole 6-element list in one call, for EVERY payload
    type — this is what keeps fan-out envelopes (shared payload, distinct
    msg_id/MAC) wire-compatible with round-4 peers."""
    from mochi_tpu.protocol.messages import _TAG_BY_TYPE

    for payload in PAYLOADS:
        env = Envelope(payload, "msg-1", "sender-1", "reply-1", 1712345678901)
        reference = encode(
            [
                _TAG_BY_TYPE[type(payload)],
                payload.to_obj(),
                env.msg_id,
                env.sender_id,
                env.reply_to,
                env.timestamp_ms,
            ]
        )
        assert env._six_bytes == reference, type(payload).__name__
        # second envelope over the SAME payload object hits the cache and
        # must produce its own correct bytes (different msg_id)
        env2 = Envelope(payload, "msg-2", "sender-1", "reply-1", 1712345678901)
        assert "_mcode" in payload.__dict__
        reference2 = reference.replace(b"msg-1", b"msg-2")
        assert env2._six_bytes == reference2, type(payload).__name__
        decoded = decode_envelope(encode_envelope(env2))
        assert decoded.payload == payload, type(payload).__name__


# ---------------------------------------------------------------------------
# Certificates in replies TO a client are built on first read (PR 44,
# ``messages._Deferred``): the SDK tallies 43 (or 3) answers on their values
# and reads the certificate of the one it returns.

def _ncert(n: int) -> WriteCertificate:
    """n MultiGrants of one grant each: the shape a single-key YCSB record's
    certificate has (43 at n=64, 3 at rf=4)."""
    txh = b"\x07" * 64
    return WriteCertificate(
        {
            f"server-{i}": MultiGrant(
                {"user1": Grant("user1", 1042, 1, txh, Status.OK)},
                "client-abc", f"server-{i}", b"\x01" * 64,
            )
            for i in range(n)
        }
    )


# name -> (eagerly built payload, how to read its certificate field(s),
#          certificates it carries)
DEFERRED_REPLIES = {
    "ReadFromServer": (
        ReadFromServer(
            TransactionResult(
                (
                    OperationResult(b"v" * 100, _ncert(43), True, Status.OK),
                    OperationResult(None, None, False, Status.OK),
                )
            ),
            "nonce-1", "rid-1",
        ),
        lambda p: p.result.operations[0].current_certificate,
        1,
    ),
    "Write2AnsFromServer": (
        Write2AnsFromServer(
            TransactionResult(
                (
                    OperationResult(b"old", _ncert(3), True, Status.OK),  # a stale Write2's answer
                    OperationResult(b"new", None, False, Status.OK),
                )
            ),
            "rid-2",
        ),
        lambda p: p.result.operations[0].current_certificate,
        1,
    ),
    "Write1OkFromServer": (
        Write1OkFromServer(sample_multigrant(signed=True), {"k1": _ncert(3), "k3": _ncert(3)}),
        lambda p: p.current_certificates,
        2,
    ),
    "Write1RefusedFromServer": (
        Write1RefusedFromServer(sample_multigrant(), {"k1": _ncert(43)}, "client-1"),
        lambda p: p.current_certificates,
        1,
    ),
}


def _decoded(payload):
    return decode_envelope(encode_envelope(Envelope(payload, "m-1", "server-1", "m-0", 7))).payload


@pytest.mark.parametrize("name", DEFERRED_REPLIES)
def test_decoded_reply_equals_its_eager_twin(name):
    twin, read, carried = DEFERRED_REPLIES[name]
    got = _decoded(twin)
    assert certificates_deferred(got) == carried  # nothing built by the decode
    assert certificates_deferred(twin) == 0  # the constructor's side is as it was
    assert got == twin and twin == got
    assert "WriteCertificate(grants=" in repr(_decoded(twin))  # repr builds too
    assert type(read(got)) is type(read(twin))
    assert certificates_deferred(got) == 0


@pytest.mark.parametrize("name", DEFERRED_REPLIES)
def test_decoded_reply_reencodes_to_the_same_bytes_before_and_after_the_read(name):
    twin, read, carried = DEFERRED_REPLIES[name]
    wire = encode(twin.to_obj())
    before, after = _decoded(twin), _decoded(twin)
    assert encode(before.to_obj()) == wire
    read(after)
    assert encode(after.to_obj()) == wire
    # an envelope sent on as it came is a concatenation of cached bytes: no
    # certificate is built for it
    env = Envelope(twin, "m-1", "server-1", "m-0", 7, mac=b"\x03" * 32)
    again = decode_envelope(encode_envelope(env))
    assert encode_envelope(again) == encode_envelope(env)
    assert certificates_deferred(again.payload) == carried


@pytest.mark.parametrize("name", DEFERRED_REPLIES)
def test_decoded_reply_builds_its_certificate_once(name, monkeypatch):
    twin, read, carried = DEFERRED_REPLIES[name]
    built = []
    real = MultiGrant.from_obj.__func__
    monkeypatch.setattr(
        MultiGrant, "from_obj",
        classmethod(lambda cls, obj: built.append(1) or real(cls, obj)),
    )
    got = _decoded(twin)
    at_decode = len(built)  # a Write1 reply's own multi_grant, as ever
    assert at_decode == (1 if name.startswith("Write1") else 0)
    first = read(got)
    grants = len(built) - at_decode
    assert grants in (3, 6, 43)  # every MultiGrant of every certificate carried
    assert read(got) is first and got == twin and first == read(twin)
    assert len(built) - at_decode == grants


@pytest.mark.parametrize(
    "tree",
    [
        {"server-1": [1, 2]},  # a MultiGrant of the wrong arity
        {"server-1": [{"k": ["k", 1, 1, b"h", 99]}, "c", "server-1", None]},  # no such Status
        ["server-1"],  # not a map at all
        7,
    ],
)
def test_malformed_certificate_tree_raises_value_error_on_every_read(tree):
    """What an eager decode refused at the frame is refused at the read, and
    stays refused: the SDK builds the one it returns before handing it out."""
    good = ReadFromServer(
        TransactionResult((OperationResult(b"v", _ncert(3), True, Status.OK),)), "n", "r"
    )
    obj = good.to_obj()
    obj[0][0][1] = tree
    got = ReadFromServer.from_obj(decode(encode(obj)))
    op = got.result.operations[0]
    assert op.value == b"v" and op.existed  # the vote's fields are there
    for _ in range(2):
        with pytest.raises(ValueError, match="malformed current_certificate"):
            op.current_certificate
    assert certificates_deferred(got) == 1
    refused = Write1RefusedFromServer(sample_multigrant(), {"k1": _ncert(3)}, "c").to_obj()
    refused[1] = {"k1": tree}
    got = Write1RefusedFromServer.from_obj(decode(encode(refused)))
    assert got.multi_grant == sample_multigrant()  # the grant is built at decode, as ever
    with pytest.raises(ValueError, match="malformed current_certificates"):
        got.current_certificates


def test_requests_and_sync_entries_stay_eager():
    """A replica verifies what it receives: nothing it decodes is deferred."""
    bad = Write2ToServer(_ncert(3), sample_txn()).to_obj()
    bad[0] = {"server-1": [1, 2]}
    with pytest.raises((ValueError, TypeError)):
        Write2ToServer.from_obj(decode(encode(bad)))
    entry = SyncEntry("k1", sample_txn(), _ncert(3)).to_obj()
    entry[2] = {"server-1": [1, 2]}
    with pytest.raises((ValueError, TypeError)):
        SyncEntry.from_obj(decode(encode(entry)))
    w2 = _decoded(Write2ToServer(_ncert(3), sample_txn()))
    assert "write_certificate" in w2.__dict__ and certificates_deferred(w2) == 0
