"""The control's restart: before it, one byte of one grant's signature is
flipped in each of the last ``FORGED`` commits of the killed replica's log, and
each frame's CRC is made right again, as someone who can edit the disk would.
A replay that verifies every certificate convicts each of them and says so in
``storage.replay``; one that trusts its own log adopts them and reads 0, so
``replay_entries_convicted``, and ``correct``, have to fail."""

import os

import schedule
from mochi_tpu.protocol import WriteCertificate
from mochi_tpu.storage import wal

_REAL = schedule.load_verb(
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "faults"),
    "restart_replica")
RESTARTS = True
END_TO_END = _REAL.END_TO_END
FORGED = 3


def forge(directory: str, server_id: str) -> int:
    """Rewrite the newest segment that holds a commit; how many were forged."""
    for _, path in reversed(wal.list_segments(directory)):
        with open(path, "rb") as fh:
            data = fh.read()
        scan = wal.scan_segment(data, server_id)
        targets = [r for r in scan.records if r.rtype == wal.RT_COMMIT][-FORGED:]
        if not targets:
            continue
        ends = [r.offset for r in scan.records[1:]] + [scan.valid_bytes]
        out = bytearray(data[:scan.records[0].offset])
        for rec, end in zip(scan.records, ends):
            if not any(rec is t for t in targets):
                out += data[rec.offset:end]
                continue
            keys, txn_obj, cert_obj = rec.body
            grants = dict(WriteCertificate.from_obj(cert_obj).grants)
            sid, grant = sorted(grants.items())[0]
            signature = bytearray(grant.signature)
            signature[0] ^= 1
            grants[sid] = grant.with_signature(bytes(signature))
            out += wal.encode_record(rec.seq, rec.rtype, [keys, txn_obj, WriteCertificate(grants).to_obj()])
        with open(path, "wb") as fh:
            fh.write(out)
        return len(targets)
    return 0


async def run(pc, event, state):
    forged = forge(os.path.join(pc.storage_root, event["server_id"]), event["server_id"])
    assert forged, "no commit in the killed replica's log to forge"
    return dict(await _REAL.run(pc, event, state), forged=forged)
