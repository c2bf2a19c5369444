"""Certificate grants a verify round trip of the replay: the entries replayed
times the quorum of grants a certificate carries, over the round trips of the
fresh process's own verifier chain at READY (``remote_batches``: only its
replay has used it).  ``REPLAY_CHUNK`` x quorum where every chunk is full."""

import schedule

NAME = "recovery.items_per_rpc"
UNIT = "items"
LAYER = "verifier SPI and service queue"
MOVES = "recover_s"
SOURCE = "program_counter"


def read(snap):
    back = schedule.restarted(snap.get("faults"))
    if not back:
        return None
    entries = rpcs = 0
    for r in back:
        chain = r["after"]["replica"]["verifier"]
        while chain and "remote_batches" not in chain:
            chain = chain.get("inner")
        if not chain:
            return None
        rpcs += chain["remote_batches"]
        entries += r["after"]["replica"]["storage"].get("replay", {}).get("entries", 0)
    return entries * snap["cluster"]["quorum"] / rpcs if rpcs else None
