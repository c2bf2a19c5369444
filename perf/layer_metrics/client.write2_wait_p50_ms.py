"""Median of the SDK's ``write2-fanout-wait`` stage timer: Write2 sent to the
whole replica set up to the quorum of commit answers.  It contains each
replica's certificate check (the verifier round trip) and its store apply."""

import statistics

NAME = "client.write2_wait_p50_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "update_p95_ms"
SOURCE = "program_span"


def read(snap):
    samples = snap["generator"]["stage_seconds"].get("write2-fanout-wait")
    return statistics.median(samples) * 1e3 if samples else None
