"""Median of the SDK's ``write1-phase`` stage timer over the window's updates:
the Write1 fan-out up to a timestamp-consistent quorum of grants."""

import statistics

NAME = "client.write1_p50_ms"
UNIT = "ms"
LAYER = "client SDK"
MOVES = "update_p95_ms"
SOURCE = "program_span"


def read(snap):
    samples = snap["generator"]["stage_seconds"].get("write1-phase")
    return statistics.median(samples) * 1e3 if samples else None
