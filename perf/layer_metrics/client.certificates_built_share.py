"""Certificates the SDK built into objects over certificates its replies
carried (``client.certificates-built`` over ``client.certificates-received``,
gained over the window, summed over the callers), in percent: since PR 44 a
tally builds the certificate of the one answer it returns, so the share is
about one over the quorum where every answer carries one (~2.3 at n=64, ~33
at rf=4).  A run whose SDK counts neither gives nothing."""

NAME = "client.certificates_built_share"
UNIT = "%"
LAYER = "client SDK"
MOVES = "ops_s"
SOURCE = "program_counter"


def read(snap):
    gained = (snap["generator"].get("sdk_counters") or {}).get("sum") or {}
    received = gained.get("client.certificates-received", 0)
    return 100.0 * gained.get("client.certificates-built", 0) / received if received else None
