"""A generator worker whose reads are broken underneath: each key's first
answer is kept and returned for ever after, as a stale cache in front of the
store would.  The reference has to see stale reads (``correct`` false)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import ycsb  # noqa: E402

_real_read = ycsb.sdk_read
_first_answer: dict = {}


async def stale_read(client, key):
    answer = await _real_read(client, key)
    return _first_answer.setdefault(key, answer)


ycsb.sdk_read = stale_read

if __name__ == "__main__":
    sys.exit(ycsb.worker_main())
