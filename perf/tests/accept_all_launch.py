"""The control's service: the product's verifier service with the verdicts
thrown away — every signature "valid".  A deployment that ran this would
accept a certificate with a forged grant; the benchmark's bad-Write2 probe has
to catch it (``correct`` false).  Used by ``control.py`` and the tests only."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import service_launch  # noqa: E402
from mochi_tpu.verifier import service, spi  # noqa: E402


class AcceptAll(spi.CachingVerifier):
    async def verify_batch(self, items):
        return [True] * len(items)


service.CachingVerifier = AcceptAll

if __name__ == "__main__":
    service_launch.main()
