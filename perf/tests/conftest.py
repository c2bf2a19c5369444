import os
import sys

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
for p in (REPO, PERF):
    if p not in sys.path:
        sys.path.insert(0, p)
