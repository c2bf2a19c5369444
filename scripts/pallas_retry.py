"""Bounded Pallas retry — one time-boxed attempt per block size; the outcome
is recorded either way (whether the kernel stays is ROADMAP C4).

History: Mosaic compiles of the verify kernel did not finish in 15 min at
block 128 or 256 (round 2).  This retry adds a smaller block (64 — fewer
unrolled table-build ops per program).

Each leg runs in a CHILD process under a hard subprocess timeout — a
Mosaic compile that never returns to the Python interpreter cannot be
bounded by an in-process SIGALRM; only killing the process can.  The
parent never imports JAX (a parent that had touched it would hold the chip
and every leg would fail or hang); the legs run one after another, so one
process at a time owns it.  The parent records compile seconds or
DID-NOT-FINISH to benchmarks/pallas_retry.json with a date either way.

Usage: python scripts/pallas_retry.py [budget_seconds_per_leg]
       python scripts/pallas_retry.py --leg <block>   (child mode)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leg(block: int) -> None:
    """Child: compile + run the kernel at one block size; print LEG_JSON."""
    import numpy as np

    import jax

    sys.path.insert(0, _REPO)
    from mochi_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs the chip (the Mosaic compile is the question)")
    from mochi_tpu.crypto import batch_verify, keys
    from mochi_tpu.crypto.pallas_verify import verify_prepared_pallas
    from mochi_tpu.verifier.spi import VerifyItem

    batch = 1024
    kp = keys.generate_keypair()
    items = [
        VerifyItem(kp.public_key, b"pr %d" % i, kp.sign(b"pr %d" % i))
        for i in range(batch)
    ]
    y_a, sign_a, y_r, sign_r, s_bits, h_bits, _pre = batch_verify.prepare(items)
    args = (y_a, sign_a, y_r, sign_r, s_bits, h_bits)

    leg: dict = {}
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        verify_prepared_pallas(*args, block=block, interpret=False)
    )
    leg["compile_plus_first_run_s"] = round(time.perf_counter() - t0, 1)
    leg["correct"] = bool(np.asarray(out).all())
    if leg["correct"]:
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(verify_prepared_pallas(*args, block=block, interpret=False))
            times.append(time.perf_counter() - t0)
        leg["sigs_per_sec"] = round(batch / min(times), 1)
    print("LEG_JSON " + json.dumps(leg), flush=True)


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--leg":
        _leg(int(sys.argv[2]))
        return
    budget = int(sys.argv[1]) if len(sys.argv) > 1 else 600

    out_path = os.path.join(_REPO, "benchmarks", "pallas_retry.json")
    record = {
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "budget_s_per_leg": budget,
        "legs": {},
    }

    for block in (64, 128):
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--leg", str(block)],
                cwd=_REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, errors="replace", timeout=budget,
            )
            line = next(
                (
                    l for l in proc.stdout.splitlines()
                    if l.startswith("LEG_JSON ")
                ),
                None,
            )
            if line is not None:
                record["legs"][str(block)] = json.loads(line[len("LEG_JSON "):])
            else:
                record["legs"][str(block)] = {
                    "error": f"rc={proc.returncode} tail={proc.stdout[-400:]}"
                }
        except subprocess.TimeoutExpired:
            record["legs"][str(block)] = {"did_not_finish_s": budget}
            # Round-2 evidence: Mosaic compile time grows with block size,
            # so if the SMALLER block blew the budget, don't spend another
            # budget on the bigger one.
            if block == 64:
                record["legs"]["128"] = {
                    "skipped": "block 64 did not finish; larger blocks "
                    "compile slower (round-2 evidence)"
                }
                break

    _append(out_path, record)
    print("PALLAS_RETRY_JSON " + json.dumps(record))
    if not any("compile_plus_first_run_s" in leg for leg in record["legs"].values()):
        sys.exit(1)  # no leg compiled (no chip, an error, or out of budget)


def _append(path: str, record: dict) -> None:
    try:
        with open(path) as fh:
            doc = json.load(fh)
        if not isinstance(doc, list):
            doc = [doc]
    except Exception:
        doc = []
    doc.append(record)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1)
    os.replace(tmp, path)


if __name__ == "__main__":
    main()
