#!/usr/bin/env bash
# Long-running scenario soak (round 16, docs/OPERATIONS.md §4k).
#
# Drives the deterministic scenario engine over a wide seed range: each
# seed draws a full scenario (topology incl. durable-WAL posture, netsim
# mesh, ordered fault legs across all eight families, workload mix) and
# runs it on the seeded ExplorerLoop with the InvariantChecker sampling.
# Zero violations is the pass verdict; ANY failing seed is a complete
# reproduction:
#
#   python -m mochi_tpu.testing.scenario repro --seed N --minimize out.json
#
# Usage:
#   scripts/soak.sh [COUNT] [START] [WORKERS] [OUT]
#
#   COUNT    seeds to run             (default 1000)
#   START    first seed               (default 0; shift per battery so
#                                      successive soaks cover fresh draws)
#   WORKERS  parallel worker procs    (default: cores, capped at 4)
#   OUT      where the summary JSON goes
#            (default soak_<START>_<COUNT>.json in the directory the
#            script was called from)

set -euo pipefail
CALLED_FROM="$PWD"
cd "$(dirname "$0")/.."

COUNT="${1:-1000}"
START="${2:-0}"
CORES="$(nproc 2>/dev/null || echo 2)"
WORKERS="${3:-$(( CORES < 4 ? CORES : 4 ))}"
OUT="${4:-${CALLED_FROM}/soak_${START}_${COUNT}.json}"
case "${OUT}" in /*) ;; *) OUT="${CALLED_FROM}/${OUT}" ;; esac

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "scenario soak: seeds ${START}..$(( START + COUNT - 1 )), ${WORKERS} workers -> ${OUT}" >&2
exec python -m mochi_tpu.testing.scenario soak \
    --count "${COUNT}" --start "${START}" --workers "${WORKERS}" \
    --out "${OUT}"
