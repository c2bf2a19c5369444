#!/usr/bin/env bash
# Start a single replica (analog of the reference's start_mochi.sh, which
# passed -DclusterConfig / -DclusterCurrentServer to the jar —
# start_mochi.sh:4-8, SURVEY.md §2.8).
#
# Usage: scripts/start_server.sh CONFIG SERVER_ID SEED_FILE [extra args...]
set -euo pipefail
REPO_DIR=$(cd "$(dirname "$0")/.." && pwd)
export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:$PYTHONPATH}"
CONFIG=$1; SERVER_ID=$2; SEED=$3; shift 3
exec python -m mochi_tpu.server \
  --config "$CONFIG" --server-id "$SERVER_ID" --seed-file "$SEED" "$@"
