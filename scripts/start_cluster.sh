#!/usr/bin/env bash
# Launch a local mochi-tpu cluster (ops analog of the reference's
# start_mochi.sh / start_mochi_docker.sh — SURVEY.md §2.8).
#
# Usage: scripts/start_cluster.sh [N_SERVERS] [RF] [BASE_PORT] [OUT_DIR]
set -euo pipefail

N=${1:-5}
RF=${2:-4}
BASE_PORT=${3:-8101}
OUT=${4:-./cluster}
REPO_DIR=$(cd "$(dirname "$0")/.." && pwd)

export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:$PYTHONPATH}"

VERIFIER="${MOCHI_VERIFIER:-cpu}"
if [ "$VERIFIER" = "tpu" ] && [ "$N" -gt 1 ]; then
  echo "MOCHI_VERIFIER=tpu starts $N processes that each claim the chip;" \
       "a chip has one owner — use MOCHI_VERIFIER=remote" >&2
  exit 2
fi

if [ ! -f "$OUT/cluster_config.json" ]; then
  python -m mochi_tpu.tools.gen_cluster \
    --out-dir "$OUT" --servers "$N" --rf "$RF" --base-port "$BASE_PORT"
fi

mkdir -p "$OUT/log"
PIDS=()

# MOCHI_VERIFIER=remote -> boot ONE TPU-owning verifier service and point
# every replica at it (a chip has a single owner process; this is the only
# way a multi-process cluster gets TPU-backed verification).  Other values
# (cpu | remote:<host>:<port>) pass through per replica; "tpu" would make
# every replica process an owner of the one chip, so it is refused for
# more than one process.
SECRET_ARGS=()
if [ "$VERIFIER" = "remote" ]; then
  VPORT=$((BASE_PORT + 2000))
  # Shared secret authenticating the verify RPC both ways (the responses
  # are verdicts; see verifier/service.py trust model).
  if [ ! -f "$OUT/verifier.secret" ]; then
    (umask 077 && python -c "import os; print(os.urandom(32).hex())" > "$OUT/verifier.secret")
  fi
  chmod 600 "$OUT/verifier.secret"
  # Known-signer registration: cert traffic is signed by the replica
  # identities in the cluster config, so hand them to the service's comb
  # registry (crypto/comb.py — the doubling-free device fast path).
  python - "$OUT" <<'PYEOF'
import json, sys
doc = json.load(open(f"{sys.argv[1]}/cluster_config.json"))
with open(f"{sys.argv[1]}/signers.txt", "w") as f:
    for sid, hexkey in sorted(doc.get("public_keys", {}).items()):
        f.write(f"{hexkey}  # {sid}\n")
PYEOF
  python -m mochi_tpu.verifier.service --port "$VPORT" \
    --backend "${MOCHI_VERIFIER_BACKEND:-tpu}" \
    --secret-file "$OUT/verifier.secret" \
    --signers-file "$OUT/signers.txt" \
    --admin-port $((VPORT + 1)) \
    >"$OUT/log/verifier.log" 2>&1 &
  VPID=$!
  PIDS+=("$VPID")
  # No replica starts before the chip's owner is READY (a cold boot compiles
  # both programs; later boots load them from the compile cache), and none
  # starts at all if it died or never got there.
  for _ in $(seq 1 900); do
    grep -q READY "$OUT/log/verifier.log" 2>/dev/null && break
    kill -0 "$VPID" 2>/dev/null || break
    sleep 1
  done
  if ! grep -q READY "$OUT/log/verifier.log" 2>/dev/null; then
    echo "verifier service is not READY; see $OUT/log/verifier.log:" >&2
    tail -n 5 "$OUT/log/verifier.log" >&2 || true
    kill "$VPID" 2>/dev/null || true
    exit 1
  fi
  VERIFIER="remote:127.0.0.1:$VPORT"
  SECRET_ARGS=(--verifier-secret-file "$OUT/verifier.secret")
fi

for i in $(seq 0 $((N - 1))); do
  python -m mochi_tpu.server \
    --config "$OUT/cluster_config.json" \
    --server-id "server-$i" \
    --seed-file "$OUT/server-$i.seed" \
    --admin-port $((BASE_PORT + 1000 + i)) \
    --verifier "$VERIFIER" \
    ${SECRET_ARGS[@]+"${SECRET_ARGS[@]}"} \
    >"$OUT/log/server-$i.log" 2>&1 &
  PIDS+=($!)
done

trap 'kill "${PIDS[@]}" 2>/dev/null || true' INT TERM
echo "cluster of $N replicas starting (rf=$RF); logs in $OUT/log/"
echo "stop with: kill ${PIDS[*]}"
wait
