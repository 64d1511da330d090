#!/usr/bin/env bash
# Launch the four-process control plane (docs/deployment.md) — the
# standalone analogue of the reference's installer/volcano-development.yaml
# (three Deployments + admission init against the Kubernetes API server).
#
#   ./examples/deployment/run-control-plane.sh [port] [nodes]
#
# Ctrl-C stops everything.
set -euo pipefail
PORT="${1:-8181}"
NODES="${2:-4}"
URL="http://127.0.0.1:${PORT}"
cd "$(dirname "$0")/../.."

# Only the scheduler imports JAX, so only it takes the accelerator; set
# JAX_PLATFORMS=cpu yourself to run it without one.

pids=()
cleanup() { kill "${pids[@]}" 2>/dev/null || true; }
trap cleanup EXIT INT TERM

python -m volcano_tpu.cmd.apiserver --port "$PORT" --default-queue \
    --nodes "$NODES" --node-resources cpu=16,memory=32Gi &
pids+=($!)
sleep 1

python -m volcano_tpu.cmd.webhook_manager --server "$URL" --port 0 &
pids+=($!)
python -m volcano_tpu.cmd.controller_manager --server "$URL" &
pids+=($!)
python -m volcano_tpu.cmd.scheduler --server "$URL" \
    --scheduler-conf examples/scheduler-conf.yaml &
pids+=($!)

echo "control plane up on ${URL}; submit work with:"
echo "  python -m volcano_tpu.cli.vcctl --server ${URL} job run -N demo -r 4 -m 4"
wait
