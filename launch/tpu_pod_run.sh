#!/usr/bin/env bash
# Launch training on every worker of a Cloud TPU pod slice.
#
# The TPU-native analogue of the reference's Slurm launcher
# (/root/reference/mingpt/slurm/slurm_run.sh): where that script resolves the
# head-node IP and has torchrun fork one process per GPU with a c10d
# rendezvous on port 29500, a TPU pod slice runs ONE identical process per
# worker host. train.py joins a multi-host job only through the explicit
# env contract parallel/distributed.py reads (COORDINATOR_ADDRESS /
# NUM_PROCESSES / PROCESS_ID) — it never autodetects, because a one-host
# TPU VM looks like a pod worker to the autodetection and must start
# without touching the network. So this launcher resolves the three values
# (worker 0's address and the worker count here, the worker's own index on
# the worker) and runs the same command everywhere with
# `gcloud ... ssh --worker=all`.
#
# Usage:
#   ./launch/tpu_pod_run.sh <tpu-name> <zone> [train.py args...]
# Example:
#   ./launch/tpu_pod_run.sh mingpt-v4-32 us-central2-b \
#       trainer_config.max_epochs=10 data_config.path=gs://bucket/corpus.txt
#
# Pre-flight (optional but recommended — the mpi_hello_world step of the
# reference runbook): build and run the native PJRT smoke test on each worker
# first:
#   gcloud compute tpus tpu-vm ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
#     --command "cd ~/mingpt_distributed_tpu/runtime && make && \
#                PJRT_PLUGIN_PATH=/lib/libtpu.so ./pjrt_smoke"

set -euo pipefail

TPU_NAME="${1:?usage: tpu_pod_run.sh <tpu-name> <zone> [train args...]}"
ZONE="${2:?usage: tpu_pod_run.sh <tpu-name> <zone> [train args...]}"
shift 2

REPO_DIR="${REPO_DIR:-\$HOME/mingpt_distributed_tpu}"
LOGLEVEL="${LOGLEVEL:-INFO}"   # reference parity: slurm_run.sh:15

COORDINATOR_PORT="${COORDINATOR_PORT:-8476}"
ENDPOINTS="$(gcloud compute tpus tpu-vm describe "$TPU_NAME" --zone "$ZONE" \
  --format='value(networkEndpoints[].ipAddress)')"   # ';'-separated, worker order
COORDINATOR_IP="${ENDPOINTS%%;*}"
NUM_PROCESSES="$(tr ';' '\n' <<<"$ENDPOINTS" | grep -c .)"
# each worker reads its own index from the TPU VM metadata server
WORKER_ID_CMD="curl -sf -H Metadata-Flavor:Google http://metadata.google.internal/computeMetadata/v1/instance/attributes/agent-worker-number"

gcloud compute tpus tpu-vm ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
  --command "cd $REPO_DIR && COORDINATOR_ADDRESS=$COORDINATOR_IP:$COORDINATOR_PORT \
NUM_PROCESSES=$NUM_PROCESSES PROCESS_ID=\$($WORKER_ID_CMD) \
LOGLEVEL=$LOGLEVEL python train.py $*"
