#!/usr/bin/env bash
# Localhost multi-process FedAvg over gRPC — the reference's
# run_fedavg_distributed_pytorch.sh (mpirun -np N+1 on one box) analogue.
#
# Usage: run_fedavg_distributed.sh [CLIENT_NUM] [ROUNDS] [DATASET] [MODEL]
set -euo pipefail
CLIENTS=${1:-4}
ROUNDS=${2:-5}
DATASET=${3:-mnist}
MODEL=${4:-lr}
WORLD=$((CLIENTS + 1))
PORT=${BASE_PORT:-50000}

# one process per chip: the server rank keeps the accelerator this box has;
# the client ranks run on the CPU (they would otherwise fight over the chip)
pids=()
for rank in $(seq 1 "$CLIENTS"); do
  JAX_PLATFORMS=cpu python -m fedml_tpu.experiments.distributed_launch \
    --rank "$rank" --world_size "$WORLD" --backend grpc --base_port "$PORT" \
    --dataset "$DATASET" --model "$MODEL" --comm_round "$ROUNDS" &
  pids+=($!)
done

python -m fedml_tpu.experiments.distributed_launch \
  --rank 0 --world_size "$WORLD" --backend grpc --base_port "$PORT" \
  --dataset "$DATASET" --model "$MODEL" --comm_round "$ROUNDS"

for p in "${pids[@]}"; do wait "$p"; done
