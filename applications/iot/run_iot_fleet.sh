#!/usr/bin/env bash
# 1 aggregation server (+ bundled MQTT broker) + 2 device clients on
# localhost — the reference's mobile/IoT paradigm, in-tree and runnable.
# Usage: run_iot_fleet.sh [broker_port]
set -euo pipefail
cd "$(dirname "$0")/../.."
export PYTHONPATH="$PWD" JAX_PLATFORMS=cpu
PORT="${1:-52883}"
BASE="--world_size 3 --backend mqtt --broker_port $PORT --serve_broker 1 \
  --dataset mnist --model lr --comm_round 2 --client_num_in_total 6 \
  --batch_size 8 --frequency_of_the_test 1 --ci 1 --job_id iot-demo"
# device processes (boot order is free; jax boot is ~60s/process on a
# small box — background them before the server)
python -m fedml_tpu.experiments.distributed_launch --rank 1 $BASE &
C1=$!
python -m fedml_tpu.experiments.distributed_launch --rank 2 $BASE &
C2=$!
# server (rank 0) hosts the broker, aggregates, prints the history JSON
python -m fedml_tpu.experiments.distributed_launch --rank 0 $BASE
wait $C1 $C2
echo "IoT fleet demo done"
