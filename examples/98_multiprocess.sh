#!/usr/bin/env bash
# N server processes + round-robin client (reference examples/98: N processes
# sharing a V100 via CUDA MPS + envoy).  TPU note: chips are not MPS-shared —
# a chip belongs to ONE process, so each replica binds its own
# (TPU_VISIBLE_DEVICES) and N may not exceed the host's chips (CHIPS).
#
#   CHIPS=4 ./98_multiprocess.sh 2 resnet50
#   EXTRA_ARGS=--cpu ./98_multiprocess.sh 2        # no chip needed
set -euo pipefail
N=${1:-2}
MODEL=${2:-mnist}
BASE_PORT=${BASE_PORT:-51000}
EXTRA_ARGS=${EXTRA_ARGS:-}   # e.g. EXTRA_ARGS=--cpu for hermetic runs
CHIPS=${CHIPS:-1}            # chips on this host
PIDS=()

if [[ "$EXTRA_ARGS" != *--cpu* && "$N" -gt "$CHIPS" ]]; then
  echo "asked for $N chip-holding replicas with CHIPS=$CHIPS: a chip belongs" \
       "to one process and the extra replicas would hang. Set CHIPS=<chips" \
       "on this host> or EXTRA_ARGS=--cpu." >&2
  exit 2
fi

cleanup() { kill "${PIDS[@]}" 2>/dev/null || true; }
trap cleanup EXIT

for i in $(seq 0 $((N-1))); do
  PORT=$((BASE_PORT + i))
  TPU_VISIBLE_DEVICES=$i TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1 \
  TPU_PROCESS_BOUNDS=1,1,1 python "$(dirname "$0")/02_inference_service.py" \
      --model "$MODEL" --port "$PORT" --metrics-port $((9100 + i)) \
      $EXTRA_ARGS &
  PIDS+=($!)
  echo "replica $i on :$PORT (pid ${PIDS[-1]})"
done

echo "waiting for replicas..."
for i in $(seq 0 $((N-1))); do
  until python - <<EOF 2>/dev/null
from tpulab.rpc.infer_service import RemoteInferenceManager
RemoteInferenceManager("localhost:$((BASE_PORT + i))").get_models()
EOF
  do sleep 2; done
done

echo "driving synchronized load across $N replicas"
python - <<EOF
# Coordinated measurement (reference examples/00 infer.cc:85 MPI_Barrier):
# one closed-loop worker per replica, all released from a start-line
# barrier together, so the aggregate inf/s is a true simultaneous figure
# rather than a ragged-start mush.
import numpy as np, threading, time
from tpulab.rpc.infer_service import RemoteInferenceManager
N, PER = $N, 100
remotes = [RemoteInferenceManager(f"localhost:{$BASE_PORT + i}")
           for i in range(N)]
runners = [r.infer_runner("$MODEL") for r in remotes]
spec = remotes[0].get_models()["$MODEL"].inputs[0]
x = np.zeros((1, *spec.dims), np.dtype(spec.dtype))
for r in runners:
    r.infer(**{spec.name: x}).result(timeout=300)  # per-replica warmup
start_line = threading.Barrier(N + 1)
done, errors = [], []

def worker(runner):
    start_line.wait()  # MPI_Barrier analog
    t0 = time.perf_counter()
    try:
        for _ in range(PER):
            runner.infer(**{spec.name: x}).result(timeout=300)
    except Exception as e:  # a failed replica must fail the benchmark
        errors.append(e)
        return
    done.append(time.perf_counter() - t0)

threads = [threading.Thread(target=worker, args=(r,)) for r in runners]
[t.start() for t in threads]
start_line.wait()
t0 = time.perf_counter()
[t.join() for t in threads]
wall = time.perf_counter() - t0
if errors:
    raise SystemExit(f"{len(errors)}/{N} replicas failed: {errors[0]!r}")
print(f"{N * PER} requests over {N} replicas (synchronized start): "
      f"{N * PER / wall:.1f} inf/s aggregate; "
      f"slowest replica {max(done):.2f}s fastest {min(done):.2f}s")
EOF
