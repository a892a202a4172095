#!/bin/bash
# What the builder ran on the chip, from the root of a checkout on the
# machine this is started on (through the chip tool):
#
#   chip_runs.sh <workload> <budget seconds> <seed>:<seconds>:<trace>[:<cal>] ...
#
# Runs the cell once per spec, in order, and keeps what each run printed.
# <cal> is "control" (after the run, tests/calibrate.py on the rows that
# run's first task was fed: the control's first step), "control8" (its
# whole task) or "faults" (whole task, one row left out, Adam's b1 0.5). Stops at
# the first run that prints no result, and starts no run once <budget
# seconds> have passed. Result lines go to
# chiprun_out/<workload>.runs.jsonl, calibrate's to
# chiprun_out/<workload>.calibrate.jsonl, each run's earlier lines to
# chiprun_out/<workload>.<seed>.<trace>.out, the last run's logs beside.
workload=$1; budget=$2; shift 2
began=$(date +%s)
mkdir -p chiprun_out
files=$(python3 - "$workload" <<'PY'
import json, sys
m = json.load(open("BENCHMARK.json"))
cell = {w["name"]: w for w in m["workloads"]}[sys.argv[1]]
config = {c["name"]: c for c in m["configs"]}[cell["config"]]["file"]
print(config, config.replace("/configs/", "/traffic/").rsplit("/", 1)[0]
      + "/" + cell["traffic"] + ".json")
PY
)
for spec in "$@"; do
  IFS=: read -r seed seconds trace cal <<< "$spec"
  if [ $(( $(date +%s) - began )) -gt "$budget" ]; then
    echo "== budget of ${budget}s passed: $spec and later not started"; break
  fi
  started=$(date +%s.%N)
  out=chiprun_out/$workload.$seed.$trace.out
  python3 benchmark/run.py --workload "$workload" --seed "$seed" \
      --seconds "$seconds" --trace "$trace" > "$out" 2> "$out.err"
  code=$?
  wall=$(python3 -c "import time; print(round(time.time() - $started, 1))")
  line=$(tail -n 1 "$out" | grep '^{' || echo null)
  echo "{\"workload\": \"$workload\", \"seed\": $seed, \"seconds\": $seconds, \"trace\": $trace, \"exit\": $code, \"wall_s\": $wall, \"result\": $line}" \
      >> chiprun_out/$workload.runs.jsonl
  echo "== $workload seed $seed seconds $seconds trace $trace exit $code wall ${wall}s"
  grep -v '^{' "$out" | tail -n 16 | cut -c1-1200
  echo "$line" | cut -c1-1500
  tail -n 5 "$out.err" | cut -c1-600
  work=.bench_work/$workload
  if [ "$line" = null ]; then
    tail -c 6000 "$work/check.log" 2>/dev/null
    break
  fi
  if [ -n "$cal" ]; then
    loss=$(python3 -c "import json; print(json.load(open('$work/check.out'))['reference_task_losses'][0])")
    case $cal in
      control) more="control_steps=1";;
      control8) more="";;
      faults) more="faults=one_row_left_out,adam_b1_0.5";;
    esac
    calbegan=$(date +%s)
    python3 benchmark/tests/calibrate.py $files "$seed" "feed=$work/feed.jsonl" \
        "task_loss=$loss" $more 2> chiprun_out/$workload.$seed.calibrate.err \
        | tail -n 1 | tee -a chiprun_out/$workload.calibrate.jsonl | cut -c1-1200
    echo "== calibrate $cal seed $seed: $(( $(date +%s) - calbegan ))s"
    tail -n 3 chiprun_out/$workload.$seed.calibrate.err | cut -c1-400
  fi
done
for f in .bench_work/$workload/*.log; do
  [ -f "$f" ] && grep -v "jax._src" "$f" | tail -c 200000 \
      > chiprun_out/$workload.$(basename "$f")
done
exit 0
