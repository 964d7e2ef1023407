#!/bin/sh
# Run a list of benchmark runs one after another on the machine this is
# started on (one chiprun call), keeping each run's output.
#   sh benchmarks/tools/chip_batch.sh <label> "<args of run 1>" "@VAR=value <args of run 2>" ...
# A leading @VAR=value sets one environment variable for that run alone.
# Each run's stdout+stderr goes to chiprun_out/batch/<label>.<n>.log; the
# tail of each (the checks and the result line) is echoed.
label=$1; shift
mkdir -p chiprun_out/batch
n=0
for a in "$@"; do
  n=$((n+1))
  log=chiprun_out/batch/$label.$n.log
  start=$(date +%s)
  setenv=""
  case "$a" in
    @*) setenv=${a%% *}; setenv=${setenv#@}; a=${a#* } ;;
  esac
  # shellcheck disable=SC2086
  env $setenv python3 benchmarks/run.py $a > "$log" 2>&1
  rc=$?
  echo "== run $n rc=$rc wall=$(( $(date +%s) - start ))s: $setenv $a"
  grep -v "^E0\|UserWarning\|warnings.warn" "$log" | tail -n "${BATCH_TAIL:-3}" | cut -c1-2500
done
