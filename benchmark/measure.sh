#!/bin/sh
# Two sets of runs of one cell with the same seeds in both, then one traced
# run; every JSON line of every run is kept under chiprun_out/.  A builder's
# tool for setting bounds (PERF.md section 2), not part of a run.
#   sh benchmark/measure.sh <cell> <seconds> <runs-per-set> [trace-only]
cell=$1; secs=$2; n=$3; mkdir -p chiprun_out
seeds="101 2147483749 303 2147483999 505 606"
if [ "$4" != "trace-only" ]; then
  for set in A B; do
    i=0
    for s in $seeds; do
      i=$((i+1)); [ $i -gt $n ] && break
      python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 \
        > chiprun_out/_run.out 2> chiprun_out/_run.err
      echo "{\"set\": \"$set\", \"seed\": $s, \"rc\": $?}" >> chiprun_out/$cell.log
      grep '^{' chiprun_out/_run.out >> chiprun_out/$cell.log
      tail -n 1 chiprun_out/_run.out >> chiprun_out/$cell.e2e.jsonl
    done
  done
fi
python3 benchmark/run.py --workload $cell --seed 707 --seconds $secs --trace 1 \
  > chiprun_out/_run.out 2> chiprun_out/_run.err
echo "{\"set\": \"trace\", \"rc\": $?}" >> chiprun_out/$cell.log
grep '^{' chiprun_out/_run.out >> chiprun_out/$cell.log
tail -n 1 chiprun_out/_run.out > chiprun_out/$cell.trace.json
tail -n 5 chiprun_out/_run.err
tail -n 3 chiprun_out/$cell.e2e.jsonl | cut -c1-400
cut -c1-1500 chiprun_out/$cell.trace.json
