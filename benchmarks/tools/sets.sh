#!/bin/bash
# Two sets of six runs of one cell with the same seeds, then one traced run:
# what a bound is set from.  Usage (on the chip): sets.sh <workload> [seconds [runs-a-set]]
# Result lines go to chiprun_out/sets_<workload>.jsonl, logs beside them.
set -u
cell=$1
seconds=${2:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
mkdir -p chiprun_out
seeds=$(echo 5001 5002 5003 2147488004 2147488005 5006 | cut -d" " -f1-${3:-6})
out=chiprun_out/sets_$cell
for set in 1 2; do
  for seed in $seeds; do
    python3 benchmarks/run.py --workload "$cell" --seed $seed --seconds "$seconds" --trace 0 > $out.last 2>&1
    rc=$?
    grep -E "^bench:" $out.last | sed "s/^/[set $set seed $seed] /" >> $out.log
    if [ $rc -ne 0 ]; then echo "[set $set seed $seed] rc=$rc" >> $out.log; tail -n 30 $out.last >> $out.log; fi
    tail -n 1 $out.last >> $out.jsonl
  done
done
python3 benchmarks/run.py --workload "$cell" --seed 5007 --seconds "$seconds" --trace 1 > $out.last 2>&1
echo "traced rc=$?" >> $out.log
grep -E "^bench:" $out.last | sed "s/^/[traced] /" >> $out.log
tail -n 1 $out.last > $out.traced.json
rm -f $out.last
python3 benchmarks/tools/spread.py $out.jsonl
