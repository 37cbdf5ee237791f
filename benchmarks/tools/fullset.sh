#!/bin/bash
# usage: fullset.sh <cell> <outfile>
cell=$1; out=chiprun_out/$2; mkdir -p chiprun_out; : > $out
seeds="2147483659 2147483693 2147483713 2147483743 2147483777 2147483783"
for set in 1 2; do for s in $seeds; do
  echo "{\"run\": \"set$set\", \"seed\": $s}" >> $out
  timeout 400 python3 benchmarks/run.py --workload $cell --seed $s --seconds 20 --trace 0 2>>chiprun_out/$2.err | grep '^{' >> $out; echo "rc=${PIPESTATUS[0]}" >> $out
done; done
for s in 3000000019 3000000037 3000000043; do
  echo "{\"run\": \"trace\", \"seed\": $s}" >> $out
  timeout 400 python3 benchmarks/run.py --workload $cell --seed $s --seconds 20 --trace 1 2>>chiprun_out/$2.err | grep '^{' >> $out; echo "rc=${PIPESTATUS[0]}" >> $out
done
grep -c '"correct": true' $out
