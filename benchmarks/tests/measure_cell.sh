#!/bin/bash
# Run by hand on the chip: the measurements a benchmark PR reports for one
# cell, the runs of the cell in one call.
#   chiprun -- bash benchmarks/tests/measure_cell.sh <workload> <first seed> [seconds]
# Two sets of 6 untraced runs on the same 6 seeds, 3 traced runs and 3 runs
# of the control on other seeds.  Each set starts with a compile cache of
# its own, as each side of the driver's check does: its first run compiles.
# Every result line goes to chiprun_out/<workload>.jsonl with the kind of
# run and the seed in front.
w=$1; s0=$2; secs=${3:-20}
mkdir -p chiprun_out; out=chiprun_out/$w.jsonl; : > $out
one() {  # kind seed trace
  line=$(python3 benchmarks/run.py --workload $w --seed $2 --seconds $secs --trace $3 2>chiprun_out/$w.last_err | tail -n 1)
  echo "{\"kind\": \"$1\", \"seed\": $2, \"rc\": $?, \"line\": ${line:-null}}" >> $out
  echo "$1 $2: $(echo "$line" | cut -c1-420)"
}
for set in set1 set2; do
  export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache/$w.$set
  for i in 0 1 2 3 4 5; do one $set $((s0 + i)) 0; done
done
for i in 10 11 12; do one traced $((s0 + i)) 1; done
for i in 20 21 22; do
  line=$(python3 benchmarks/tests/chip_control.py --workload $w --seed $((s0 + i)) --seconds 5 2>>chiprun_out/$w.last_err | tail -n 1)
  echo "{\"kind\": \"control\", \"seed\": $((s0 + i)), \"line\": ${line:-null}}" >> $out
  echo "control $((s0 + i)): $(echo "$line" | cut -c1-900)"
done
