#!/bin/bash
# The measurement a benchmark PR makes for a bound: set A, one traced run,
# set B, with the same seeds in both sets, all in one chip call.
#   chiprun --timeout 3590 -- benchmark/two_sets.sh <cell> <out dir under chiprun_out> <seconds> <traced seed> <seed>...
# Every run's full output is kept in the out dir; its result line, its
# warm-up and check lines are echoed, and all window lines at the end.
cell=$1; out=$2; secs=$3; tseed=$4; shift 4
mkdir -p "$out"
one() { # <set> <seed> <trace>
  t0=$(date +%s)
  python3 -m benchmark.run --workload "$cell" --seed "$2" --seconds "$secs" --trace "$3" \
    > "$out/$1_$2.out" 2> "$out/$1_$2.err"
  echo "rc=$? set=$1 seed=$2 trace=$3 wall=$(( $(date +%s) - t0 ))s $(tail -n 1 "$out/$1_$2.out" | cut -c1-900)"
  grep '"stage": "check"\|"stage": "warmup"' "$out/$1_$2.out" | cut -c1-420
}
[ "${SETS:-AB}" != "B" ] && for s in "$@"; do one A "$s" 0; done
[ "$tseed" != "-" ] && one T "$tseed" 1
[ "${SETS:-AB}" != "A" ] && for s in "$@"; do one B "$s" 0; done
grep -h '"stage": "window"' "$out"/*.out | cut -c1-700
