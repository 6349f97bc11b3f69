#!/bin/bash
# Two sets of runs of one cell on the same seeds, then traced runs; each
# run's output goes to <out>/<set>-<seed>.out and .err, one summary line
# per run to standard output, and bench/spread.py's summary last.
#
#   bash bench/sets.sh <cell> <out dir> <seeds,comma,separated> [traced seeds]
set -u
W=$1; OUT=$2; SEEDS=$3; TSEEDS=${4:-}
SECS=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p "$OUT"
one() {  # set seed trace
  local st=$(date +%s.%N)
  timeout 400 python3 bench/run.py --workload "$W" --seed "$2" \
    --seconds "$SECS" --trace "$3" > "$OUT/$1-$2.out" 2> "$OUT/$1-$2.err"
  local rc=$?
  echo "RUN set=$1 seed=$2 trace=$3 rc=$rc wall=$(python3 -c "import time; print(round(time.time()-$st,1))") $(tail -1 "$OUT/$1-$2.out" | cut -c1-3000)"
  grep "^\[window\]" "$OUT/$1-$2.err" | cut -c1-900
}
for set in A B; do
  for s in ${SEEDS//,/ }; do one $set "$s" 0; done
done
for s in ${TSEEDS//,/ }; do one T "$s" 1; done
python3 bench/spread.py "$OUT"/A-*.out "$OUT"/B-*.out
