#!/bin/sh
# All runs of one cell in ONE chip call, sharing the compile cache
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).
#
#   chiprun --chips N -- sh benchmark/prove.sh <cell> <runs> <seed> [<seed> ...]
#
# Run i takes the i-th seed (cycling).  The last line of every run is
# collected into chiprun_out/prove/<cell>.<tag>.jsonl, the full output of
# each run beside it; the first run's setup_s (it compiles) is reported apart.
# Environment: BENCH_SECONDS (default: BENCHMARK.json run_seconds),
# BENCH_TRACE (0), BENCH_TAG (run), BENCH_EXTRA (further flags of run.py).
set -u
cell=$1; runs=$2; shift 2
cd "$(dirname "$0")/.."
seconds=${BENCH_SECONDS:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
trace=${BENCH_TRACE:-0}; tag=${BENCH_TAG:-run}
out=chiprun_out/prove; mkdir -p "$out"
lines="$out/$cell.$tag.jsonl"; : > "$lines"
i=0; rc_all=0
while [ "$i" -lt "$runs" ]; do
  n=$(( i % $# + 1 )); eval "seed=\${$n}"
  log="$out/$cell.$tag.$i.log"
  python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" \
    --trace "$trace" ${BENCH_EXTRA:-} > "$log" 2> "$log.err"
  rc=$?
  [ "$rc" -ne 0 ] && { rc_all=$rc; tail -n 20 "$log.err"; }
  grep '"bench": "check"' "$log" | grep -v '"ok": true'
  last=$(tail -n 1 "$log")
  echo "{\"run\": $i, \"seed\": $seed, \"rc\": $rc, \"first_run\": $([ "$i" -eq 0 ] && echo true || echo false), \"line\": ${last:-null}}" >> "$lines"
  i=$(( i + 1 ))
done
cat "$lines"
python3 benchmark/harness/spread.py "$lines"
exit $rc_all
