#!/usr/bin/env bash
# Two sets of six runs of one cell, the same six seeds in both sets, all in
# one call (one machine, one compile cache): what the bounds in
# BENCHMARK.json were set from. Prints each run's result line and, last,
# each metric's spread per set (IQR / median, statistics.quantiles n=4).
#   chiprun [--chips 4] --timeout 3000 -- bash benchmark/tools/run_sets.sh <cell> <seconds> <first-seed> <out-dir>
set -u
cell=$1; seconds=$2; first=$3; out=$4
mkdir -p "$out"
for set in A B; do
  for i in 1 2 3 4 5 6; do
    seed=$((first + i * 104729))
    log="$out/${cell}_${set}${i}.log"
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 > "$log" 2>&1
    echo "rc=$? set=$set seed=$seed $(tail -n 1 "$log")"
  done
done | tee "$out/${cell}_sets.txt"
python3 - "$out/${cell}_sets.txt" <<'PY'
import json, statistics, sys
sets = {"A": {}, "B": {}}
for line in open(sys.argv[1]):
    head, _, tail = line.partition(" {")
    fields = dict(f.split("=") for f in head.split())
    if fields["rc"] != "0":
        print("FAILED RUN:", line.strip()[:300]); continue
    res = json.loads("{" + tail)
    assert res["correct"] and res["failed"] == 0, line
    for k, v in res["metrics"].items():
        sets[fields["set"]].setdefault(k, []).append(v["value"])
for s, metrics in sets.items():
    for k, vals in metrics.items():
        q1, _, q3 = statistics.quantiles(vals, n=4)
        med = statistics.median(vals)
        print(f"spread set={s} metric={k} n={len(vals)} median={med!r} iqr_share={(q3 - q1) / med:.5f} min={min(vals)!r} max={max(vals)!r}")
PY
