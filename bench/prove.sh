# The measurements a benchmark change owes for one open-loop cell, in one
# process tree on a machine with the cell's chips, from the root of a
# checkout:
#
#     bash bench/prove.sh <cell> "<rates to sweep>" [budget_seconds]
#
# In order, each step skipped once the budget runs short: one traced run and
# a cut of its trace; a knee sweep that stops at the first rate that fails,
# writing 0.8 of the knee to bench/rates/<cell>.json; program and control
# readings for the limits (bench/calibrate.py, a dozen seeds, three of them
# with the control); two sets of six runs on the same seeds, for the bounds;
# three traced runs.  Outputs go under bench_out/prove/<cell>/, traces
# under bench_out/.
T0=$(date +%s)
W=$1
RATES=$2
BUDGET=${3:-3300}
OUT=bench_out/prove/$W
TRACES=bench_out/$W
SECONDS_RUN=$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')
mkdir -p "$OUT" "$TRACES"
left() { echo $(( BUDGET - ($(date +%s) - T0) )); }
guard() { [ "$(left)" -gt "$1" ] || { echo "-- skipped $2: $(left) s left"; return 1; }; }
run() {
  tag=$1; shift
  timeout 600 python3 bench/run.py "$@" > "$OUT/$tag.out" 2> "$OUT/$tag.err"
  echo "== $tag rc=$? t=$(( $(date +%s) - T0 ))"
  grep -E "^(set-up|session|stamps|end-to-end)" "$OUT/$tag.out" | cut -c1-900
  tail -1 "$OUT/$tag.out" | cut -c1-2500
}
# knee <rates...>: the highest rate, in order, before the first whose window
# ends with a backlog (over max(2, 5%) unserved, or a last-third median TTFT
# over 1.5x + 0.2 s of the first third's)
knee() { python3 - "$OUT" "$W" "$@" <<'EOF'
import json, sys
out, cell, rates = sys.argv[1], sys.argv[2], sys.argv[3:]
rows, knee = [], None
for r in rates:
    lines = open(f"{out}/S_{r}.out").read().splitlines()
    st = json.loads([l for l in lines if l.startswith("stamps: ")][-1][8:])
    ok = (st["unserved_at_window_end"] <= max(2, 0.05 * st["requests_due_in_window"])
          and st["ttft_p50_last_third_s"] <= 1.5 * st["ttft_p50_first_third_s"] + 0.2)
    rows.append([float(r), ok, st])
    if not ok:
        break
    knee = float(r)
rate = round(0.8 * (knee or 0.75 * float(rates[0])), 2)
print(json.dumps({"cell": cell, "knee_rps": knee, "rate_rps": rate, "rows": rows}))
json.dump({"rate_rps": rate}, open(f"bench/rates/{cell}.json", "w"))
EOF
}

run A --workload "$W" --seed 3000001001 --seconds 10 --trace 1 --trace-dir "$TRACES/A"
X=$(find "$TRACES/A" -name '*.xplane.pb' | head -1)
[ -n "$X" ] && timeout 300 python3 bench/trace.py "$X" --cut "$OUT/trace_cut.json" --cut-seconds 0.6 \
  > "$OUT/trace_summary.txt" 2>&1
head -50 "$OUT/trace_summary.txt" 2>/dev/null | cut -c1-160
tail -1 "$OUT/A.out" | grep -q '"correct"' || { tail -40 "$OUT/A.err" | cut -c1-400; exit 1; }

swept=""
for r in $RATES; do
  guard 2400 "sweep $r" || break
  run S_$r --workload "$W" --rate "$r" --seed 3000002001 --seconds 15 --trace 0
  swept="$swept $r"
  knee $swept | tee "$OUT/knee.json" | grep -q '"knee_rps": '"$r," || break
done
cat "bench/rates/$W.json"; echo

guard 1900 "calibrate" && timeout 1200 python3 bench/calibrate.py --workload "$W" \
  --seeds $(seq 3000003001 3000003012) --control-seeds 3 --seconds 10 --out "$OUT/calib.jsonl" \
  > "$OUT/calib.log" 2>&1
echo "== calibrate rc=$? t=$(( $(date +%s) - T0 ))"
cut -c1-700 "$OUT/calib.jsonl" 2>/dev/null

for set in 1 2; do for s in $(seq 3000004001 3000004006); do
  guard 200 "F$set $s" && run F${set}_$s --workload "$W" --seed $s --seconds "$SECONDS_RUN" --trace 0
done; done
for s in 3000005001 3000005002 3000005003; do
  guard 200 "T $s" && run T_$s --workload "$W" --seed $s --seconds "$SECONDS_RUN" --trace 1
done
echo "total $(( $(date +%s) - T0 )) s"
