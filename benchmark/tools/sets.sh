# Sets of runs of one cell, one JSON line each into chiprun_out/sets_<cell>.jsonl
# (read it with `python -m benchmark.tools.spread chiprun_out/sets_<cell>.jsonl`).
# usage: bash benchmark/tools/sets.sh <workload> <first seed> <sets> <runs> [seconds]
# Both sets run the same seeds: first seed, +1, ...
w=$1; base=$2; sets=${3:-2}; runs=${4:-6}; secs=${5:-51}
mkdir -p chiprun_out
for k in $(seq 1 $sets); do
for i in $(seq 0 $((runs - 1))); do
s=$((base + i))
line=$(python -m benchmark.run --workload $w --seed $s --seconds $secs --trace 0 2> chiprun_out/last.err | tail -1)
echo "{\"set\": $k, \"seed\": $s, \"line\": $line}" | tee -a chiprun_out/sets_$w.jsonl | cut -c1-700
grep "warm-up synced\|lead-in starts\|gap_ms\|ttft_ms\|reference done\|correct False" chiprun_out/last.err
done
done
