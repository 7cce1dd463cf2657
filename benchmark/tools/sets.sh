# Two sets of six runs of one cell with the same seeds (set A, set B),
# then three traced runs, on the chip; one JSON line per run in
# <outdir>/results.jsonl. How the bounds and readings in PERF.md were taken.
#   chiprun [--chips 4] -- bash benchmark/tools/sets.sh <cell> <outdir> <seedbase>
c=$1; o=$2; b=$3
mkdir -p $o
for set in A B; do for i in 1 2 3 4 5 6; do s=$((b + i))
  python3 benchmark/run.py --workload $c --seed $s --seconds 30 --trace 0 > $o/$set.$s.out 2> $o/$set.$s.err
  echo "{\"set\": \"$set\", \"seed\": $s, \"rc\": $?, \"res\": $(tail -1 $o/$set.$s.out || echo null)}" >> $o/results.jsonl
done; done
for i in 101 102 103; do s=$((b + i))
  python3 benchmark/run.py --workload $c --seed $s --seconds 30 --trace 1 > $o/T.$s.out 2> $o/T.$s.err
  echo "{\"set\": \"T\", \"seed\": $s, \"rc\": $?, \"res\": $(tail -1 $o/T.$s.out || echo null)}" >> $o/results.jsonl
done
grep -h "\[window\]\|\[setup\] phases" $o/*.err | sort | uniq -c | head -40
