# Runs of one cell, one process each:
#   bash bench/tools/series.sh <out-dir> <cell> <seconds> <trace> <seed>...
# Appends each run's result line to <out-dir>/series_<cell>.jsonl and keeps
# each run's standard error in <out-dir>/err_<cell>_<seed>_<trace>.txt.
O=$1; C=$2; S=$3; T=$4; shift 4
mkdir -p "$O"
for seed in "$@"; do
  python3 bench/run.py --workload "$C" --seed "$seed" --seconds "$S" --trace "$T" \
    2> "$O/err_${C}_${seed}_${T}.txt" | tail -n 1 | \
    python3 -c "import sys,json; l=sys.stdin.read().strip(); d=json.loads(l) if l else {'missing': True}; d['seed']=$seed; d['trace']=$T; print(json.dumps(d))" \
    | tee -a "$O/series_${C}.jsonl"
done
