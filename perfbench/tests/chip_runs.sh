#!/bin/bash
# Several runs of one cell in one chip call, their result lines gathered in
# chiprun_out/.  usage: chip_runs.sh <tag> <workload> <seconds> <trace> <seed>...
tag=$1; cell=$2; seconds=$3; trace=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  start=$(date +%s)
  python3 perfbench/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" \
      --trace "$trace" $EXTRA > chiprun_out/.line 2> chiprun_out/.err
  rc=$?
  end=$(date +%s)
  echo "== $cell seed=$seed trace=$trace rc=$rc wall=$((end - start))s"
  grep -v cpu_aot_loader chiprun_out/.err > chiprun_out/$tag.$seed.err
  tail -${ERR_LINES:-3} chiprun_out/$tag.$seed.err | cut -c1-1500
  tail -1 chiprun_out/.line | cut -c1-${LINE_CHARS:-1800}
  echo "{\"tag\": \"$tag\", \"cell\": \"$cell\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"line\": $(tail -1 chiprun_out/.line | grep '^{' || echo null)}" >> chiprun_out/$tag.jsonl
done
rm -f chiprun_out/.line chiprun_out/.err
