#!/bin/sh
# One chip call's worth of trial runs of a cell: `sh perfbench/tests/chip_trial.sh
# <cell> <seconds> <seed> [<seed> ...]`; a seed written `t<seed>` is a traced run.
# Result lines are gathered in chiprun_out/<cell>.trial.jsonl.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out
for s in "$@"; do
  trace=0; case $s in t*) trace=1; s=${s#t};; esac
  echo "== $cell seed $s trace $trace" >&2
  python3 -m perfbench.run --workload "$cell" --seed "$s" --seconds "$seconds" --trace $trace \
    2> chiprun_out/$cell.$s.$trace.err | tail -n 1 | tee -a chiprun_out/$cell.trial.jsonl
  echo "rc=$?" >&2
  tail -n 8 chiprun_out/$cell.$s.$trace.err >&2
done
