#!/bin/sh
# The measurement a bound is set from, in one chip call:
#   sh perfbench/tests/full_sets.sh <cell> <run_seconds> [<first seed>]
# two sets of 6 runs with the same seeds in both, then three traced runs on
# further seeds (one of them past 2**31). Result lines land in
# chiprun_out/<cell>.setA.jsonl, .setB.jsonl and .traced.jsonl; read the
# spreads with `python -m perfbench.tests.spread`.
cell=$1; seconds=$2; s=${3:-1001}
for set in setA setB; do
  sh perfbench/tests/chip_trial.sh "$cell" "$seconds" $s $((s+1)) $((s+2)) $((s+3)) $((s+4)) $((s+5))
  mv chiprun_out/$cell.trial.jsonl chiprun_out/$cell.$set.jsonl
done
sh perfbench/tests/chip_trial.sh "$cell" "$seconds" t$((s+6)) t$((s+10)) t$((s+2147482998))
mv chiprun_out/$cell.trial.jsonl chiprun_out/$cell.traced.jsonl
