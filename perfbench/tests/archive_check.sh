#!/bin/sh
# Prove that the committed files are enough: after `git add -A`, unpack
# `git archive $(git write-tree)` into the git-ignored build/archive_check
# (here, where git is), then run a cell from there on the chip:
#   sh perfbench/tests/archive_check.sh unpack
#   chiprun -- sh perfbench/tests/archive_check.sh run <cell> <seconds> <seed>
case $1 in
unpack)
  rm -rf build/archive_check && mkdir -p build/archive_check
  git archive "$(git write-tree)" | tar -x -C build/archive_check
  ;;
run)
  cd build/archive_check || exit 1
  python3 -m perfbench.run --workload "$2" --seed "$4" --seconds "$3" --trace 0 \
    | tee ../../chiprun_out/archive_check.jsonl
  ;;
esac
