#!/bin/bash
# CPU smoke (seconds):  JAX_PLATFORMS=cpu ./train.sh
# One of four expert-parallel shares:  ./train.sh --config_args=experts_held=0:2
set -e
echo seed-1 > train.list
paddle train \
  --config=trainer_config.py \
  --save_dir=./output \
  --num_passes=2 \
  --log_period=4 \
  "$@"
