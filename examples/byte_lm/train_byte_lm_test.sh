#!/usr/bin/env bash
# Smoke-train the byte-level EVA decoder (--arch evabyte_tiny: three layers
# of window-plus-chunk-summary attention with rotary positions and a gated
# SiLU feed-forward layer, three bytes predicted at every position) on the
# synthetic example text of examples/bert read as UTF-8 bytes
# (--tokenizer bytes: 320 ids, no dict.txt), documents packed into blocks
# of 128 bytes: four windows of 32.  About a minute with
# UNICORE_TPU_PLATFORM=cpu.  Append "--attention-shares 2" to hold half of
# the attention heads, "--layers-held 2" to hold two of the three layers.
set -e
cd "$(dirname "$0")"
export PYTHONPATH="$(cd ../.. && pwd)${PYTHONPATH:+:$PYTHONPATH}"
DATA=../bert/example_data
[ -f $DATA/train.idx ] || (cd ../bert && python make_example_data.py)
python -m unicore_tpu_cli.train $DATA \
  --task causal_lm --tokenizer bytes --loss lm_cross_entropy \
  --arch evabyte_tiny --tokens-per-sample 128 \
  --optimizer adam --adam-betas "(0.9, 0.95)" --adam-eps 1e-8 \
  --clip-norm 1.0 --weight-decay 0.1 \
  --no-weight-decay-names norm,adaptive_mu_k,adaptive_phi \
  --lr-scheduler fixed --lr 1e-3 --max-update 40 --max-epoch 50 \
  --batch-size 2 --update-freq 1 \
  --log-interval 10 --log-format simple --no-save \
  --num-workers 2 --seed 1 "$@"
