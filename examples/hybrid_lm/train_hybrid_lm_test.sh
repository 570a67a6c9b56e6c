#!/usr/bin/env bash
# Smoke-train the hybrid Mamba-2 / attention / LatentMoE causal LM
# (--arch nemotron_h_tiny: pattern *EMEM, 16 experts) on the synthetic
# example text of examples/bert, documents packed into blocks of 128
# tokens.  About a minute with UNICORE_TPU_PLATFORM=cpu.  Append
# "--n-routed-experts-held 4 --first-routed-expert-held 8" to train the
# share of the expert layers that holds experts 8..11, and "--mixer-shares 2"
# to hold half of the mixers' heads (docs/hybrid_lm.md).
set -e
cd "$(dirname "$0")"
export PYTHONPATH="$(cd ../.. && pwd)${PYTHONPATH:+:$PYTHONPATH}"
DATA=../bert/example_data
[ -f $DATA/train.idx ] || (cd ../bert && python make_example_data.py)
python -m unicore_tpu_cli.train $DATA \
  --task causal_lm --loss lm_cross_entropy --arch nemotron_h_tiny \
  --tokens-per-sample 128 \
  --optimizer adam --adam-betas "(0.9, 0.95)" --adam-eps 1e-8 \
  --clip-norm 1.0 --weight-decay 0.1 \
  --no-weight-decay-names norm,a_log,dt_bias,d_skip,conv_bias,correction \
  --lr-scheduler fixed --lr 1e-3 --max-update 40 --max-epoch 50 \
  --batch-size 1 --update-freq 1 \
  --log-interval 10 --log-format simple --no-save \
  --num-workers 2 --seed 1 "$@"
