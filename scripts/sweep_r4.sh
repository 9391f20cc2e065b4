#!/bin/bash
# Round-4 sweep plan: the batch>=24 region, loss_chunk where it was built to
# matter, and the ~25ms layer-scan overhead diagnosed by varying ONLY the
# remat policy under scan.  One process, combos serialized (a chip belongs to
# one process; shared compile cache).  Appends JSON lines to SWEEP_r04.json.
#
# combo format: batch,remat,attn,minib,scan,chunk[,k=v...]
set -u
cd "$(dirname "$0")/.."
python scripts/sweep_bench.py \
  16,proj_attn,flash,1,0,0 \
  20,proj_attn,flash,1,0,0 \
  24,proj_attn,flash,1,0,0 \
  24,proj_attn,flash,1,0,512 \
  32,proj_attn,flash,1,0,512 \
  32,proj_attn,flash,2,0,0 \
  16,proj_attn,flash,1,0,0,flash_block_q=256,flash_block_k=256 \
  16,proj_attn,flash,1,1,0 \
  16,proj,flash,1,1,0 \
  16,full,flash,1,1,0 \
  16,1,flash,1,1,0 \
  | tee -a SWEEP_r04.json
