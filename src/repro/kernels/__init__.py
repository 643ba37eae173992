"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel lives in <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), dispatches through ops.py (jit'd wrappers with padding/backend
selection) and is validated against its pure-jnp oracle in ref.py
(interpret mode on the CPU backend; native Mosaic on TPU):

  flash_attention — blocked causal/SWA/softcap GQA, online softmax
  lru_scan        — diagonal linear recurrence (RG-LRU / diagonal SSM)
  ssd_chunk       — Mamba-2 SSD intra-chunk quadratic dual form
  schedule_step   — the fused scheduler pass: Eq. 3 score, Eq. 2
                    best-node reduction, Eq. 4 argmin, gang-fit tiles
                    and the BE backfill scan over the (jobs, nodes)
                    tile in one invocation (subsumes the former
                    fitgpp_score kernel, kept only as an error shim)
"""
