"""Pallas TPU kernels for the compute hot spots (interpret-validated on CPU).

flash_attention  causal GQA flash attention with its backward (JAX's
                 splash kernels): the training path's attention on TPU
paged_attention  block-table decode attention over a paged KV pool
                 (serve engine kv_backend="paged" hot path)
fused_adam_sync  one-pass fused AdamW update (HBM-bound optimizer step)
ssd_scan         Mamba-2 SSD chunk-local core (MXU quadratic block)
int8_quant       per-row int8 quant/dequant (pod-axis compression wire fmt)
"""
