# Pallas TPU kernels for the compute hot-spots (validated on CPU with
# interpret=True; BlockSpecs tile for VMEM / MXU on the v5e target):
#   wordcount_hash  — Map+LocalReduce histogram (the paper's hot loop)
#   moe_dispatch    — bucket-slot prefix counts (the displacement window)
#   flash_attention — blocked online-softmax prefill attention
#   flash_decode    — 1-token query vs long KV cache (decode roofline)
#   ssd_scan        — Mamba-2 chunked state-space-dual scan
