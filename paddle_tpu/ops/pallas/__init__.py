"""Pallas TPU kernels for hot ops (flash attention, ragged paged attention,
the paged arena's append, the routed experts' grouped matmul, the full
layers' key selection, the Mamba layers' decode-step state update).

Reference parity: the role of paddle/phi/kernels/gpu/flash_attn_kernel.cu +
dynload/flashattn.cc in /root/reference — except the kernels are written in
Pallas/Mosaic against VMEM/MXU instead of binding an external CUDA library.
`_backend.py` holds the shared dispatch gate (TPU compiled / CPU interpret /
XLA fallback); `paged_attention.py` is the serving engine's ragged
mixed-batch attention over the paged KV arena, `paged_kv_append.py` the
block writer that puts a step's new K/V into it and `grouped_matmul.py` the
product of a step's sorted (token, expert) rows with the experts they hit;
`topk_select.py` is the exact top-k set without a sort and
`ssm_state_update.py` the decode step of a Mamba layer's scan, its state
updated in place.
"""
