"""Pallas TPU kernels with pure-jnp oracles (`ops.py` = jit'd
entry points, `ref.py` = reference semantics, tested equal): flash
decode over dense/slot/paged KV (`decode_attention`), token-tree
verification attention (`tree_attention`), the Mamba2 SSD
intra-chunk scan (`ssd_scan`), and the fused int8 dequant-GEMV
(`int8_gemv`). They compile for the TPU and run in the Pallas
interpreter on other backends (`common.use_interpreter`).
"""
# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
