"""Pallas TPU kernels for the serving hot path.

The compute plane is mostly XLA-fused jit code; kernels live here only
where explicit tiling beats the compiler — flash attention (O(S^2) HBM
traffic -> O(S*D)), paged decode-attention (block-table gather + int8
dequant + attention fused over the paged KV pool, docs/PERFORMANCE.md §7),
learned sparse attention (a prompt's exact top-k selection as a mask,
and the tiled attention under it: ``sparse_attention.py``), a decode
step's routed experts read by the list of those its tokens chose
(``touched_experts.py``) and a decode step's read of a latent paged cache,
the query carried into the latent space and a row read once
(``mla_attention.py``).
"""

from seldon_core_tpu.ops.flash_attention import (
    flash_attention,
    flash_causal_attention_blhd,
)
from seldon_core_tpu.ops.mla_attention import (
    mla_decode_attention,
    mla_decode_attention_reference,
)
from seldon_core_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_reference,
)
from seldon_core_tpu.ops.sparse_attention import (
    masked_flash_attention,
    select_topk_mask,
    sparse_decode_attention,
)
from seldon_core_tpu.ops.touched_experts import touched_expert_products

__all__ = [
    "flash_attention",
    "flash_causal_attention_blhd",
    "paged_decode_attention",
    "paged_decode_attention_reference",
    "mla_decode_attention",
    "mla_decode_attention_reference",
    "masked_flash_attention",
    "select_topk_mask",
    "sparse_decode_attention",
    "touched_expert_products",
]
