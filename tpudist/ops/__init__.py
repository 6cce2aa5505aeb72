"""Pallas TPU kernels for hot ops, with XLA reference implementations used
as fallbacks and in correctness tests (interpret mode on CPU).

- :mod:`attention` — which attention runs on one device, in which operand
  layout, with which tiles: one table by device kind and the dispatch the
  models take as their default ``attention_fn``.
- :mod:`flash_attention` — blockwise online-softmax attention (Pallas
  kernels and the blockwise XLA scan) and ``attention_reference``, the
  dense ground truth; pairs with ``tpudist.parallel.ring_attention`` (ring
  shards between chips, flash blocks within a chip).
- :mod:`rope` — rotary angles and rotation, shared by the models and the
  fused RoPE+QKV kernel.
- :mod:`paged_attention` — serving-decode attention that walks the paged
  KV cache's block table INSIDE the kernel (vLLM-PagedAttention style):
  live blocks only, int8 dequant in-registers, the decode-window mask
  fused so s=1 decode and the speculative verify share one kernel.
- :mod:`paged_prefill` — the prefill sibling: walks the reused prefix
  out of the pool, runs the chunk's causal self-attention, and WRITES
  the touched KV blocks in-kernel (merge + requantize), closing the
  last dense ``[slots, max_len]`` materialization.
- :mod:`fused_sample` — the decode tail (constrain mask, greedy argmax,
  temperature, top-k/top-p, spec-decode residual prep) in one kernel;
  random draws stay in-graph so sampled streams are byte-identical.
- :mod:`fused_linear` — fused RoPE+QKV projection on per-slot vector
  offsets, and the LoRA gather-matmul addressed through
  scalar-prefetched adapter ids.
"""

from tpudist.ops.flash_attention import (  # noqa: F401
    attention_reference,
    blockwise_attention,
    flash_attention,
    flash_attention_packed,
    flash_attention_with_lse,
)
from tpudist.ops.attention import (  # noqa: F401
    default_attention,
    make_length_aware_attention,
)
from tpudist.ops.rope import (  # noqa: F401
    rope_angles,
    rope_rotate,
    rope_rotate_packed,
)
from tpudist.ops.paged_attention import (  # noqa: F401
    paged_attention,
    paged_attention_reference,
)
from tpudist.ops.paged_prefill import (  # noqa: F401
    paged_prefill_attention,
    paged_prefill_reference,
)
from tpudist.ops.fused_sample import (  # noqa: F401
    fused_residual_prep,
    fused_residual_reference,
    fused_sample_prep,
    fused_sample_reference,
)
from tpudist.ops.fused_linear import (  # noqa: F401
    fused_rope_qkv,
    fused_rope_qkv_reference,
    lora_delta,
    lora_delta_reference,
)
