"""Parallelism building blocks beyond plain data parallelism.

The pjit DP formulation (parameters replicated, batch sharded, XLA inserts
the gradient all-reduce) lives in ``tpudist.train.step``; the 2-stage
model-split parity shape in ``tpudist.models.split_mlp``.  This package
holds the scalable strategies on the 4-axis mesh
(``tpudist.runtime.mesh``):

- :mod:`ring_attention` — sequence/context parallelism (``seq`` axis,
  incl. the zigzag causal-balanced layout — every (device, hop) costs the
  same two half-chunk blocks):
  blockwise attention with K/V rotating over ICI via ``ppermute``.
- :mod:`tensor_parallel` — Megatron-style column/row linear pairs
  (``model`` axis), both pjit-spec and explicit-``psum`` forms.
- :mod:`pipeline` — microbatched GPipe schedule (``stage`` axis) with
  activations hopping the ring inside one jitted ``lax.scan``.
- :mod:`moe` — capacity-based top-1 expert parallelism with a single
  fused ``all_to_all`` each way (``model`` axis as the expert group).
- :mod:`fsdp` — ZeRO-3-style fully-sharded state layout over the ``data``
  axis (XLA inserts the all-gather/reduce-scatter pair).
"""

from tpudist.parallel.ring_attention import (  # noqa: F401
    make_zigzag_lm_loss,
    make_zigzag_ring_attention,
    ring_attention_shard_zigzag,
    zigzag_indices,
    make_ring_attention,
    ring_attention_shard,
)
from tpudist.parallel.tensor_parallel import (  # noqa: F401
    column_spec,
    init_mlp_params,
    make_tp_mlp,
    mlp_param_sharding,
    row_spec,
    tp_mlp_overlap_shard,
    tp_mlp_shard,
)
from tpudist.parallel.overlap import (  # noqa: F401
    OVERLAP_MODES,
    OVERLAP_SCOPE,
    ag_matmul,
    matmul_rs,
    overlap_mode,
)
from tpudist.parallel.pipeline import (  # noqa: F401
    make_pipeline,
    pipeline_1f1b_shard,
    pipeline_shard,
)
from tpudist.parallel.pipeline_interleaved import (  # noqa: F401
    deinterleave_block_params,
    interleave_block_params,
    interleaved_schedule,
    pipeline_interleaved_shard,
)
from tpudist.parallel.pipeline_lm import (  # noqa: F401
    make_pp_lm_apply,
    make_pp_lm_train_step,
    pp_state_sharding,
    stack_block_params,
    stack_block_params_interleaved,
    unstack_block_params,
)
from tpudist.parallel.moe import MoEStats, make_moe, moe_shard  # noqa: F401
from tpudist.parallel.fsdp import (  # noqa: F401
    fsdp_sharding,
    merge_shardings,
    overlap_fsdp_mlp,
    state_bytes_per_device,
    zero1_sharding,
)
