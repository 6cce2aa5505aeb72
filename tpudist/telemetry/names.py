"""The one vocabulary of names the program writes into a trace and its
telemetry stream, and every reader of either looks for.

Plain string constants, no jax import: the program (``tpudist.ops``,
``tpudist.models``, ``tpudist.train``, ``tpudist.data``,
``tpudist.runtime``) and the readers (``cellbench/readers``, the
aggregator) both import this module, so a name is spelled once.

Four kinds of name, four places they show:

- **kernel names** — ``pallas_call(name=..., metadata={"kernel": ...})``:
  the Mosaic custom call's event text in a device trace carries
  ``kernel_metadata={"kernel": "<name>"}`` whatever a ``shard_map`` round
  the call does to the instruction's own name;
- **scopes** — ``jax.named_scope``: a component of the ``op_name`` of
  every HLO instruction traced inside it (``jit(step)/loss/...``,
  ``.../transpose(jvp(attn))/...`` for its backward), which the profiler
  keeps with the device operation;
- **spans and events** — ``tpudist.telemetry``: records of the JSONL
  stream and the session's ring, and (spans) ``TraceAnnotation``s on the
  host rows of a profiler trace;
- **kept activations** — ``jax.ad_checkpoint.checkpoint_name``: what a
  rematerialised layer keeps by name; they show in a gradient's jaxpr
  (``name[name=...]``) and in the ``mixer_layout`` event's ``remat_keeps``.
"""

# -- kernel names (tpudist/ops) ----------------------------------------------
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
FLASH_KERNELS = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)
PAGED_ATTENTION = "paged_attention"
PAGED_PREFILL = "paged_prefill"
FUSED_ROPE_QKV = "fused_rope_qkv"
LORA_DELTA = "lora_delta"
FUSED_SAMPLE = "fused_sample"
FUSED_RESIDUAL = "fused_residual"
#: the key of ``pallas_call``'s ``metadata`` dict that holds the name
KERNEL_KEY = "kernel"

# -- scopes (jax.named_scope) -------------------------------------------------
# tpudist/models/transformer.py: the sublayers of the forward pass; JAX adds
# ``jvp(...)`` / ``transpose(jvp(...))`` round them for the backward pass
EMBED = "embed"
ATTN = "attn"
MLP = "mlp"
HEAD = "head"
# tpudist/train/lm.py: the phases of the jitted step
LOSS = "loss"
OPTIMIZER = "optimizer"
GRAD_ACCUM = "grad_accum"
# tpudist/models/hybrid.py: the mixers and the feed-forward arm of a decoder
# whose layers follow a pattern (each layer a module ``PATTERN_LAYER_<i>``, a
# component of its ops' names; the dense feed-forward arm runs under ``mlp``
# there as ``transformer``'s does under its blocks).  ``linear_attn`` is the
# whole linear-attention
# mixer (norm, projections, convolution, gates, output projection) as ``attn``
# is the whole softmax one; ``delta_rule`` (tpudist/ops/gated_delta.py) is the
# recurrence alone, nested in it.  ``moe`` (tpudist/parallel/moe.py) runs from
# the router to the combine; ``experts`` (the grouped products),
# ``moe_combine`` (each token's rows back out of the buffer and added up,
# forward and backward) and ``shared_expert`` nest in it
LINEAR_ATTN = "linear_attn"
DELTA_RULE = "delta_rule"
MOE = "moe"
EXPERTS = "experts"
SHARED_EXPERT = "shared_expert"
MOE_COMBINE = "moe_combine"
PATTERN_LAYER = "layer"
# ``ssm`` is the whole state-space mixer (norm, projection, convolution, the
# scan, gated norm, output projection); ``ssd_scan`` (tpudist/ops/ssd.py) is
# the chunked scan alone, nested in it.  ``latent_proj`` holds the two
# projections that take an expert layer's tokens into its experts' latent
# space and its partial sum back out, nested in ``moe``
SSM = "ssm"
SSD_SCAN = "ssd_scan"
LATENT_PROJ = "latent_proj"
# nested in ``ssm`` beside the scan, so that the mixer's time outside its
# products and its scan can be read by part: ``ssm_conv`` the depthwise
# convolution over x, B and C with its bias and SiLU; ``ssm_norm`` the
# gate's product ``y * silu(z)`` and the gated norm, with its reduction
# where the members of a head share take the statistic together
SSM_CONV = "ssm_conv"
SSM_NORM = "ssm_norm"
# ``window_attn`` is a whole softmax mixer whose band is a sliding window
# (a layer of kind WINDOW), as ``attn`` is one that attends causally to
# everything; ``head_gate`` (the gate's projection, its sigmoid and the
# product into the attention's output a head) nests in either
WINDOW_ATTN = "window_attn"
HEAD_GATE = "head_gate"
# ``kda`` is a whole delta-rule mixer whose decay is a number a CHANNEL (a
# layer of kind CHANNEL_LINEAR: norm, projections, convolution, gates, the
# scan, output projection), as ``linear_attn`` is one whose decay is a number
# a head; ``delta_rule`` nests in it as there, and ``kda_gate`` beside it:
# what such a mixer adds to a delta-rule mixer, the forget gate's low-rank
# projections with their softplus and ``exp(A_log)``, and the output gate's
# low-rank projections with their sigmoid and its product into the normed
# output.  ``latent_attn`` is a whole latent-attention mixer (a layer of kind
# LATENT); ``latent_kv`` nests in it: what latent attention adds round the
# flash kernels, the projection into the latent and the shared key, the
# latent's norm, the projection out of it and the assembly of the heads' keys
KDA = "kda"
KDA_GATE = "kda_gate"
LATENT_ATTN = "latent_attn"
LATENT_KV = "latent_kv"
SCOPES = (EMBED, ATTN, MLP, HEAD, LOSS, OPTIMIZER, GRAD_ACCUM, LINEAR_ATTN,
          DELTA_RULE, MOE, EXPERTS, SHARED_EXPERT, MOE_COMBINE, SSM, SSD_SCAN,
          LATENT_PROJ, WINDOW_ATTN, HEAD_GATE, SSM_CONV, SSM_NORM, KDA,
          KDA_GATE, LATENT_ATTN, LATENT_KV)
#: what JAX itself writes round the scopes of a transposed (backward) op
BACKWARD_MARK = "transpose("

# -- activations kept under remat (jax.ad_checkpoint.checkpoint_name) --------
# tpudist/models/hybrid.py: what a rematerialised pattern layer keeps besides
# its input, whatever the remat policy.  ``mixer_out`` is the layer's
# activation between its mixer and its feed-forward arm, ``[tokens, d_model]``
# in the compute dtype (both arms; it is also the dense arm's input).  The
# dense arm alone keeps the outputs of its three products as well:
# ``gate_proj``'s and ``up_proj``'s (``[tokens, ffn_width]`` each) and
# ``down_proj``'s (``[tokens, d_model]``, which the norm after the sublayer
# needs), so nothing of the feed-forward's forward runs again in the backward
# pass: ``tokens x (2 x ffn_width + d_model) x itemsize`` bytes a layer
MIXER_OUT = "mixer_out"
FFN_GATE = "ffn_gate"
FFN_UP = "ffn_up"
FFN_OUT = "ffn_out"
DENSE_FFN_KEEPS = (FFN_GATE, FFN_UP, FFN_OUT)
# tpudist/parallel/moe.py: the result of an expert share that takes its
# arrivals through windows, ``[tokens, width]`` in the compute dtype (the
# latent width where the layer has one).  A layer of one sublayer keeps it:
# the projection behind the share needs it for its weight gradient, and the
# share's loop, run again for it, could not be merged with the backward
# pass's own as straight-line code is
EXPERT_OUT = "expert_out"
# tpudist/models/hybrid.py, tpudist/parallel/moe.py: the outputs of an expert
# layer's dense products that its backward pass reads, which a rematerialised
# layer of one sublayer keeps beside ``expert_out`` so that each runs once a
# step, not twice: the UNSCORED shared expert's first product(s), ``[tokens,
# shared_width]`` in the compute dtype each (``up``; ``gate`` too where the
# expert is gated; ``down``'s output is read by nothing behind a norm that
# comes before the sublayer and is not kept), named where ``ExpertShare``
# calls the expert, not in the expert functions, which the grouped products
# share; the router's logits, ``[tokens, n_experts]`` in float32 (named in
# ``expert_share`` for every caller; a caller whose policy does not name
# them keeps nothing); and ``latent_down``'s output, ``[tokens,
# latent_width]`` in the compute dtype, where the layer has latent
# projections (``latent_up``'s is ``expert_out``'s successor and read by
# nothing).  ``tokens x ((1 or 2) x shared_width + latent_width) x itemsize
# + tokens x n_experts x 4`` bytes a layer
SHARED_GATE = "shared_gate"
SHARED_UP = "shared_up"
SHARED_EXPERT_KEEPS = {"gate": SHARED_GATE, "up": SHARED_UP}  # by weight
ROUTER_LOGITS = "router_logits"
LATENT_IN = "latent_in"
# tpudist/parallel/moe.py: what the router decided, named by ``route`` for
# every caller: the picks ``[tokens, k]`` int32 and the picks' raw scores
# ``[tokens, k]`` float32 (before they are renormalised), one name for both.
# Every rematerialised expert layer keeps them beside the router's logits,
# a layer of two sublayers too: what is run again behind them is
# elementwise, with no sort and no pick of a score.  ``tokens x k x 8``
# bytes a layer
ROUTER_PICKS = "router_picks"
# tpudist/ops/gated_delta.py: the inverse of a chunk of the delta rule,
# ``T = (I + A)^-1``, ``[chunks, batch, heads, chunk x chunk]`` in float32
# whatever the compute dtype (named with its two minor dimensions as one, so
# that it lies lane-dense).  A rematerialised layer whose mixer scans by the
# delta rule (a decay a head or a channel) keeps it: ``T`` is the one
# residual of the inverse's own backward pass, and with it kept the
# rematerialised forward does not solve for it again.  ``tokens x heads x
# chunk x 4`` bytes a layer
DELTA_INVERSE = "delta_inverse"
# tpudist/models/hybrid.py (``KimiDeltaAttention``): what a delta-rule mixer
# whose decay is a number a channel computes on the way to its scan, each
# ``[tokens, heads x 128]`` in the compute dtype, lane-dense: the outputs of
# ``q_proj``, ``k_proj`` and ``v_proj``, named where each product comes out,
# before the concatenate.  A rematerialised layer of kind CHANNEL_LINEAR keeps
# them (KDA_KEEPS) beside ``delta_inverse``: the three products then run once
# a step, not twice (the convolution, SiLU and the norms that follow them are
# elementwise and run again from the kept tensors).  ``tokens x heads x
# (2 x dk + dv) x itemsize`` bytes a layer.  A mixer whose decay is a number
# a head (``GatedDeltaNet``) names nothing of the kind: its projections are
# narrower (PR 36) or its cell has no memory left for them (ROADMAP S18)
KDA_Q = "kda_q_proj"
KDA_K = "kda_k_proj"
KDA_V = "kda_v_proj"
KDA_KEEPS = (KDA_Q, KDA_K, KDA_V)

# -- spans (tpudist.telemetry.span / record_span) -----------------------------
STEP = "step"            # one arrival of a step's result to the next
COMPILE = "compile"      # the first step: enqueue to its result, compile in
DISPATCH = "dispatch"    # child of step: the enqueue of the next step
DATA_WAIT = "data_wait"  # the loop's blocking next() on its loader
LM_BATCH = "lm_batch"    # tpudist/data/lm.py: producing one token batch
INIT = "init"            # tpudist/runtime/bootstrap.py: initialize()
# tpudist/runtime/compilation_cache.py, from JAX's own duration events
XLA_TRACE = "xla_trace"
XLA_LOWER = "xla_lower"
XLA_BACKEND_COMPILE = "xla_backend_compile"
#: ``jax.monitoring`` duration event -> span name
XLA_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": XLA_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": XLA_LOWER,
    "/jax/core/compile/backend_compile_duration": XLA_BACKEND_COMPILE,
}
#: the ``parent`` every ``xla_*`` span carries, so that none enters a goodput sum
XLA_PARENT = "xla"
# every ``xla_*`` span says which program it was for as ``fun=``: JAX's own
# ``fun_name`` with ``jit(...)`` stripped, so one program has one name across
# its trace, its lowering and its compile.  A nested trace carries its own
# name (``sin``, ``_flash_forward``) and lies inside its caller's duration.
#: what ``tpudist/train/lm.py`` calls the function it jits: the LM step
STEP_PROGRAM = "step"
# ``xla_backend_compile`` (JAX reports a load from the persistent cache and a
# compile under the one duration event) also says which it was as ``cache=``:
# CACHE_HIT with ``load_s=`` the retrieval, CACHE_MISS (compiled and written),
# or UNCACHED (compiled and not written: the cache is off, or the program is
# under its 0.5 s floor); and ``cold_s=``, what compiling it costs without
# the cache: the span's own duration where it compiled, the compile time the
# entry saved + its retrieval where it loaded (JAX keeps an entry's compile
# time in whole seconds, cut off)
CACHE_HIT = "hit"
CACHE_MISS = "miss"
UNCACHED = "uncached"
#: ``jax.monitoring`` duration events of a hit, told on the compiling thread
#: just before the ``backend_compile_duration`` that encloses them: what the
#: load took, and the entry's own compile time less that
XLA_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
XLA_CACHE_TIME_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
XLA_CACHE_DURATIONS = (XLA_CACHE_RETRIEVAL, XLA_CACHE_TIME_SAVED)
#: the ``StepTraceAnnotation`` of the training loops
STEP_ANNOTATION = "train"

# -- events ------------------------------------------------------------------
COMPILE_CACHE_HIT = "compile_cache_hit"
COMPILE_CACHE_MISS = "compile_cache_miss"
# tpudist/models/transformer.py, once a trace of each attention call site of
# a training ``Block``: which operand layout the attention took, as
# ``layout=`` PACKED (the flash kernels index the fused projection's own
# [b, s, 3·d] output) or HEAD_MAJOR ([b, h, s, dh] operands, re-laid out
# round the attention) with the ``reason=`` it was not packed; where the
# flash kernels run, ``diag_sub=`` the squares a tile an edge of the band
# crosses is worked by (0: whole and masked), ``computed_over_live=`` score
# entries computed over live pairs and, of a windowed call, ``window=``,
# ``tiles=`` [block_q, block_k], ``grid_kv=`` the length of the key axis of
# the forward and dq grids (the longest run of live key tiles a query tile
# has) and ``live_steps_share=`` live tiles over a head's grid steps.
# tpudist/models/hybrid.py, once a trace of each latent-attention call site:
# ``layout=`` HEAD_MAJOR with its ``reason=`` (a head's 192 dims are not
# whole lane tiles: WHY_DH), ``kernel=`` what the dispatch's table routes the
# call to, ``qk_dim=`` the width the scores are taken at (a head's own key
# part + the part all heads share), ``v_dim=`` the values' own width (the
# flash kernels take it as it is: nothing is padded) and ``latent_rank=``
ATTN_LAYOUT = "attn_layout"
PACKED = "packed"
HEAD_MAJOR = "head_major"
WHY_DH = "dh"                # head_dim is not a multiple of 128 lanes
WHY_SEQ = "seq"              # too short for the flash kernels, or no tile fits
WHY_PLATFORM = "platform"    # not a TPU
WHY_CUSTOM_FN = "custom_fn"  # an injected attention_fn without a packed route
WHY_WIDTHS = "widths"        # values narrower than keys: the packed layout has one width
# tpudist/models/hybrid.py, once a trace of the decoder: ``kinds=`` the layer
# kinds in order (the keys of models/hybrid.py::MIXERS, or EXPERT_LAYER) and
# ``one_sublayer=`` whether a layer is one sublayer (a mixer OR the
# feed-forward arm) and not a mixer and its feed-forward; ``attention=``
# GATED_ATTN / NORMED_ATTN / GROUPED_ATTN with
# ``attn_heads=`` [held, in all], ``attn_kv_heads=``, or HEAD_GATED_ATTN with
# ``softmax_kinds=`` a softmax kind (FULL, WINDOW) its ``heads`` [held, in
# all], ``kv_heads``, ``window``, ``rotary_dim``, ``rope_theta``,
# ``yarn_factor`` and ``rope_scale``; ``head_dim=``; of the
# state-space mixers ``ssm_heads=`` and ``ssm_groups=`` [held, in all],
# ``ssm_head_dim=``, ``ssm_state=``, ``ssm_chunk=``,
# ``ssm_group_members=`` how many of the members that share a layer read one
# group (1: a member holds whole groups) and ``ssm_norm_over=`` what the
# gated norm's mean square runs over: HELD (the heads held here) or the
# mapped axis; ``softmax_scale=`` (None: ``head_dim ** -0.5``),
# ``residual_scale=``, ``embedding_scale=``, ``logits_divisor=``,
# ``tied_head=``; of the
# delta-rule mixers ``linear_heads=`` [value heads held, in all],
# ``linear_key_heads=``, ``linear_key_dim=``, ``linear_value_dim=``,
# ``linear_projections=`` FUSED / SEPARATE, ``beta_scale=`` (the write
# strength is that times a sigmoid), ``linear_gate_rank=`` the rank of a
# CHANNEL_LINEAR mixer's two gates; of the LATENT mixers ``latent_rank=``,
# ``latent_key_dims=`` [a head's own, the part all heads share] and
# ``latent_value_dim=`` (its heads are ``attn_heads=``); ``heads_axis=`` the mapped axis the
# members that share a layer by heads reduce over, or None;
# ``feed_forward=`` EXPERT_SHARE / DENSE_FFN and ``feed_forwards=`` the arm
# a layer; ``norm=`` ZERO_CENTRED / PLAIN
# and ``norm_after=`` whether it follows its sublayer; ``remat_keeps=`` the
# names a rematerialised layer keeps besides its input (MIXER_OUT, and
# DENSE_FFN_KEEPS in a dense arm that keeps its products:
# ``dense_products_kept=`` says so a layer; in an expert layer of one sublayer
# EXPERT_OUT, ROUTER_LOGITS, ROUTER_PICKS, LATENT_IN where it has latent
# projections, and SHARED_EXPERT_KEEPS' names of an unscored shared expert's
# first products; in an expert layer of two sublayers MIXER_OUT,
# ROUTER_LOGITS, ROUTER_PICKS; DELTA_INVERSE besides in a layer of kind LINEAR
# or CHANNEL_LINEAR, behind KDA_KEEPS in the latter; ``[]`` without remat) and
# ``remat_kept_bytes_per_layer=`` what they hold (both a list a layer where
# the layers do not all keep the same: their feed-forward arms differ, or
# only some mixers scan by the delta rule).  And of each expert
# layer
# (tpudist/parallel/moe.py): ``experts=`` the router's width, ``held=``,
# ``first=``, ``top_k=``, ``dropless=``, ``buffer_rows=`` (the bound: a
# block's assignments), ``blocks=``, ``window_rows=`` the rows of a window
# where the share takes its arrivals through windows (the bound itself
# where it keeps one buffer) and ``windows_at_most=`` how many of them the
# bound fills (1), ``strip_rows=`` the rows of one scatter-add, an even
# share of a window's eight (the bound itself where it keeps one buffer),
# ``combine=`` PICK_MAJOR (each token's ``k`` rows are
# added up as ``k`` slabs of [tokens, d]) or SCATTER_ADD (a window's rows
# are added into their tokens', the strips that hold arrivals and no
# other), ``scoring=`` SOFTMAX / SIGMOID /
# SIGMOID_BIAS,
# ``scale=`` what
# the picks' renormalised weights are multiplied by, ``width=`` the rows'
# width (an expert layer's latent width where it has one).  ``mixer_layout``
# is also said once a trace of each CHANNEL_LINEAR call site: ``decay=``
# CHANNEL, ``heads=`` [held, in all], ``dk=``, ``dv=``, ``chunk=``,
# ``sub_block=`` the rows of the chunk's sub-blocks and ``gate_floor=`` what
# the gate is held above (tpudist/ops/gated_delta.py), ``gate_rank=``
MIXER_LAYOUT = "mixer_layout"
MOE_LAYOUT = "moe_layout"
HELD = "held"
PICK_MAJOR = "pick_major"
SCATTER_ADD = "scatter_add"
LINEAR = "linear_attention"
FULL = "full_attention"
WINDOW = "sliding_attention"  # softmax attention inside a sliding window
STATE_SPACE = "state_space"  # a Mamba-2 mixer (tpudist/ops/ssd.py)
CHANNEL_LINEAR = "channel_gated_linear_attention"  # a delta rule, a decay a channel
LATENT = "latent_attention"  # softmax over keys and values out of a latent
CHANNEL = "channel"
EXPERT_LAYER = "expert_layer"  # one-sublayer layers only: the feed-forward arm
GATED_ATTN = "gated"         # per-head q/k norms, an output gate, part rotary
NORMED_ATTN = "normed"       # one q/k norm statistic over all heads, no gate
GROUPED_ATTN = "grouped"     # plain grouped-query: no norm, gate or rotary
HEAD_GATED_ATTN = "head_gated"  # a gate a head from its own projection, rotary
# tpudist/parallel/moe.py: how a router scores (``route``) and what an expert
# computes
SOFTMAX = "softmax"          # softmax over all experts, top k renormalised
SIGMOID = "sigmoid"          # sigmoid scores, the top k renormalised x a scale
SIGMOID_BIAS = "sigmoid_bias"  # sigmoid scores, picked by score + a bias
GATED_SILU = "gated_silu"    # down(silu(gate(x)) * up(x))
RELU2 = "relu2"              # down(relu(up(x))^2)
FUSED = "fused_per_key_head"
SEPARATE = "separate"
ZERO_CENTRED = "zero_centred"
PLAIN = "plain"
EXPERT_SHARE = "expert_share"
DENSE_FFN = "dense_gated"
#: ``jax.monitoring`` event -> event name
XLA_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": COMPILE_CACHE_HIT,
    "/jax/compilation_cache/cache_misses": COMPILE_CACHE_MISS,
}
#: ... -> the ``cache=`` an ``xla_backend_compile`` span says for it
XLA_CACHE_TAGS = {COMPILE_CACHE_HIT: CACHE_HIT, COMPILE_CACHE_MISS: CACHE_MISS}


def kernel(name: str) -> dict:
    """The two keyword arguments that name a ``pallas_call``:
    ``pl.pallas_call(..., **names.kernel(names.FLASH_FWD))``."""
    return {"name": name, "metadata": {KERNEL_KEY: name}}
