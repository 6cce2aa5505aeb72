"""Decoder-only Transformer LM — the long-context model family.

The reference's only model is a 5-layer MLP on 2-dim inputs
(``toy_model_and_data.py:12-22``); this family is the capability extension
that gives the sequence-parallel machinery (``tpudist.parallel``) and the
Pallas attention kernel (``tpudist.ops``) a real consumer, designed
TPU-first:

- **pluggable attention op**: the block calls an injected
  ``attention_fn(q, k, v) -> out`` over ``[batch, heads, seq, head_dim]``
  (or, where the fn carries a ``packed`` route as the default does, hands
  it the fused projection's ``[batch, seq, 3·d]`` output as it lies).
  Three interchangeable implementations ship: the dense XLA reference
  (:func:`tpudist.ops.attention_reference`), the Pallas flash kernel
  (:func:`tpudist.ops.flash_attention`), and ring attention over a
  ``seq``-sharded mesh (:func:`tpudist.parallel.make_ring_attention`) —
  all numerically identical (tests assert it), so single-chip and
  multi-chip long-context runs share one model definition.
- **static shapes, pre-LN, bias-free projections** — the standard
  XLA-friendly decoder block; everything jits into one program.
- DP×SP training: batch sharded over ``data``, sequence over ``seq``; the
  ring closure carries its own shard_map, the rest of the network is
  elementwise/feature-contracting so pjit keeps activations sharded as
  ``P(data, seq, None)`` throughout.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpudist import telemetry
from tpudist.ops.attention import (
    default_attention,
    make_length_aware_attention,
    merge_heads,
    split_heads,
)
from tpudist.ops.rope import rope_rotate, rope_rotate_packed
from tpudist.telemetry import names

AttentionFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def moe_expert_fn(params, tokens):
    """The expert used by the MoE FFN: relu(x·w)·wo — shared between the
    sharded execution path (``tpudist.parallel.moe``) and the dense
    reference below, so they cannot drift."""
    return jax.nn.relu(tokens @ params["w"]) @ params["wo"]


def dense_moe_reference(params, tokens):
    """Single-device MoE execution: every expert computed for every token,
    combined by the top-1 gate.  Matches ``moe_shard`` exactly when no
    token overflows capacity; used at init time and on unsharded runs."""
    # Routing in f32 (precision-sensitive), expert matmuls in the compute
    # dtype — mirrors moe_shard's discipline exactly.
    probs = jax.nn.softmax((tokens @ params["router"]).astype(jnp.float32),
                           axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    h = jax.nn.relu(jnp.einsum("td,edf->tef", tokens, params["experts"]["w"]))
    y_all = jnp.einsum("tef,efd->ted", h, params["experts"]["wo"])
    pick = jax.nn.one_hot(idx, probs.shape[-1], dtype=tokens.dtype)
    return jnp.einsum("ted,te->td", y_all,
                      pick * gate.astype(tokens.dtype)[:, None])


class MoEFFN(nn.Module):
    """Switch-style FFN: top-1 routed experts.  ``moe_fn`` (built with
    :func:`tpudist.parallel.make_moe` over a ``model``-axis mesh) runs the
    expert-parallel path; without it the dense reference executes — same
    parameters either way, so init and single-device runs need no mesh."""

    d_model: int
    d_ff: int
    n_experts: int
    moe_fn: Optional[Callable] = None
    dtype: jnp.dtype = jnp.float32  # compute dtype; params stay f32 masters

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        init = nn.initializers.lecun_normal()
        params = {
            "router": self.param("router", init, (d, self.n_experts)),
            "experts": {
                "w": self.param("w", init, (self.n_experts, d, self.d_ff)),
                "wo": self.param("wo", init, (self.n_experts, self.d_ff, d)),
            },
        }
        # Same mixed-precision contract as the Dense layers: f32 master
        # params cast to the compute dtype here; the routing softmax inside
        # both execution paths upcasts to f32.
        params = jax.tree.map(lambda a: a.astype(self.dtype), params)
        tokens = x.reshape(b * s, d).astype(self.dtype)
        if self.moe_fn is not None:
            y, stats = self.moe_fn(params, tokens)
            # Routing observability: collected by train steps built with
            # ``aux=True`` (make_lm_train_step) and logged host-side — the
            # reference's reduce-then-log-on-rank-0 discipline (SURVEY.md
            # §5.5) applied to expert load.
            self.sow("intermediates", "moe_dropped_fraction",
                     stats.dropped_fraction)
            self.sow("intermediates", "moe_expert_load", stats.expert_load)
            # Differentiable Switch/GShard balance loss — added to the LM
            # loss by make_lm_train_step(moe_balance_weight=...).
            self.sow("intermediates", "moe_balance_loss", stats.balance_loss)
        else:
            y = dense_moe_reference(params, tokens)
        return y.reshape(b, s, d)


class _Kernel(nn.Module):
    """Declares a Dense-compatible ``kernel`` param WITHOUT the matmul —
    the injection seam for externally-computed linear layers (the
    overlapped FSDP MLP).  Named like the ``nn.Dense`` it replaces, the
    param path (``block_i/wi/kernel``) and init (lecun_normal, same rng
    fold — flax folds by path) are IDENTICAL to the dense twin, so
    checkpoints, sharding rules, and parity tests see one param tree
    regardless of which execution path runs."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape)


class Block(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    attention_fn: AttentionFn
    n_experts: int = 0  # 0 = dense FFN; >0 = MoE FFN with that many experts
    moe_fn: Optional[Callable] = None
    # Pluggable dense-FFN execution (the attention_fn pattern applied to
    # the MLP): ``mlp_fn(params, x) -> y`` with
    # ``params = {"wi": [d, ff], "wo": [ff, d]}`` kernels (cast to the
    # compute dtype) and ``x: [b, s, d]`` the post-LN activations;
    # the residual add stays here.  Param tree is identical to the
    # built-in wi/wo Dense pair (see _Kernel), so the two paths are
    # checkpoint/sharding-compatible and parity-testable.  Used by the
    # overlapped FSDP layer compute
    # (tpudist.parallel.fsdp.overlap_fsdp_mlp).  Mutually exclusive
    # with the MoE FFN.
    mlp_fn: Optional[Callable] = None
    dtype: jnp.dtype = jnp.float32  # compute dtype; params stay f32 masters
    rope: bool = False  # rotary q/k position encoding (no learned pos table)
    # Grouped-query attention: project K/V at this many heads (must divide
    # n_heads; None = n_heads = plain MHA).  Attention fns tagged
    # ``supports_gqa`` (the default flash path) consume grouped K/V
    # natively; others get K/V broadcast to full heads.  The decode cache
    # stores only n_kv_heads either way (the GQA memory win).
    n_kv_heads: Optional[int] = None
    # Sliding-window size for the DECODE cache mask (training-time
    # windowing lives in attention_fn — TransformerLM threads both).
    sliding_window: Optional[int] = None
    # Autoregressive decode mode: single-token inputs attend over a
    # ``max_len`` K/V cache carried in the flax "cache" collection.
    decode: bool = False
    max_len: int = 2048  # cache length (decode only)
    # Per-slot parameter indirection (per-tenant adapters,
    # tpudist.models.lora): rank of the LoRA factor pairs applied
    # around the qkv/wi/wo projections.  0 = the seam is compiled out
    # (byte-identical program to the pre-adapter Block).  > 0: apply()
    # must supply an "adapters" collection — per layer the factor
    # leaves {a_qkv, b_qkv, a_wi, b_wi, a_wo, b_wo} plus the ``on``
    # mask (scalar for one lane, [batch] for a slot-batched program);
    # the projection output becomes ``where(on, y + (x·A)·B, y)`` — a
    # SELECT, so an off lane is bit-exact base.  The same seam later
    # serves multi-model and MoE routing: anything per-slot that picks
    # parameters rides in as gathered data, never as a new program.
    lora_rank: int = 0
    # Decode-attention execution (decode mode only) — the third arm of
    # the attention dispatch (reference / flash are the training arms):
    #   None      — the dense cached softmax below (the gather path:
    #               a paged engine gathers its pool to a dense view
    #               first, a dense engine owns the arena outright);
    #   "paged"   — the Pallas paged-attention kernel
    #               (tpudist.ops.paged_attention): the block table is
    #               walked INSIDE the kernel, so only live blocks are
    #               fetched.  The cache collection then carries a small
    #               WINDOW buffer instead of a [max_len] arena, and the
    #               block pool rides in through the read-only "pool"
    #               collection ({pk, pv, sk, sv, table, pos0} per
    #               layer) — built by the slot-decode programs
    #               (tpudist.models.generate), never flax-initialized.
    #   "paged_prefill" — the Pallas paged-PREFILL kernel
    #               (tpudist.ops.paged_prefill): multi-token chunks per
    #               slot attend over the pool prefix AND emit their
    #               quantized KV block writes in-kernel, sown into a
    #               "pwrites" collection the slot-decode program commits
    #               (no dense lane view, no sequential teacher-force).
    decode_kernel: Optional[str] = None
    # static layer index into the [L, ...] pool (decode_kernel only)
    layer_idx: int = 0
    # Fused RoPE+QKV projection (tpudist.ops.fused_linear.fused_rope_qkv)
    # on the paged decode/prefill arms: the qkv matmul, head split, and
    # rotary rotation run as one kernel on the per-slot cursor vector;
    # the attention arm receives q/k already rotated.  Param tree is
    # unchanged (_Kernel declares the same qkv/kernel param).
    fused_rope: bool = False
    # In-kernel LoRA gather-matmul (tpudist.ops.fused_linear.lora_delta):
    # the "adapters" collection carries the FULL factor pools plus the
    # per-slot ``ids`` vector (tpudist.models.lora.pool_collection)
    # instead of pre-gathered factors — each slot's grid step DMAs only
    # its own factor block.  Batched slot programs only (the vmapped
    # gather path keeps gather_collection).
    lora_kernel: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dh = self.d_model // self.n_heads
        n_kv = self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        if not 1 <= n_kv <= self.n_heads or self.n_heads % n_kv:
            raise ValueError(
                f"n_kv_heads {n_kv} must be in [1, {self.n_heads}] and "
                f"divide n_heads {self.n_heads}")
        kv_dim = n_kv * dh
        # -- per-tenant adapter seam (lora_rank > 0): the gathered
        # factor collection rides in through apply() like the paged
        # kernel's pool — never flax-initialized (is_initializing skips
        # it: the seam adds no params and no cache).
        ad = None
        if self.lora_rank > 0 and not self.is_initializing():
            if self.n_experts > 0 or self.mlp_fn is not None:
                raise ValueError(
                    "lora_rank adapters wrap the plain qkv/wi/wo Dense "
                    "path; they cannot compose with an MoE FFN or an "
                    "injected mlp_fn (the fused MLP hides the wi/wo seam)")
            keys = ("a_qkv", "b_qkv", "a_wi", "b_wi", "a_wo", "b_wo", "on")
            if self.lora_kernel:
                keys = keys + ("ids",)   # pool form: full pools + ids
            ad = {k: self.get_variable("adapters", k) for k in keys}
            if ad["a_qkv"] is None:
                raise ValueError(
                    "lora_rank > 0 requires an 'adapters' collection "
                    "(tpudist.models.lora.gather_collection / "
                    "adapter_collection) supplied with apply()")

        def _delta(h_in, a_key, b_key):
            """The raw adapter delta ``(h·A)·B`` — in-graph against the
            pre-gathered factors, or through the Pallas gather-matmul
            when lora_kernel (the pool rides whole; each slot's grid
            step DMAs only its own factor block)."""
            if self.lora_kernel:
                from tpudist.ops.fused_linear import lora_delta
                interpret = jax.devices()[0].platform != "tpu"
                return lora_delta(
                    h_in.astype(self.dtype), ad[a_key], ad[b_key],
                    ad["ids"], layer=self.layer_idx, interpret=interpret)
            a = ad[a_key].astype(self.dtype)
            bm = ad[b_key].astype(self.dtype)
            return (h_in.astype(self.dtype) @ a) @ bm

        def _ad(y, h_in, a_key, b_key):
            """``where(on, y + (h·A)·B, y)`` — the adapter delta as a
            SELECT: an off lane's output is the base tensor bit-exactly
            (clamped-gather garbage in A/B is deselected, the KV-mask
            discipline applied to parameters)."""
            if ad is None:
                return y
            delta = _delta(h_in, a_key, b_key)
            on = jnp.asarray(ad["on"])
            m = on.reshape(on.shape + (1,) * (y.ndim - on.ndim))
            return jnp.where(m, y + delta, y)

        # the two sublayers, each under its scope (tpudist.telemetry.names):
        # pre-LN through the residual add, so the transposes and copies
        # round the attention kernel read as ``attn`` in a device trace
        with jax.named_scope(names.ATTN):
            # LayerNorm statistics in f32 for stability; projections compute in
            # ``dtype`` (flax casts inputs + the f32 master params at apply).
            h = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)
            use_fused_qkv = self.fused_rope and not self.is_initializing()
            if use_fused_qkv and not (
                    self.decode
                    and self.decode_kernel in ("paged", "paged_prefill")):
                raise ValueError(
                    "fused_rope fuses the QKV projection with the per-slot "
                    "rope offsets of the paged decode/prefill arms — set "
                    "decode_kernel='paged'/'paged_prefill' (training and the "
                    "dense decode path keep the unfused projection)")
            rotated = False
            # The training arm hands an attention_fn that carries a
            # ``packed`` route (the default: make_length_aware_attention)
            # the projection's own [b, s, (n_heads + 2·n_kv)·dh] output and
            # takes [b, s, d] back: on TPU at dh % 128 == 0 the flash
            # kernels index that layout and nothing is transposed, sliced
            # or copied round them.  Decode, and an injected attention_fn
            # without the tag (ring attention, a user's own), get head-major
            # q, k, v [b, h, s, dh] as ever.
            packed = None if self.decode else getattr(
                self.attention_fn, "packed", None)
            if use_fused_qkv:
                from tpudist.ops.fused_linear import fused_rope_qkv
                # same qkv/kernel param as the Dense twin (_Kernel seam)
                w = _Kernel((self.d_model, self.d_model + 2 * kv_dim),
                            name="qkv")()
                if self.decode_kernel == "paged":
                    # absolute cursors
                    offs = self.get_variable("cache", "idx")
                else:
                    offs = self.get_variable("pool", "pos0")  # chunk starts
                extra = on = None
                if ad is not None:
                    extra = _delta(h, "a_qkv", "b_qkv")
                    on = jnp.asarray(ad["on"]).astype(jnp.int32)
                q, k, v = fused_rope_qkv(
                    h.astype(self.dtype), w.astype(self.dtype),
                    offs.astype(jnp.int32), extra, on,
                    n_heads=self.n_heads, n_kv=n_kv, dh=dh, rope=self.rope,
                    interpret=jax.devices()[0].platform != "tpu")
                rotated = True
            else:
                qkv = nn.Dense(self.d_model + 2 * kv_dim, use_bias=False,
                               name="qkv", dtype=self.dtype)(h)
                qkv = _ad(qkv, h, "a_qkv", "b_qkv")
                if packed is None:
                    q, k, v = split_heads(qkv, self.n_heads, n_kv)
            if self.decode:
                if self.decode_kernel == "paged":
                    attn = self._decode_attention_paged(q, k, v,
                                                        rotated=rotated)
                elif self.decode_kernel == "paged_prefill":
                    attn = self._prefill_attention_paged(q, k, v,
                                                         rotated=rotated)
                elif self.decode_kernel is not None:
                    raise ValueError(
                        f"unknown decode_kernel {self.decode_kernel!r} "
                        "(None = dense cached softmax, 'paged' = the Pallas "
                        "paged-attention decode kernel, 'paged_prefill' = "
                        "the Pallas paged-prefill kernel)")
                else:
                    attn = self._decode_attention(q, k, v)
                attn = merge_heads(attn)
            else:
                fn_window = getattr(self.attention_fn, "window", None)
                if (self.sliding_window is not None
                        and fn_window != self.sliding_window):
                    # sliding_window alone only masks the decode cache; a
                    # non-windowed attention_fn would train full-causal and
                    # decode windowed.  TransformerLM/pipeline_lm thread a
                    # matching windowed fn — raw Block users must too (fns
                    # built by make_length_aware_attention /
                    # make_ring_attention carry a ``window`` tag).
                    raise ValueError(
                        "Block.sliding_window is set but attention_fn is not "
                        "tagged with a matching window — inject an "
                        "attention_fn built with the same window (e.g. "
                        "make_length_aware_attention(window)), or tag a "
                        "custom fn with .window")
                if packed is not None:
                    if self.rope:
                        qkv = rope_rotate_packed(qkv, self.n_heads + n_kv, dh)
                    attn = packed(qkv, self.n_heads, n_kv)
                else:
                    telemetry.event(names.ATTN_LAYOUT,
                                    layout=names.HEAD_MAJOR,
                                    reason=names.WHY_CUSTOM_FN)
                    if self.rope:
                        q, k = rope_rotate(q), rope_rotate(k)
                    if n_kv != self.n_heads and not getattr(
                            self.attention_fn, "supports_gqa", False):
                        group = self.n_heads // n_kv
                        k = jnp.repeat(k, group, axis=1)
                        v = jnp.repeat(v, group, axis=1)
                    attn = merge_heads(self.attention_fn(q, k, v))
            x = x + nn.Dense(self.d_model, use_bias=False, name="proj",
                             dtype=self.dtype)(attn)

        with jax.named_scope(names.MLP):
            h = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)
            if self.n_experts > 0:
                if self.mlp_fn is not None:
                    raise ValueError(
                        "mlp_fn replaces the dense FFN; it cannot compose "
                        "with the MoE FFN (n_experts > 0)")
                return x + MoEFFN(self.d_model, self.d_ff, self.n_experts,
                                  self.moe_fn, dtype=self.dtype, name="moe")(h)
            if self.mlp_fn is not None:
                wi = _Kernel((self.d_model, self.d_ff), name="wi")()
                wo = _Kernel((self.d_ff, self.d_model), name="wo")()
                # Same mixed-precision contract as the Dense twins: f32
                # master kernels cast to the compute dtype at apply.
                y = self.mlp_fn(
                    {"wi": wi.astype(self.dtype), "wo": wo.astype(self.dtype)},
                    h.astype(self.dtype))
                return x + y
            hin = h
            h = nn.Dense(self.d_ff, use_bias=False, name="wi",
                         dtype=self.dtype)(hin)
            h = _ad(h, hin, "a_wi", "b_wi")
            h = nn.gelu(h)
            y = nn.Dense(self.d_model, use_bias=False, name="wo",
                         dtype=self.dtype)(h)
            return x + _ad(y, h, "a_wo", "b_wo")

    def _decode_attention(self, q, k, v):
        """Cached attention over a decode WINDOW of ``s >= 1`` tokens:
        write the window's K/V at the cache cursor, attend each query
        causally over the filled prefix plus the window tokens before it
        (per-query mask row ``arange(max_len) <= pos + i``).  ``s == 1``
        is the classic single-token decode step; ``s > 1`` is the
        speculative-decoding verify pass — K+1 drafted tokens scored
        against the cache in ONE forward, so the weights and the KV
        arena stream once per window instead of once per token (the
        fewer-HBM-sweeps-per-token lever the decode roofline left).
        Static shapes ([max_len] cache, masks instead of slicing) keep
        every window size one compiled program.  The cache is sized by
        the K/V head count — GQA models pay n_kv_heads/n_heads of the
        MHA cache."""
        b, nh, s, dh = q.shape
        n_kv = k.shape[1]
        ck = self.variable("cache", "k", jnp.zeros,
                           (b, n_kv, self.max_len, dh), self.dtype)
        cv = self.variable("cache", "v", jnp.zeros,
                           (b, n_kv, self.max_len, dh), self.dtype)
        ci = self.variable("cache", "idx",
                           lambda: jnp.zeros((), jnp.int32))
        pos = ci.value
        if self.rope:
            q = rope_rotate(q, offset=pos)
            k = rope_rotate(k, offset=pos)
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k.astype(self.dtype), (0, 0, pos, 0))
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v.astype(self.dtype), (0, 0, pos, 0))
        ci.value = pos + s
        scale = dh ** -0.5
        # grouped einsums read the un-repeated cache directly — per-step
        # bandwidth scales with n_kv_heads, the actual GQA win
        group = nh // n_kv
        qg = q.reshape(b, n_kv, group, s, dh)
        scores = jnp.einsum("bngqd,bnkd->bngqk", qg, ck.value,
                            preferred_element_type=jnp.float32) * scale
        # per-query causal rows: window token i sees cache <= pos + i
        qpos = pos + jnp.arange(s)
        live = jnp.arange(self.max_len)[None, :] <= qpos[:, None]
        if self.sliding_window is not None:
            live &= (jnp.arange(self.max_len)[None, :]
                     > qpos[:, None] - self.sliding_window)
        scores = jnp.where(live[None, None, None, :, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bngqk,bnkd->bngqd", w.astype(self.dtype), cv.value,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, nh, s, dh).astype(q.dtype)

    def _decode_attention_paged(self, q, k, v, rotated=False):
        """Cached decode attention through the Pallas paged-attention
        kernel (:func:`tpudist.ops.paged_attention`): the KV pool stays
        paged — the kernel walks this slot batch's block tables in its
        grid and fetches only live blocks, so no dense ``[max_len]``
        view is ever materialized and bytes/token track live KV.

        Runs BATCHED over the slot axis (``b = num_slots``), not
        vmapped like the gather path: the kernel's grid covers every
        slot in one call, so per-slot cursors ride in as vectors.  The
        cache collection carries, per layer, a small decode-WINDOW
        buffer (``k``/``v`` ``[b, n_kv, W, dh]`` — this dispatch's
        uncommitted tokens; the slot-decode program commits them to the
        pool post-scan) and the per-slot absolute cursor ``idx [b]``;
        the pool itself ({pk, pv, sk, sv, table, pos0}) rides in
        read-only through the "pool" collection.  The same per-query
        causal window mask serves s == 1 decode and the s == K+1
        speculative verify pass (it is fused into the kernel)."""
        b, nh, s, dh = q.shape

        def _missing():
            raise ValueError(
                "decode_kernel='paged' caches are window views built by "
                "the slot-decode programs (tpudist.models.generate.make_"
                "slot_decode(attn_kernel='paged')) — they are supplied "
                "with apply(), never flax-initialized")

        pool_k = self.get_variable("pool", "pk")
        pool_v = self.get_variable("pool", "pv")
        scale_k = self.get_variable("pool", "sk")
        scale_v = self.get_variable("pool", "sv")
        table = self.get_variable("pool", "table")
        pos0 = self.get_variable("pool", "pos0")
        if pool_k is None:
            _missing()
        ck = self.variable("cache", "k", _missing)
        cv = self.variable("cache", "v", _missing)
        ci = self.variable("cache", "idx", _missing)
        pos = ci.value                      # [b] absolute cursors
        fill = (pos - pos0).astype(jnp.int32)   # window tokens already in
        if self.rope and not rotated:
            q = rope_rotate(q, offset=pos)
            k = rope_rotate(k, offset=pos)
        # append this call's K/V at each lane's window offset (the
        # kernel consumes them as the walk's final virtual block)
        ck.value = jax.vmap(
            lambda buf, kk, f: jax.lax.dynamic_update_slice(
                buf, kk, (0, f, 0)))(ck.value, k.astype(self.dtype), fill)
        cv.value = jax.vmap(
            lambda buf, vv, f: jax.lax.dynamic_update_slice(
                buf, vv, (0, f, 0)))(cv.value, v.astype(self.dtype), fill)
        ci.value = pos + s
        from tpudist.ops.paged_attention import paged_attention

        # interpret mode = the tier-1 CPU path (the flash-kernel rule)
        interpret = jax.devices()[0].platform != "tpu"
        return paged_attention(
            q, pool_k, pool_v, scale_k, scale_v, table,
            pos0.astype(jnp.int32), fill, ck.value, cv.value,
            layer=self.layer_idx, window=self.sliding_window,
            interpret=interpret)

    def _prefill_attention_paged(self, q, k, v, rotated=False):
        """Chunked-prefill attention through the Pallas paged-PREFILL
        kernel (:func:`tpudist.ops.paged_prefill`): every slot's
        multi-token chunk attends over its pool prefix (walked in-kernel
        via the block table) plus itself (causal), and the blocks the
        chunk touches are quantized and emitted IN-KERNEL — sown into
        the mutable "pwrites" collection (per layer: ``k``/``v``
        ``[S, Mw, n_kv, bs, dh]`` storage dtype + ``sk``/``sv``
        ``[S, Mw, n_kv]`` scales) for the slot-decode program to
        scatter with ``_Paged.commit_quantized``.  No dense lane view,
        no sequential teacher-force scan: the whole admission batch
        prefills in one dispatch per layer.

        The pool collection carries two extra leaves next to the decode
        arm's: ``clen [S]`` (each lane's live chunk length) and
        ``wtable [S, Mw]`` (the touched blocks' physical ids, sentinel
        past the lane's span — ``_Paged.write_tables``)."""

        def _missing():
            raise ValueError(
                "decode_kernel='paged_prefill' pools are built by the "
                "slot-decode prefill programs (tpudist.models.generate."
                "make_slot_decode(prefill_kernel=True)) — they are "
                "supplied with apply(), never flax-initialized")

        pool_k = self.get_variable("pool", "pk")
        pool_v = self.get_variable("pool", "pv")
        scale_k = self.get_variable("pool", "sk")
        scale_v = self.get_variable("pool", "sv")
        table = self.get_variable("pool", "table")
        pos0 = self.get_variable("pool", "pos0")
        clen = self.get_variable("pool", "clen")
        wtable = self.get_variable("pool", "wtable")
        if pool_k is None or wtable is None:
            _missing()
        if self.rope and not rotated:
            q = rope_rotate(q, offset=pos0)
            k = rope_rotate(k, offset=pos0)
        from tpudist.ops.paged_prefill import paged_prefill_attention

        interpret = jax.devices()[0].platform != "tpu"
        o, qk, qv, sk, sv = paged_prefill_attention(
            q, k.astype(self.dtype), v.astype(self.dtype),
            pool_k, pool_v, scale_k, scale_v, table, wtable,
            pos0.astype(jnp.int32), clen.astype(jnp.int32),
            layer=self.layer_idx, window=self.sliding_window,
            interpret=interpret)
        self.put_variable("pwrites", "k", qk)
        self.put_variable("pwrites", "v", qv)
        self.put_variable("pwrites", "sk", sk)
        self.put_variable("pwrites", "sv", sv)
        return o


def remat_module(cls, policy: str, keep: tuple = ()):
    """``cls`` rematerialised in the backward pass (``nn.remat``), keeping
    what the named policy keeps (the names of ``TransformerLM.remat_policy``,
    which every decoder of ``tpudist.models`` takes) and, besides, the
    activations the module names ``keep``
    (``jax.ad_checkpoint.checkpoint_name``).  The module takes only the
    activation, so nothing is static."""
    policies = {
        "nothing": None,  # save only block boundaries
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if policy not in policies:
        raise ValueError(
            f"remat_policy must be one of {sorted(policies)}, "
            f"got {policy!r}")
    pol = policies[policy]
    if keep:
        named = jax.checkpoint_policies.save_only_these_names(*keep)
        pol = named if pol is None else (
            jax.checkpoint_policies.save_from_both_policies(pol, named))
    return nn.remat(cls) if pol is None else nn.remat(cls, policy=pol)


class TransformerLM(nn.Module):
    """Causal LM: token + learned position embeddings, N pre-LN blocks,
    tied-free output head."""

    vocab: int = 256
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 512
    max_len: int = 2048
    attention_fn: Optional[AttentionFn] = None
    n_experts: int = 0  # >0: MoE FFN in every block (expert parallelism)
    moe_fn: Optional[Callable] = None
    # Pluggable dense-FFN execution in every block (see Block.mlp_fn) —
    # e.g. the overlapped FSDP MLP (parallel/fsdp.py overlap_fsdp_mlp).
    mlp_fn: Optional[Callable] = None
    # Compute dtype.  bf16 = mixed precision: f32 master params (flax
    # param_dtype default) cast to bf16 at apply, matmuls at bf16 MXU
    # throughput, f32 LayerNorm/softmax/loss — grads land f32 for the
    # optimizer.  The Lightning ``precision=`` analog for the LM family.
    dtype: jnp.dtype = jnp.float32
    # Rotary position encoding on q/k instead of the learned position
    # table — length-extrapolating, the modern long-context default.
    rope: bool = False
    # Grouped-query attention (Llama-2/Mistral style): K/V heads shared by
    # groups of query heads; halves-or-better the decode KV cache.
    n_kv_heads: Optional[int] = None  # None = n_heads (MHA)
    # Sliding-window (local) attention: each token attends to the previous
    # ``sliding_window`` positions only (Mistral-style).  Ignored when a
    # custom attention_fn is injected (compose the window there).
    sliding_window: Optional[int] = None
    # KV-cache decode mode (see tpudist.models.generate): one token per
    # call, positions tracked in the flax "cache" collection.
    decode: bool = False
    # Decode-attention arm (see Block.decode_kernel): None = dense
    # cached softmax over a gathered/dense arena, "paged" = the Pallas
    # paged-attention kernel walking the block pool in place (the
    # slot-batched path — cursors become [batch] vectors).
    decode_kernel: Optional[str] = None
    # Per-tenant adapter seam in every block (see Block.lora_rank):
    # 0 compiles it out; > 0 makes apply() take an "adapters"
    # collection of gathered rank-r LoRA factors (tpudist.models.lora).
    lora_rank: int = 0
    # Fused RoPE+QKV projection on the paged decode/prefill arms
    # (see Block.fused_rope).
    fused_rope: bool = False
    # In-kernel LoRA gather-matmul (see Block.lora_kernel): the
    # "adapters" collection carries full pools + per-slot ids.
    lora_kernel: bool = False
    # Rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory drops from O(layers × per-block internals) to the
    # block boundaries, at ~1 extra forward of FLOPs — the lever that fits
    # d_model≥1024 configs in HBM.  Identical numerics (tests assert it).
    remat: bool = False
    # What the remat'd backward may keep (jax.checkpoint policies — the
    # memory/FLOPs dial between full remat and no remat):
    #   "nothing"  save only block boundaries (max memory savings, ~1
    #              extra forward of recompute) — the default;
    #   "dots"     save matmul outputs (jax.checkpoint_policies.
    #              checkpoint_dots): recompute only the cheap elementwise/
    #              norm chains — most of the memory win at a sliver of
    #              the recompute, usually the best MFU under mild
    #              memory pressure;
    #   "dots_no_batch"  save non-batch matmul outputs only (scan-
    #              friendly variant).
    remat_policy: str = "nothing"

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        """``tokens: [batch, seq] int32`` → logits ``[batch, seq, vocab]``.

        ``positions``: explicit ``[seq] int32`` position ids for the
        learned position table — for permuted sequence layouts (e.g. the
        zigzag causal-balanced ring, ``zigzag_indices``) where token
        order on device differs from temporal order.  Every non-attention
        sublayer is position-wise, so permuted tokens + matching position
        ids + a layout-aware ``attention_fn`` train identically to the
        natural order (tests assert it).  Unsupported with ``rope`` (the
        rotary path derives positions from array index; use the learned
        table for permuted layouts) and with ``decode``.
        """
        if positions is not None and (self.rope or self.decode):
            raise ValueError("explicit positions require the learned "
                             "position table in training mode "
                             "(rope=False, decode=False)")
        if positions is not None and self.attention_fn is None:
            # The default/windowed attention masks over ARRAY order; on a
            # permuted stream that attends temporally-future tokens with
            # no error and a decreasing loss.  Permuted layouts must
            # inject a layout-aware attention_fn (e.g. the zigzag ring).
            raise ValueError("explicit positions require a layout-aware "
                             "attention_fn (the built-in causal mask is "
                             "array-order)")
        if self.sliding_window is not None:
            if self.attention_fn is not None:
                raise ValueError(
                    "sliding_window with a custom attention_fn would window "
                    "decode but not training — compose the window inside "
                    "the injected attention_fn instead")
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got {self.sliding_window}")
        attn = self.attention_fn or (
            make_length_aware_attention(self.sliding_window)
            if self.sliding_window is not None else default_attention)
        seq = tokens.shape[1]
        with jax.named_scope(names.EMBED):
            x = nn.Embed(self.vocab, self.d_model, name="tok_embed",
                         dtype=self.dtype)(tokens)
            if not self.rope:
                if self.decode:
                    pi = self.variable("cache", "pos",
                                       lambda: jnp.zeros((), jnp.int32))
                    if pi.value.ndim:
                        # slot-batched paged-kernel decode: every lane sits
                        # at its own cursor, so positions are [batch, seq]
                        positions = (pi.value[:, None]
                                     + jnp.arange(seq, dtype=jnp.int32)[None])
                    else:
                        positions = pi.value + jnp.arange(seq, dtype=jnp.int32)
                    pi.value = pi.value + seq
                elif positions is None:
                    positions = jnp.arange(seq, dtype=jnp.int32)
                pos = nn.Embed(self.max_len, self.d_model, name="pos_embed",
                               dtype=self.dtype)(positions)
                x = x + (pos if pos.ndim == 3 else pos[None])
        block_cls = Block
        if self.remat and not self.decode:
            block_cls = remat_module(Block, self.remat_policy)
        for i in range(self.n_layers):
            x = block_cls(
                self.d_model, self.n_heads, self.d_ff, attn,
                n_experts=self.n_experts, moe_fn=self.moe_fn,
                mlp_fn=self.mlp_fn,
                dtype=self.dtype, rope=self.rope,
                n_kv_heads=self.n_kv_heads, decode=self.decode,
                max_len=self.max_len, sliding_window=self.sliding_window,
                decode_kernel=self.decode_kernel, layer_idx=i,
                lora_rank=self.lora_rank, fused_rope=self.fused_rope,
                lora_kernel=self.lora_kernel,
                name=f"block_{i}",
            )(x)
        with jax.named_scope(names.HEAD):
            x = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)
            return nn.Dense(self.vocab, use_bias=False, name="head",
                            dtype=self.dtype)(x)


def transformer_tp_sharding(mesh, tree, *, axis_name: str = "model"):
    """Megatron-style tensor-parallel layout for a TransformerLM state
    pytree (params or a whole ``ModelState`` including optimizer moments —
    matching is by path, and Adam's moments mirror the param tree).

    Per block: ``qkv`` column-split (attention heads land whole on each
    device), ``proj`` row-split, ``wi`` column-split, ``wo`` row-split; MoE
    expert stacks split on the expert axis; embeddings/norms/head
    replicated.  Under ``jit`` the XLA SPMD partitioner inserts the
    all-reduces these seams imply — the pjit-spec formulation of
    ``tpudist.parallel.tensor_parallel``, applied to the whole model.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    col = P(None, axis_name)
    row = P(axis_name, None)

    def spec_for(path) -> P:
        keys = [k for k in (getattr(e, "key", getattr(e, "name", None))
                            for e in path) if isinstance(k, str)]
        if "moe" in keys:
            if keys[-1] in ("w", "wo"):
                return P(axis_name)  # expert-stack leading axis
            return P()  # router replicated
        if "kernel" in keys:
            if "qkv" in keys or "wi" in keys:
                return col
            if "proj" in keys or "wo" in keys:
                return row
        return P()

    def shard_for(path, leaf):
        spec = spec_for(path)
        if getattr(leaf, "ndim", 0) < len(spec):
            spec = P()  # scalars/odd-rank leaves (e.g. Adam's count)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(shard_for, tree)


def create_transformer(
    rng: jax.Array,
    *,
    seq_len: int = 128,
    attention_fn: Optional[AttentionFn] = None,
    **kwargs,
):
    """Init a TransformerLM; returns ``(module, params)``.  Same same-rng
    cross-process replication contract as :func:`create_toy_model`.

    Init always runs through the dense attention twin: parameter shapes do
    not depend on the attention op, and a sharded ring op would reject the
    size-1 dummy batch (not divisible by the mesh's data axis).
    """
    module = TransformerLM(attention_fn=attention_fn, **kwargs)
    # Init always runs the dense/unsharded twins: moe_fn would demand a
    # mesh-divisible dummy batch, mlp_fn a mesh at init time — and
    # neither changes parameter shapes or paths (_Kernel mirrors the
    # Dense pair exactly), so params are identical either way.
    init_kwargs = {k: v for k, v in kwargs.items()
                   if k not in ("moe_fn", "mlp_fn")}
    init_module = TransformerLM(attention_fn=None, **init_kwargs)
    params = init_module.init(rng, jnp.zeros((1, seq_len), jnp.int32))
    return module, params


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy (mean over all predicted positions)."""
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def lm_loss_with_targets(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Cross entropy against EXPLICIT per-position targets; ``-1`` masks a
    position out (mean over unmasked).  For permuted sequence layouts
    (zigzag ring), where "next token" is not the array neighbor: compute
    targets in temporal order, permute them alongside the tokens, mask
    the final temporal position with ``-1``.  Identical to :func:`lm_loss`
    on natural order (tests assert it)."""
    valid = targets >= 0
    safe = jnp.where(valid, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)
