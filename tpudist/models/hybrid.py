"""A decoder LM whose layers follow a pattern of mixers (gated delta-rule
linear attention, Mamba-2 state-space mixers and softmax attention, causal
to everything or inside a sliding window) and a feed-forward arm: a routed
expert layer of which this device holds a share, or a dense gated
feed-forward, the same for every layer or said a layer.  A layer is a mixer
followed by the feed-forward arm, or ONE sublayer (a mixer, or the
feed-forward arm).

What :mod:`tpudist.models.transformer`'s ``Block`` (pre-LN, LayerNorm,
ungated GELU FFN) cannot say, by mechanism:

- a **layer pattern**: ``layer_types`` names each layer's kind
  (:data:`MIXERS`' keys; in a decoder of one-sublayer layers also
  ``names.EXPERT_LAYER``, a layer that is the feed-forward arm);
- **RMS norms** in float32, zero-centred (``x * rsqrt(mean(x^2) + eps) *
  (1 + w)``, ``w`` from 0) or plain (``... * w``, ``w`` from 1), **before**
  each sublayer (``x + f(norm(x))``) or **after** it (``x + norm(f(x))``);
- **gated softmax attention** (:class:`GatedAttention`): the query
  projection also gives a sigmoid gate on the attention's output, queries
  and keys are RMS-normed per head, rotary positions turn only the first
  ``rotary_dim`` of a head's dims, grouped key/value heads, a head width
  that is not ``d_model / n_heads``;
- **softmax attention behind one norm over all heads**
  (:class:`NormedAttention`): queries and keys RMS-normed with ONE statistic
  a token over every head's dims together, no gate, no rotary positions;
- **plain grouped-query softmax attention** (:class:`GroupedAttention`): no
  norm, no gate, no rotary positions, and a softmax scale of its own where
  the sizes name one (``softmax_scale``; ``head_dim ** -0.5`` otherwise);
- **two softmax kinds in one decoder**, ``full_attention`` and
  ``sliding_attention``, each with sizes of its own
  (:class:`SoftmaxSizes`: query heads held of how many, the sliding window,
  the rotary dims, base and YaRN constants), behind **a gate a head**
  (:class:`HeadGatedAttention`: a sigmoid of the gate's own projection of
  the layer's input, one number a head a token); a window layer's attention
  is ``tpudist.ops.attention``'s windowed instance, whose tiles the
  dispatch's table gives;
- **leading dense layers before the expert layers**: the feed-forward arm a
  layer (``HybridLM.feed_forwards``), each layer keeping under remat what
  its own arm names;
- **gated delta-rule linear attention** (:class:`GatedDeltaNet`):
  projections fused per key head or separate, a short depthwise causal
  convolution with SiLU over q, k, v, per-head decay, a write strength of
  ``beta_scale * sigmoid``, L2-normed queries and keys, the chunked scan of
  :mod:`tpudist.ops.gated_delta` (key and value widths may differ), an RMS
  norm gated by ``silu(z)``;
- **a delta rule whose decay is a number a CHANNEL**
  (:class:`KimiDeltaAttention`): a projection each for q, k and v, the same
  convolution and L2 norms, a forget gate ``-exp(A_log) * softplus(.)`` a
  channel of a head behind a two-step projection of low rank, the chunked
  scan at ``g [b, s, h, dk]`` (sub-blocks of 16, the gate held above -5 a
  position: :mod:`tpudist.ops.gated_delta`), an RMS norm a head gated by a
  SIGMOID behind another low-rank projection;
- **latent attention without positions** (:class:`LatentAttention`): keys
  and values projected out of one normed latent a token, a head's key its
  own part beside a part ALL heads share, nothing turned, the scores taken
  over both parts and the values narrower than the keys (192 on 128: the
  dispatch's head-major route, the flash kernels at a value width of their
  own); the keys are expanded to every head (training: no absorbed form);
- **a Mamba-2 state-space mixer** (:class:`Mamba2Mixer`): one input
  projection into a gate, heads, groups of ``B`` and ``C`` and a step a
  head, a depthwise causal convolution with bias and SiLU, the chunked scan
  of :mod:`tpudist.ops.ssd`, an RMS norm of ``y * silu(z)`` a GROUP, the
  group a member's own or one that the members of a head share read
  between them;
- **routed experts as a share** (:class:`ExpertShare`):
  :func:`tpudist.parallel.moe.expert_share`, dropless; softmax or sigmoid
  (with or without a choice bias) scoring with a scale, gated SiLU or
  squared-ReLU experts, at
  the model's width or in a latent space behind two projections, with a
  scored or plain shared expert; or a **dense gated feed-forward**
  (:class:`GatedMLP`);
- **a share of the heads**: the mixers are told how many heads of how many
  they hold (``n_heads`` of ``n_heads_total``, ...), as the expert layer is
  told its experts.  With ``heads_axis`` (a mapped axis over the members
  that share a layer) the one norm statistic that runs over all heads and
  the output projections' partial sums are reduced over it; without it a
  member's partial output goes on as it is;
- **scalar multipliers** on the embeddings (``embedding_scale``), on each
  sublayer's output before the residual add (``residual_scale``) and under
  the logits (``logits_divisor``), and **a tied head** (``tied_head``: the
  logits are the final norm's output against the embedding itself, one
  tensor with two gradient paths); each 1 / False where an architecture
  names none, and then no instruction.

Which arm a layer takes is data on :class:`HybridSizes`, filled in by
whoever builds the module; nothing here knows a model.  The six
architectures that run through it (``cellbench/archs``): ``qwen3_next``
(norms zero-centred and before the sublayer, :class:`GatedAttention`, fused
projections with ``nv / nk`` value heads a key head at 128 / 128, write
strength in ``[0, 1]``, :class:`ExpertShare` behind every mixer),
``olmo_hybrid`` (norms plain and after the sublayer,
:class:`NormedAttention`, separate projections at ``dk`` 96 / ``dv`` 192,
write strength in ``[0, 2]``, :class:`GatedMLP`, half of each mixer's heads
held) and ``nemotron_h`` (layers of one sublayer, norms plain and before
it, :class:`Mamba2Mixer` holding one WHOLE group of eight,
:class:`GroupedAttention` at 4 : 1, :class:`ExpertShare` with sigmoid +
bias scoring, a scale, squared-ReLU experts in a latent space and a plain
shared expert), ``laguna`` (norms plain and before the sublayers,
:class:`HeadGatedAttention` at two kinds: 24 query heads causal to
everything with YaRN on half of a head's dims, 36 inside a window of 512
with plain rotary positions on all of them, on the same 4 key/value heads,
half of each layer's heads held; a dense feed-forward in layer 0 and
:class:`ExpertShare` with sigmoid scoring, a scale, gated SiLU experts and a
plain shared expert behind the others) and ``granitemoehybrid`` (norms
plain and before the sublayers, :class:`Mamba2Mixer` holding half the heads
of the ONE group, whose ``B`` and ``C`` both members hold whole,
:class:`GroupedAttention` at 4 : 1 on 64-wide heads with a softmax scale of
1 / 64, a :class:`GatedMLP` behind every mixer whose products are NOT kept
under remat, the four multipliers and a tied head) and ``kimi_linear``
(norms plain and before the sublayers, :class:`KimiDeltaAttention` at 32
heads of 128 / 128 with gates of rank 128 three layers in four,
:class:`LatentAttention` at 32 heads of 128 + 64 on 128 out of a latent of
512 in the fourth, all heads held; a dense feed-forward in layer 0 and
:class:`ExpertShare` with sigmoid + bias scoring, a scale, gated SiLU
experts and a plain shared expert behind the others).

The embedding, the head (untied, or the embedding's own ``attend``), their
names and scopes, the loss the step
builders take (``lm_loss``) and the remat policy names are
``transformer``'s, imported; the causal attention itself is
``tpudist.ops.attention``'s length-aware dispatch (the flash kernels where
they run; one instance a window), the rotary angles and frequencies
``tpudist.ops.rope``'s.  Training only: no decode cache.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpudist import telemetry
from tpudist.models.transformer import remat_module
from tpudist.ops.attention import (attention_within, default_attention,
                                   merge_heads, route)
from tpudist.ops.gated_delta import (GATE_FLOOR, SUB_BLOCK,
                                     chunked_gated_delta_rule)
from tpudist.ops.rope import rope_angles_at, rope_inv_freq, yarn_inv_freq
from tpudist.ops.ssd import ssd_scan
from tpudist.parallel.moe import EXPERT_FNS, EXPERT_LEAVES, expert_share
from tpudist.telemetry import names


def _rms(x, eps, axis=None):
    """``x * rsqrt(mean(x^2) + eps)`` over the last axis in float32.  With
    ``axis`` the last axis is this member's equal slice of a vector that the
    members of that mapped axis hold between them, and the mean square runs
    over all of it: the sum of squares and the count are both reduced (one
    ``psum`` of a number a vector)."""
    x = x.astype(jnp.float32)
    if axis is None:
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    square = jax.lax.psum(jnp.sum(x * x, axis=-1, keepdims=True), axis)
    dims = x.shape[-1] * jax.lax.psum(1, axis)
    return x * jax.lax.rsqrt(square / dims + eps)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * (1 + scale)`` over the last axis, in
    float32; ``scale`` starts at 0."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        return _rms(x, self.eps) * (1.0 + scale)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, in float32;
    ``scale`` starts at 1.  With ``axis`` the last axis is this member's
    equal slice of a vector that the members of that mapped axis hold
    between them: the mean square is taken over all of it (one ``psum`` of
    a number a token) and ``scale`` is the slice's."""

    eps: float = 1e-6
    axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms(x, self.eps, self.axis) * scale


NORMS = {names.ZERO_CENTRED: ZeroCentredRMSNorm, names.PLAIN: RMSNorm}


def _dense(features: int, name: str, dtype):
    return nn.Dense(features, use_bias=False, name=name, dtype=dtype)


def rotate_partial(x, inv_freq, scale: float = 1.0):
    """Rotary positions on the first ``2 * len(inv_freq)`` dims of each head
    of ``x [b, s, heads, dh]`` (half-split pairing ``(i, i + half)``, angles
    ``position * inv_freq`` in f32, cos and sin times ``scale``); the other
    dims pass through."""
    half = inv_freq.shape[0]
    angles = rope_angles_at(0, x.shape[1], inv_freq)[:, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale != 1.0:
        sin, cos = scale * sin, scale * cos
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x1 * sin + x2 * cos).astype(x.dtype), x[..., 2 * half:]], axis=-1)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's constants (:func:`tpudist.ops.rope.yarn_inv_freq`) and
    ``scale``, what multiplies cos and sin with them."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class SoftmaxSizes:
    """One softmax-attention kind's own sizes (the head width is the
    decoder's): query heads HELD here of ``n_heads_total`` in all (None:
    all of them are here) with the key/value heads they read, the sliding
    window (None: causal to everything), and its rotary positions: the
    dims rotated, the base, and YaRN's constants where the frequencies are
    its."""

    n_heads: int
    n_kv_heads: int
    n_heads_total: Optional[int] = None
    window: Optional[int] = None
    rotary_dim: int = 0
    rope_theta: float = 1e4
    yarn: Optional[Yarn] = None

    def rotary(self):
        """``(inv_freq [rotary_dim / 2] f32, scale)``."""
        half = self.rotary_dim // 2
        if self.yarn is None:
            return rope_inv_freq(half, self.rope_theta), 1.0
        y = self.yarn
        return yarn_inv_freq(
            half, self.rope_theta, factor=y.factor,
            original_positions=y.original_positions, beta_fast=y.beta_fast,
            beta_slow=y.beta_slow), y.scale


@dataclasses.dataclass(frozen=True)
class HybridSizes:
    """The sizes of a pattern decoder, apart from depth and vocabulary, and
    which arm each part of a layer takes (``names.*``)."""

    d_model: int
    head_dim: int
    # softmax attention: heads HELD here, of ``n_heads_total`` query heads
    # in all (None: all of them are here)
    n_heads: Optional[int] = None
    n_kv_heads: Optional[int] = None
    rotary_dim: int = 0          # the gated attention's
    rope_theta: float = 1e7
    n_heads_total: Optional[int] = None
    attention: str = names.GATED_ATTN        # one of ATTENTIONS
    # what multiplies q . k before the softmax where it is not
    # ``head_dim ** -0.5`` (None); the grouped arm's alone
    softmax_scale: Optional[float] = None
    # the head-gated arm's sizes INSTEAD of the five above, which it does
    # not read: ((kind, SoftmaxSizes), ...), a softmax kind (names.FULL,
    # names.WINDOW) of the decoder's layers each
    softmax_kinds: tuple = ()
    # gated delta-rule linear attention: heads HELD, of
    # ``linear_value_heads_total`` value heads in all
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    linear_conv_width: int = 4
    linear_value_heads_total: Optional[int] = None
    linear_projections: str = names.FUSED    # or names.SEPARATE
    beta_scale: float = 1.0      # write strength = beta_scale * sigmoid(.)
    # the channel-gated delta-rule mixer reads ``linear_value_heads`` (its
    # heads: a key head a value head), the two widths, the convolution's
    # and this: the rank of its forget gate's and its output gate's
    # two-step projections
    linear_gate_rank: int = 0
    # latent attention (``n_heads`` of ``n_heads_total`` heads): the rank of
    # the latent that keys and values are projected out of, the two parts
    # of a head's query and key (its own, out of the latent; and the part
    # ALL heads share, projected beside the latent) and the values' width;
    # the scores are taken over both parts, ``sum(latent_key_dims)`` wide
    latent_rank: int = 0
    latent_key_dims: tuple = (0, 0)
    latent_value_dim: int = 0
    # state-space (Mamba-2) mixers: heads and groups HELD, of ``*_total`` in
    # all; a group's ``B`` and ``C`` serve ``ssm_heads / ssm_groups`` of the
    # heads held.  Where the members that share a layer outnumber the groups
    # (32 of 64 heads, 1 of 1 group) a group is held by every member that
    # holds some of its heads (:func:`ssm_group_members` of them)
    ssm_heads: int = 0
    ssm_groups: int = 1
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    ssm_heads_total: Optional[int] = None
    ssm_groups_total: Optional[int] = None
    # the mapped axis over the members that share a layer by heads, or None
    heads_axis: Optional[str] = None
    # norms: which, and on which side of the sublayer
    norm: str = names.ZERO_CENTRED           # or names.PLAIN
    norm_after: bool = False
    # a layer is a mixer AND the feed-forward arm, or (``one_sublayer``) ONE
    # sublayer: a mixer, or the feed-forward arm (``names.EXPERT_LAYER``)
    one_sublayer: bool = False
    # the feed-forward arm
    feed_forward: str = names.EXPERT_SHARE   # or names.DENSE_FFN
    ffn_width: int = 0           # the dense arm's
    # whether a rematerialised layer keeps the outputs of the dense arm's
    # three products (:func:`remat_keeps`), so that its forward runs once a
    # step, or (False) ``mixer_out`` alone and ``gate`` and ``up`` run again: a
    # choice by memory, ``tokens x (2 x ffn_width + d_model) x itemsize``
    # bytes a layer
    ffn_products_kept: bool = True
    # routed experts: ``n_experts`` is the router's width, ``held`` of them
    # (``first_expert`` on) live here
    n_experts: int = 8
    held: int = 8
    first_expert: int = 0
    top_k: int = 2
    expert_width: int = 512
    shared_width: int = 512
    # how the router scores (moe.SCORINGS) and what multiplies the picks'
    # weights; whether its weight trains (the gradient through the scores
    # into the tokens is computed either way)
    scoring: str = names.SOFTMAX
    routed_scale: float = 1.0
    router_trained: bool = True
    expert_fn: str = names.GATED_SILU        # one of moe.EXPERT_FNS
    # the routed experts' width of input and output where it is not
    # ``d_model``: two projections wrap them (router and shared expert read
    # the tokens at ``d_model``)
    latent_width: Optional[int] = None
    # the shared expert is ``sigmoid(x . score) * E(x)``, or ``E(x)`` as it is
    shared_scored: bool = True
    # scalar multipliers, each 1 where an architecture names none (and then
    # no instruction): on the embeddings, on each sublayer's output before
    # the residual add, and what the logits are divided by; and whether the
    # head is the embedding itself (``tok_embed``'s ``attend``: no ``head``
    # parameter, one tensor with two gradient paths)
    embedding_scale: float = 1.0
    residual_scale: float = 1.0
    logits_divisor: float = 1.0
    tied_head: bool = False
    eps: float = 1e-6

    def softmax(self, kind: str) -> SoftmaxSizes:
        """The head-gated arm's sizes of layers of ``kind``."""
        kinds = dict(self.softmax_kinds)
        if kind not in kinds:
            raise ValueError(
                f"softmax_kinds names {sorted(kinds)}, not {kind!r}: the "
                f"{names.HEAD_GATED_ATTN} arm takes a kind's sizes from it")
        return kinds[kind]


class GatedAttention(nn.Module):
    """Causal softmax attention with normed, partly rotated q and k and a
    sigmoid gate from the query projection on its output."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        h, kv, dh = z.n_heads, z.n_kv_heads, z.head_dim
        qg = _dense(h * 2 * dh, "q_proj", self.dtype)(x)
        qg = qg.reshape(b, s, h, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = _dense(kv * dh, "k_proj", self.dtype)(x).reshape(b, s, kv, dh)
        v = _dense(kv * dh, "v_proj", self.dtype)(x)
        q = ZeroCentredRMSNorm(z.eps, name="q_norm")(q).astype(self.dtype)
        k = ZeroCentredRMSNorm(z.eps, name="k_norm")(k).astype(self.dtype)
        q, k = (rotate_partial(t, rope_inv_freq(z.rotary_dim // 2,
                                                z.rope_theta)) for t in (q, k))
        # q's heads, k's, v's side by side: the layout the dispatch's packed
        # route (the flash kernels, at head_dim % 128 == 0) indexes itself
        qkv = jnp.concatenate(
            [q.reshape(b, s, h * dh), k.reshape(b, s, kv * dh), v], axis=-1)
        attn = default_attention.packed(qkv, h, kv)
        attn = attn * jax.nn.sigmoid(
            gate.reshape(b, s, h * dh).astype(jnp.float32)).astype(self.dtype)
        return _dense(d, "o_proj", self.dtype)(attn)


def _over_members(y, axis):
    """An output projection's partial sums, added up over the members that
    share the layer by heads."""
    return y if axis is None else jax.lax.psum(y, axis)


class NormedAttention(nn.Module):
    """Causal softmax attention whose queries and keys are RMS-normed with
    one statistic over ALL heads' dims together (``q_norm`` holds a weight
    a dim of every head); no gate and no rotary positions (``rotary_dim``
    is not read).  Holding ``n_heads`` of ``n_heads_total``, the statistic
    runs over the held heads' dims unless ``heads_axis`` reduces it (and
    ``o_proj``'s partial sums) over the members."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        h, kv, dh = z.n_heads, z.n_kv_heads, z.head_dim

        def normed(name, heads):
            t = _dense(heads * dh, f"{name}_proj", self.dtype)(x)
            return RMSNorm(z.eps, z.heads_axis,
                           name=f"{name}_norm")(t).astype(self.dtype)

        qkv = jnp.concatenate(
            [normed("q", h), normed("k", kv),
             _dense(kv * dh, "v_proj", self.dtype)(x)], axis=-1)
        attn = default_attention.packed(qkv, h, kv)
        return _over_members(_dense(d, "o_proj", self.dtype)(attn),
                             z.heads_axis)


class GroupedAttention(nn.Module):
    """Plain causal grouped-query softmax attention: no norm on queries or
    keys, no gate, no rotary positions (``rotary_dim`` is not read).  The
    scores are ``q . k * softmax_scale`` where the sizes name a scale: the
    attention itself multiplies by ``head_dim ** -0.5``, so the queries are
    multiplied by ``softmax_scale * sqrt(head_dim)`` first, in the compute
    dtype (exact where that is a power of two, as 1 / 64 at 64-wide heads
    is).  Holding ``n_heads`` query heads with the ``n_kv_heads`` they read,
    ``o_proj``'s partial sums are reduced over ``heads_axis`` where there
    is one."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        h, kv, dh = z.n_heads, z.n_kv_heads, z.head_dim
        q, k, v = (_dense(heads * dh, f"{name}_proj", self.dtype)(x)
                   for name, heads in (("q", h), ("k", kv), ("v", kv)))
        if z.softmax_scale is not None:
            q = q * (z.softmax_scale * dh ** 0.5)
        qkv = jnp.concatenate([q, k, v], axis=-1)
        attn = default_attention.packed(qkv, h, kv)
        return _over_members(_dense(x.shape[-1], "o_proj", self.dtype)(attn),
                             z.heads_axis)


class HeadGatedAttention(nn.Module):
    """Grouped-query softmax attention of layers of ``kind``, by that
    kind's own sizes (:meth:`HybridSizes.softmax`): causal to everything or
    inside its sliding window, rotary positions on the first ``rotary_dim``
    dims of a head at its own frequencies and scale, no q/k norm, and a
    gate a head: ``sigmoid`` in float32 of a projection of the layer's
    input (``g_proj``, one column a held head), multiplied into that head's
    attention output before ``o_proj``, under the scope
    ``names.HEAD_GATE``.  Holding ``n_heads`` query heads with the
    ``n_kv_heads`` they read, ``o_proj``'s partial sums are reduced over
    ``heads_axis`` where there is one; the gate is a head's own, so no
    statistic crosses the cut."""

    sizes: HybridSizes
    dtype: jnp.dtype
    kind: str = names.FULL

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z, a = self.sizes, self.sizes.softmax(self.kind)
        h, kv, dh = a.n_heads, a.n_kv_heads, z.head_dim
        q, k, v = (_dense(heads * dh, f"{name}_proj", self.dtype)(x)
                   for name, heads in (("q", h), ("k", kv), ("v", kv)))
        if a.rotary_dim:
            inv_freq, scale = a.rotary()
            q = rotate_partial(q.reshape(b, s, h, dh), inv_freq,
                               scale).reshape(b, s, h * dh)
            k = rotate_partial(k.reshape(b, s, kv, dh), inv_freq,
                               scale).reshape(b, s, kv * dh)
        attn = attention_within(a.window).packed(
            jnp.concatenate([q, k, v], axis=-1), h, kv)
        with jax.named_scope(names.HEAD_GATE):
            gate = jax.nn.sigmoid(
                _dense(h, "g_proj", self.dtype)(x).astype(jnp.float32))
            attn = (attn.reshape(b, s, h, dh) * gate[..., None]).astype(
                self.dtype).reshape(b, s, h * dh)
        return _over_members(_dense(d, "o_proj", self.dtype)(attn),
                             z.heads_axis)


#: ``HybridSizes.attention`` -> the softmax-attention arm: ``gated`` (per-head
#: q/k norms, a gate from the query projection, part rotary), ``normed`` (one
#: q/k norm statistic over all heads, no gate, no rotary), ``grouped`` (plain
#: grouped-query) and ``head_gated`` (a gate a head from its own projection;
#: heads, rotary positions and window by the layer's kind, from
#: ``HybridSizes.softmax_kinds``)
ATTENTIONS = {names.GATED_ATTN: GatedAttention,
              names.NORMED_ATTN: NormedAttention,
              names.GROUPED_ATTN: GroupedAttention,
              names.HEAD_GATED_ATTN: HeadGatedAttention}


def causal_depthwise_conv(x, kernel):
    """``y[t, c] = sum_j kernel[c, j] * x[t - (width - 1) + j, c]`` over
    ``x [b, s, c]``, zeros before position 0, no bias.  Products and sum
    in float32, the result in ``x``'s dtype: ``x`` is padded as it is, so
    that the taps, the sum and whatever elementwise follows are one pass
    over it."""
    width = kernel.shape[-1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s].astype(jnp.float32) * kernel[:, j]
               for j in range(width))


def _unit_heads(t, heads: int, width: int, dtype, scale: float = 1.0):
    """``t [b, s, heads * width]`` as ``[b, s, heads, width]``, L2-normed a
    head in float32 (times ``scale``), in ``dtype``."""
    t = t.reshape(*t.shape[:2], heads, width).astype(jnp.float32)
    t = t * (scale * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6))
    return t.astype(dtype)


#: positions a chunk of the delta rule's scan, and the kinds of layer whose
#: mixer runs it (and keeps its chunks' inverse under remat)
DELTA_CHUNK = 64
DELTA_RULE_KINDS = (names.LINEAR, names.CHANNEL_LINEAR)


class GatedDeltaNet(nn.Module):
    """The gated delta-rule linear-attention mixer, holding
    ``linear_key_heads`` / ``linear_value_heads`` heads (each of its
    projections the held heads' columns, ``out_proj`` their rows)."""

    sizes: HybridSizes
    dtype: jnp.dtype
    chunk: int = DELTA_CHUNK

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        nk, nv = z.linear_key_heads, z.linear_value_heads
        dk, dv = z.linear_key_dim, z.linear_value_dim
        r = nv // nk
        if z.linear_projections == names.FUSED:
            # laid out per key head: q, k (dk each), then v, z (r * dv each)
            qkvz = _dense(nk * (2 * dk + 2 * r * dv), "in_proj_qkvz",
                          self.dtype)(x).reshape(b, s, nk,
                                                 2 * dk + 2 * r * dv)
            ba = _dense(nk * 2 * r, "in_proj_ba", self.dtype)(x)
            ba = ba.reshape(b, s, nk, 2 * r).astype(jnp.float32)
            q, k, v, gate = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv],
                                      axis=-1)
        else:
            # a projection each, key head ``j // r`` serving value head ``j``
            q, k, v, gate = (
                _dense(width, f"{name}_proj", self.dtype)(x)
                for name, width in (("q", nk * dk), ("k", nk * dk),
                                    ("v", nv * dv), ("g", nv * dv)))
            # b and a side by side per key head, as the fused one has them
            ba = jnp.concatenate(
                [_dense(nv, f"{name}_proj", self.dtype)(x).reshape(
                    b, s, nk, r) for name in "ba"],
                axis=-1).astype(jnp.float32)
        mixed = jnp.concatenate([q.reshape(b, s, nk * dk),
                                 k.reshape(b, s, nk * dk),
                                 v.reshape(b, s, nv * dv)], axis=-1)
        kernel = self.param("conv", nn.initializers.lecun_normal(),
                            (mixed.shape[-1], z.linear_conv_width))
        mixed = jax.nn.silu(causal_depthwise_conv(mixed, kernel)).astype(
            self.dtype)
        q, k, v = jnp.split(mixed, [nk * dk, 2 * nk * dk], axis=-1)
        a_log = self.param("A_log", nn.initializers.zeros, (nv,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (nv,))
        beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, nv))
        beyond_one = {}
        if z.beta_scale != 1.0:
            # the scan is told, for its inverse, that beta passes 1 (and
            # only then: whatever stands in for the scan in a test of the
            # other arm takes no such argument)
            beta = z.beta_scale * beta
            beyond_one = {"beta_max": z.beta_scale}
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., r:].reshape(b, s, nv) + dt_bias)

        def unit(t, scale=1.0):
            """L2-normed per head, each key head serving r value heads."""
            return jnp.repeat(_unit_heads(t, nk, dk, self.dtype, scale), r,
                              axis=2)

        o = chunked_gated_delta_rule(
            unit(q, dk ** -0.5), unit(k), v.reshape(b, s, nv, dv), g, beta,
            chunk=self.chunk, **beyond_one)
        norm = self.param("norm", nn.initializers.ones, (dv,))
        o = norm * _rms(o, z.eps) * jax.nn.silu(
            gate.reshape(b, s, nv, dv).astype(jnp.float32))
        return _over_members(_dense(d, "out_proj", self.dtype)(
            o.reshape(b, s, nv * dv).astype(self.dtype)), z.heads_axis)


class KimiDeltaAttention(nn.Module):
    """The delta-rule mixer whose decay is a number a CHANNEL (Kimi Delta
    Attention), holding ``linear_value_heads`` heads of ``linear_key_dim``
    / ``linear_value_dim`` (each projection the held heads' columns,
    ``o_proj`` their rows; its partial sums reduced over ``heads_axis``
    where there is one): ``q, k, v`` a projection each, a depthwise causal
    convolution with SiLU over the three, queries and keys L2-normed a head
    (queries times ``dk ** -0.5``); the forget gate ``g = -exp(A_log[head])
    * softplus(f_b(f_a(x)) + dt_bias)`` in float32, a number a channel
    behind a projection of rank ``linear_gate_rank``; the write strength
    ``sigmoid(b_proj(x))`` a head; :func:`chunked_gated_delta_rule` at
    ``g [b, s, h, dk]``; and ``o_proj(norm * rms(o) * sigmoid(g_b(g_a(x))))``,
    the RMS over a head's ``dv`` and the gate a SIGMOID behind a projection
    of the same rank.  The gates' projections, softplus, ``exp(A_log)`` and
    the gate's product run under ``names.KDA_GATE``: what this mixer adds
    to :class:`GatedDeltaNet`.  The three projections' outputs are named
    (``names.KDA_KEEPS``) for a rematerialised layer to keep
    (:func:`remat_keeps`): ``tokens x heads x (2 x dk + dv) x itemsize``
    bytes a layer."""

    sizes: HybridSizes
    dtype: jnp.dtype
    chunk: int = DELTA_CHUNK

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        h, dk, dv = z.linear_value_heads, z.linear_key_dim, z.linear_value_dim
        rank = z.linear_gate_rank
        telemetry.event(
            names.MIXER_LAYOUT, decay=names.CHANNEL,
            heads=[h, z.linear_value_heads_total or h], dk=dk, dv=dv,
            chunk=self.chunk, sub_block=SUB_BLOCK, gate_floor=GATE_FLOOR,
            gate_rank=rank)
        mixed = jnp.concatenate(
            [checkpoint_name(_dense(h * width, f"{name}_proj", self.dtype)(x),
                             kept)
             for name, width, kept in (("q", dk, names.KDA_Q),
                                       ("k", dk, names.KDA_K),
                                       ("v", dv, names.KDA_V))], axis=-1)
        kernel = self.param("conv", nn.initializers.lecun_normal(),
                            (mixed.shape[-1], z.linear_conv_width))
        mixed = jax.nn.silu(causal_depthwise_conv(mixed, kernel)).astype(
            self.dtype)
        q, k, v = jnp.split(mixed, [h * dk, 2 * h * dk], axis=-1)
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h * dk,))

        def low_rank(name, width):
            return _dense(width, f"{name}_b_proj", self.dtype)(
                _dense(rank, f"{name}_a_proj", self.dtype)(x))

        with jax.named_scope(names.KDA_GATE):
            f = low_rank("f", h * dk).astype(jnp.float32) + dt_bias
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f.reshape(b, s, h, dk))
        beta = jax.nn.sigmoid(
            _dense(h, "b_proj", self.dtype)(x).astype(jnp.float32))

        o = chunked_gated_delta_rule(
            _unit_heads(q, h, dk, self.dtype, dk ** -0.5),
            _unit_heads(k, h, dk, self.dtype), v.reshape(b, s, h, dv), g,
            beta, chunk=self.chunk)
        norm = self.param("norm", nn.initializers.ones, (dv,))
        with jax.named_scope(names.KDA_GATE):
            gate = jax.nn.sigmoid(low_rank("g", h * dv).astype(jnp.float32))
            o = norm * _rms(o, z.eps) * gate.reshape(b, s, h, dv)
        return _over_members(_dense(d, "o_proj", self.dtype)(
            o.reshape(b, s, h * dv).astype(self.dtype)), z.heads_axis)


class LatentAttention(nn.Module):
    """Causal softmax attention whose keys and values come out of a latent
    (multi-head latent attention), without positions, holding ``n_heads``
    heads: ``q = q_proj(x)``, a head's ``own + shared`` dims
    (``latent_key_dims``); ``kv_a_proj(x)`` gives the latent
    (``latent_rank``) and ONE key part that all heads share; ``kv_b_proj``
    of the RMS-normed latent gives each held head its own key part and its
    values (``latent_value_dim``); head ``h``'s key is ``[own_h | shared]``,
    the shared part as it is (nothing is turned: no rotary positions); the
    scores are ``q . k / sqrt(own + shared)`` and the values narrower than
    the keys, which the dispatch's head-major route and the flash kernels
    take as they are (``ops/flash_attention.py``: values of a width of
    their own); ``o_proj`` reads the heads' values side by side, its
    partial sums reduced over ``heads_axis`` where there is one.  The keys
    are expanded to every head for training; the form that absorbs
    ``kv_b_proj`` into the queries is a decoder's and is not here.  What
    stands round the kernels besides ``q_proj`` and ``o_proj`` runs under
    ``names.LATENT_KV``."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        h, dv = z.n_heads, z.latent_value_dim
        own, shared = z.latent_key_dims
        r = route(jax.devices()[0].device_kind, s, own + shared)
        telemetry.event(
            names.ATTN_LAYOUT, layout=names.HEAD_MAJOR,
            reason=r.why_not or names.WHY_WIDTHS, kernel=r.kernel,
            qk_dim=own + shared, v_dim=dv, latent_rank=z.latent_rank)
        q = _dense(h * (own + shared), "q_proj", self.dtype)(x)
        with jax.named_scope(names.LATENT_KV):
            latent, k_shared = jnp.split(
                _dense(z.latent_rank + shared, "kv_a_proj", self.dtype)(x),
                [z.latent_rank], axis=-1)
            latent = RMSNorm(z.eps, name="kv_norm")(latent).astype(self.dtype)
            kv = _dense(h * (own + dv), "kv_b_proj", self.dtype)(
                latent).reshape(b, s, h, own + dv)
            k = jnp.concatenate(
                [kv[..., :own], jnp.broadcast_to(
                    k_shared[:, :, None], (b, s, h, shared))], axis=-1)
            v = kv[..., own:]
        # head-major: a head's 192 dims are not whole lane tiles
        heads_first = lambda t: t.transpose(0, 2, 1, 3)
        attn = default_attention(
            heads_first(q.reshape(b, s, h, own + shared)), heads_first(k),
            heads_first(v))
        return _over_members(_dense(d, "o_proj", self.dtype)(
            merge_heads(attn)), z.heads_axis)


def ssm_group_members(sizes: HybridSizes) -> int:
    """How many of the members that share a layer by heads hold some heads
    of one state-space group, and so its ``B`` and ``C``: 1 where a member
    holds whole groups (16 of 128 heads in 1 of 8 groups), the members
    themselves where they outnumber the groups and every member holds every
    group (32 of 64 heads, the 1 group of 1: 2).  Anything between (a group
    over some of the members) is not written down."""
    if not sizes.ssm_heads:
        return 1
    heads = sizes.ssm_heads_total or sizes.ssm_heads
    groups = sizes.ssm_groups_total or sizes.ssm_groups
    # a group's heads in all over those of them held here
    members, rest = divmod(heads * sizes.ssm_groups, groups * sizes.ssm_heads)
    if rest or (members > 1 and sizes.ssm_groups != groups):
        raise ValueError(
            f"{sizes.ssm_heads} of {heads} state-space heads in "
            f"{sizes.ssm_groups} of {groups} groups: a member holds whole "
            f"groups, or every member holds every group with an equal part "
            f"of its heads")
    return members


def ssm_norm_axis(sizes: HybridSizes) -> Optional[str]:
    """The mapped axis the gated norm's statistic is reduced over: the
    members' where they read one group between them, None where a member
    holds whole groups or there is no axis (the heads held, then)."""
    return sizes.heads_axis if ssm_group_members(sizes) > 1 else None


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space mixer, holding ``ssm_heads`` heads of
    ``ssm_groups`` groups: ``[z, xBC, dt] = in_proj(u)`` (the held heads'
    and groups' columns: ``z`` and ``x`` a head's ``ssm_head_dim`` each,
    ``B`` and ``C`` a group's ``ssm_state`` each, ``dt`` a number a head), a
    depthwise causal convolution with bias and SiLU over ``xBC`` (scope
    ``names.SSM_CONV``), ``dt = softplus(dt + dt_bias)``, the chunked scan
    of :mod:`tpudist.ops.ssd`, an RMS norm of ``y * silu(z)`` whose mean
    square runs over each GROUP's channels (scope ``names.SSM_NORM``), and
    ``out_proj`` (the held heads' rows; its partial sums reduced over
    ``heads_axis`` where there is one).

    A member either holds whole groups, and computes the norm alone, or
    (:func:`ssm_group_members` over 1) holds part of the heads of every
    group beside the other members: a group's ``B`` / ``C`` columns of
    ``in_proj``, conv channels and bias are then every member's, and the
    norm's mean square runs over the held heads' channels of the group or,
    given ``heads_axis``, over all the members' (sum of squares and count
    both reduced)."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        z = self.sizes
        h, g, p, n = z.ssm_heads, z.ssm_groups, z.ssm_head_dim, z.ssm_state
        inner, bc = h * p, g * n
        gate, mixed, dt = jnp.split(
            _dense(2 * inner + 2 * bc + h, "in_proj", self.dtype)(x),
            [inner, 2 * inner + 2 * bc], axis=-1)
        kernel = self.param("conv", nn.initializers.lecun_normal(),
                            (inner + 2 * bc, z.ssm_conv_width))
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (inner + 2 * bc,))
        with jax.named_scope(names.SSM_CONV):
            mixed = jax.nn.silu(causal_depthwise_conv(mixed, kernel)
                                + bias).astype(self.dtype)
        u, b_in, c_out = jnp.split(mixed, [inner, inner + bc], axis=-1)
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,))
        y = ssd_scan(u.reshape(b, s, h, p),
                     jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                     a_log, b_in.reshape(b, s, g, n),
                     c_out.reshape(b, s, g, n), skip, chunk=z.ssm_chunk)
        norm = self.param("norm", nn.initializers.ones, (inner,))
        with jax.named_scope(names.SSM_NORM):
            y = (y.reshape(b, s, inner).astype(jnp.float32)
                 * jax.nn.silu(gate.astype(jnp.float32)))
            y = _rms(y.reshape(b, s, g, inner // g), z.eps,
                     ssm_norm_axis(z)).reshape(b, s, inner) * norm
        return _over_members(_dense(d, "out_proj", self.dtype)(
            y.astype(self.dtype)), z.heads_axis)


class GatedMLP(nn.Module):
    """The dense feed-forward arm, ``down(silu(gate(x)) * up(x))`` without
    biases, under scope ``names.MLP``.  Its three products' outputs are
    named (``names.DENSE_FFN_KEEPS``): a rematerialised layer keeps them
    where the sizes say so (:func:`remat_keeps`), ``tokens x (2 x ffn_width
    + d_model) x itemsize`` bytes, and its backward pass then recomputes
    only ``silu(gate) * up``, one elementwise pass, and none of the
    products; where they are not kept the whole forward runs again."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.sizes.ffn_width
        with jax.named_scope(names.MLP):
            gate = checkpoint_name(_dense(w, "gate_proj", self.dtype)(x),
                                   names.FFN_GATE)
            up = checkpoint_name(_dense(w, "up_proj", self.dtype)(x),
                                 names.FFN_UP)
            return checkpoint_name(
                _dense(x.shape[-1], "down_proj", self.dtype)(
                    jax.nn.silu(gate) * up), names.FFN_OUT)


class ExpertShare(nn.Module):
    """The routed expert layer as this device's share
    (:func:`tpudist.parallel.moe.expert_share`), with its shared expert.
    By ``sizes``: how the router scores (``scoring``, ``routed_scale``; a
    ``choice_bias`` buffer where the scoring takes one: it steers the
    choice alone, so its gradient is zero and Adam leaves it where it
    was), what an expert computes (``expert_fn``), whether the routed
    experts work in a latent space of ``latent_width`` behind two
    projections (``latent_down`` before them, ``latent_up`` on their
    partial sum; router and shared expert read the tokens at ``d_model``),
    and whether the shared expert is scored.  The outputs of its dense
    products that the backward pass reads carry names for a rematerialised
    layer to keep (:func:`remat_keeps`): ``latent_down``'s, the unscored
    shared expert's first products' (named here, by the weight multiplied,
    and not in the expert function, which the grouped products run too),
    the router's logits (in ``expert_share``), and with them what the
    router decided, its picks and their scores (in ``route``)."""

    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        """``x [b, s, d]`` float32 (the norm's output): the router reads it
        as it is, the experts its rounding to the compute dtype."""
        b, s, d = x.shape
        z = self.sizes
        init = nn.initializers.lecun_normal()
        e, expert_fn = z.held, EXPERT_FNS[z.expert_fn]
        if z.shared_scored and z.latent_width:
            raise ValueError(
                "a scored shared expert reads the rows the routed experts "
                "read; behind latent projections those are latent_width wide")

        def weights(prefix, rows, width, lead=()):
            return {name: self.param(
                prefix + name, init,
                lead + ((width, rows) if name == "down" else (rows, width)))
                for name in EXPERT_LEAVES[z.expert_fn]}

        router = self.param("router", init, (d, z.n_experts))
        params = {
            "router": router if z.router_trained
            else jax.lax.stop_gradient(router),
            "experts": weights("", z.latent_width or d, z.expert_width, (e,)),
        }
        shared = weights("shared_", d, z.shared_width)
        if z.shared_scored:
            params["shared"] = {
                **shared, "score": self.param("shared_score", init, (d, 1))}
        if z.scoring == names.SIGMOID_BIAS:
            params["choice_bias"] = self.param(
                "choice_bias", nn.initializers.zeros, (z.n_experts,))
        rows = x.reshape(b * s, d)
        tokens = inside = rows.astype(self.dtype)
        if z.latent_width:
            with jax.named_scope(names.MOE), jax.named_scope(
                    names.LATENT_PROJ):
                inside = checkpoint_name(
                    _dense(z.latent_width, "latent_down", self.dtype)(tokens),
                    names.LATENT_IN)
        y, counts, windows, strips = expert_share(
            params, inside, n_experts=z.n_experts, held=e,
            first_expert=z.first_expert, k=z.top_k, expert_fn=expert_fn,
            router_input=rows, scoring=z.scoring, scale=z.routed_scale)
        with jax.named_scope(names.MOE):
            if z.latent_width:
                with jax.named_scope(names.LATENT_PROJ):
                    y = _dense(d, "latent_up", self.dtype)(y)
            if not z.shared_scored:
                with jax.named_scope(names.SHARED_EXPERT):
                    shared = jax.tree.map(lambda w: w.astype(self.dtype),
                                          shared)

                    def product(rows, w):
                        out = jnp.matmul(rows, w)
                        for leaf, name in names.SHARED_EXPERT_KEEPS.items():
                            if shared.get(leaf) is w:
                                return checkpoint_name(out, name)
                        return out

                    y = y + expert_fn(shared, tokens, product)
        # assignments per held expert: collected by train steps built with
        # ``aux=True`` (make_lm_train_step), one row a layer
        self.sow("intermediates", "moe_expert_tokens", counts)
        # and the windows each block of tokens took and the strips it
        # scattered (1 and 1 where the share keeps one buffer), one row a
        # layer each
        self.sow("intermediates", "moe_windows", windows)
        self.sow("intermediates", "moe_strips", strips)
        return y.reshape(b, s, d)


def _softmax(kind: str):
    """What builds the softmax attention of layers of ``kind``: the decoder's
    arm of :data:`ATTENTIONS`.  The head-gated arm alone goes by its layer's
    kind (sizes a kind, a window); the others attend causally to everything
    at the decoder's one set of sizes."""
    def build(sizes, dtype, name):
        if sizes.attention not in ATTENTIONS:
            raise ValueError(f"attention is {sizes.attention!r}; the softmax "
                             f"arm is one of {sorted(ATTENTIONS)}")
        if (sizes.softmax_scale is not None
                and sizes.attention != names.GROUPED_ATTN):
            raise ValueError(
                f"softmax_scale is read by the {names.GROUPED_ATTN!r} arm "
                f"alone: {sizes.attention!r} attends at head_dim ** -0.5")
        if sizes.attention == names.HEAD_GATED_ATTN:
            return HeadGatedAttention(sizes, dtype, kind, name=name)
        if kind != names.FULL:
            raise ValueError(
                f"a {kind!r} layer takes the {names.HEAD_GATED_ATTN!r} arm: "
                f"{sizes.attention!r} has no window")
        return ATTENTIONS[sizes.attention](sizes, dtype, name=name)

    return build


#: layer kind -> (the scope its sublayer runs under, the sublayer's name in
#: the parameter tree, what builds it): the mixers a layer can hold: gated
#: delta-rule linear attention, softmax attention causal to everything
#: (``full_attention``) or inside a sliding window (``sliding_attention``,
#: the head-gated arm's: the two may differ in every size of
#: :class:`SoftmaxSizes`), a Mamba-2 state-space mixer, a delta rule whose
#: decay is a number a channel and latent attention.  A
#: ``names.EXPERT_LAYER`` (one-sublayer layers only) holds the feed-forward
#: arm instead
MIXERS = {
    names.LINEAR: (names.LINEAR_ATTN, "linear_attn", GatedDeltaNet),
    names.FULL: (names.ATTN, "attn", _softmax(names.FULL)),
    names.WINDOW: (names.WINDOW_ATTN, "window_attn", _softmax(names.WINDOW)),
    names.STATE_SPACE: (names.SSM, "ssm", Mamba2Mixer),
    names.CHANNEL_LINEAR: (names.KDA, "kda", KimiDeltaAttention),
    names.LATENT: (names.LATENT_ATTN, "latent_attn", LatentAttention),
}


def layer_kinds(sizes: HybridSizes) -> tuple:
    """The kinds a layer of a decoder of these sizes can be."""
    return tuple(MIXERS) + ((names.EXPERT_LAYER,) if sizes.one_sublayer
                            else ())


class HybridLayer(nn.Module):
    """``h = x + Mixer(norm(x))``, ``y = h + FFN(norm(h))`` with the norms
    before the sublayers, ``h = x + norm(Mixer(x))``, ``y = h + norm(FFN(h))``
    with them after; what is added to ``x`` is multiplied by
    ``sizes.residual_scale`` first where that is not 1.  With
    ``sizes.one_sublayer`` a layer is the first of the two lines alone, its
    sublayer a mixer or (``names.EXPERT_LAYER``) the feed-forward arm."""

    kind: str
    sizes: HybridSizes
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        z = self.sizes

        def scaled(y):
            return y if z.residual_scale == 1.0 else z.residual_scale * y

        def residual(x, sublayer, norm_name):
            norm = NORMS[z.norm](z.eps, name=norm_name)
            if z.norm_after:
                return x + scaled(norm(sublayer(x)).astype(self.dtype))
            return x + scaled(sublayer(norm(x)))

        def feed_forward(x):
            # the expert layer names its own scope (``moe``), the dense one
            # ``mlp``
            ffn = (ExpertShare(z, self.dtype, name="experts")
                   if z.feed_forward == names.EXPERT_SHARE
                   else GatedMLP(z, self.dtype, name="mlp"))
            return residual(x, ffn, f"{ffn.name}_norm")

        if self.kind == names.EXPERT_LAYER:
            return feed_forward(x)
        scope, name, build = MIXERS[self.kind]
        mixer = build(z, self.dtype, name=name)
        with jax.named_scope(scope):
            x = residual(x, lambda h: mixer(h.astype(self.dtype)),
                         "mixer_norm")
        if z.one_sublayer:
            return x
        # kept under remat: the feed-forward's backward pass then needs
        # nothing of the mixer's, whose forward is recomputed after it
        return feed_forward(checkpoint_name(x, names.MIXER_OUT))


def remat_keeps(sizes: HybridSizes, kind: Optional[str] = None) -> tuple:
    """The names a rematerialised :class:`HybridLayer` keeps besides its
    input, under every policy: ``names.MIXER_OUT`` (the layer's activation
    between its mixer and its feed-forward arm, ``tokens x d_model x
    itemsize`` bytes), and in the dense arm, where ``ffn_products_kept``,
    the outputs of the feed-forward's three products (its input IS
    ``MIXER_OUT``), so that no product of the feed-forward runs twice a
    step; where they are not kept (a choice by memory: 3.0 GB over ten
    layers at 8,192 x 8,192, ROADMAP S26) ``MIXER_OUT`` alone, and each
    feed-forward's ``gate`` and ``up`` run again in the backward pass
    (``down``'s output is read by nothing there).  The expert-share arm of
    a two-sublayer layer recomputes its feed-forward's buffers (those of
    its dispatch are many times a layer's activations) but not its router:
    it keeps the router's logits and ``names.ROUTER_PICKS``, the picks and
    their scores (``tokens x top_k x 8`` bytes), so that the float32
    product, the sort over ``n_experts`` and the pick of the scores run
    once a step.  A layer of ``kind`` ``names.LINEAR`` or
    ``names.CHANNEL_LINEAR`` (a mixer that scans by the delta rule, a decay
    a head or a channel) keeps ``names.DELTA_INVERSE`` besides, the float32
    inverse ``T`` of each chunk (``tokens x heads x chunk x 4`` bytes: 134
    MB at 2 x 8,192 x 32 heads), which is all the inverse's own backward
    pass reads, so the rematerialised scan solves for nothing.  At a decay
    a HEAD (``names.LINEAR``) that is all: the rest of the mixer's forward
    (projections, convolution, norms, gates, the scan's products) runs
    again in the backward pass (its projections' outputs were measured and
    left out where they are narrow, and do not fit where they are fused:
    ROADMAP S18).  At a decay a CHANNEL (``names.CHANNEL_LINEAR``) the
    layer keeps ``names.KDA_KEEPS`` before the inverse, the outputs of
    ``q_proj``, ``k_proj`` and ``v_proj`` (``tokens x heads x (2 x dk +
    dv) x itemsize`` bytes: 201 MB at 8,192 x 32 x 128 in bf16, lane-dense
    columns), so those three products run once a step; the convolution,
    SiLU, L2 norms, the gates' low-rank products and the scan's own
    products run again from them.  No other mixer names anything
    of its own (a latent attention's and a state-space mixer's forward runs
    again whole), and no ``kind`` given says no mixer; what a delta-rule
    mixer holds meanwhile (the channel-gated scan's scaled keys, ``tokens x
    heads x 4 x dk x itemsize`` bytes, 268 MB at 8,192 x 32 x 128 in bf16)
    is the compiler's to place.

    A layer of one sublayer keeps its input and, where it is an expert
    layer, (a) the share's result ``names.EXPERT_OUT`` where the share
    takes its arrivals through windows
    (:func:`tpudist.parallel.moe.share_windows`; only such a share names
    it), ``tokens x (latent_width or d_model) x itemsize`` bytes: what
    follows the share needs it for its gradient, and the share's loop run
    again for it is one the compiler cannot merge with the backward
    pass's own; (b) the outputs of the dense products that its backward
    pass reads and its rematerialised forward would therefore run again:
    the router's logits (``tokens x n_experts x 4`` bytes, float32) and
    what it decided by them (``names.ROUTER_PICKS``, ``tokens x top_k x 8``
    bytes: behind the two, no sort runs again),
    ``latent_down``'s output where the layer has latent projections
    (``tokens x latent_width x itemsize``) and an UNSCORED shared expert's
    first products' (``up``; ``gate`` too where the expert is gated:
    ``tokens x shared_width x itemsize`` each; the activation between
    them and ``down`` is recomputed, one elementwise pass).  What no
    backward pass reads is not kept: ``down``'s and ``latent_up``'s
    outputs, behind a norm that comes BEFORE the sublayer.  A norm AFTER
    it (``norm_after``) reads the sublayer's whole output: that is not
    covered here (such an expert layer runs ``down`` and ``latent_up``
    twice; no architecture has one).  A scored shared expert is computed
    inside ``expert_share`` and names nothing.  At 8,192 tokens, 512
    experts, a latent width of 1,024 and a squared-ReLU shared expert of
    5,376 in bf16 at 22 picks: 16.8 + 16.8 + 1.4 + 16.8 + 88.1 = 139.9 MB
    a layer."""
    router = (names.ROUTER_LOGITS, names.ROUTER_PICKS)
    mixer = (names.DELTA_INVERSE,) if kind in DELTA_RULE_KINDS else ()
    if kind == names.CHANNEL_LINEAR:
        mixer = names.KDA_KEEPS + mixer
    if sizes.one_sublayer:
        if sizes.feed_forward != names.EXPERT_SHARE:
            return mixer
        keep = (names.EXPERT_OUT,) + router
        if sizes.latent_width:
            keep += (names.LATENT_IN,)
        if not sizes.shared_scored:
            keep += tuple(names.SHARED_EXPERT_KEEPS[leaf]
                          for leaf in EXPERT_LEAVES[sizes.expert_fn]
                          if leaf in names.SHARED_EXPERT_KEEPS)
        return keep + mixer
    if sizes.feed_forward == names.DENSE_FFN and sizes.ffn_products_kept:
        return (names.MIXER_OUT,) + names.DENSE_FFN_KEEPS + mixer
    if sizes.feed_forward == names.EXPERT_SHARE:
        return (names.MIXER_OUT,) + router + mixer
    return (names.MIXER_OUT,) + mixer


def kept_bytes(keep: tuple, sizes: HybridSizes, tokens: int, dtype) -> int:
    """What the activations named ``keep`` hold a layer from its forward to
    its backward pass, over ``tokens`` positions in compute dtype ``dtype``
    (the router's logits in float32, its picks and their scores in int32
    and float32, a delta rule's inverse in float32: ``DELTA_CHUNK`` numbers
    a position a held head; a channel-gated mixer's projections the held
    heads' columns)."""
    columns = {names.MIXER_OUT: sizes.d_model,
               names.FFN_GATE: sizes.ffn_width,
               names.FFN_UP: sizes.ffn_width, names.FFN_OUT: sizes.d_model,
               names.EXPERT_OUT: sizes.latent_width or sizes.d_model,
               names.LATENT_IN: sizes.latent_width or 0,
               names.SHARED_GATE: sizes.shared_width,
               names.SHARED_UP: sizes.shared_width,
               names.KDA_Q: sizes.linear_value_heads * sizes.linear_key_dim,
               names.KDA_K: sizes.linear_value_heads * sizes.linear_key_dim,
               names.KDA_V: sizes.linear_value_heads * sizes.linear_value_dim}
    itemsize = jnp.dtype(dtype).itemsize
    fixed = {names.ROUTER_LOGITS: 4 * sizes.n_experts,
             names.ROUTER_PICKS: 8 * sizes.top_k,
             names.DELTA_INVERSE: 4 * sizes.linear_value_heads * DELTA_CHUNK}
    return tokens * sum(
        fixed[name] if name in fixed else itemsize * columns[name]
        for name in keep)


class HybridLM(nn.Module):
    """Causal LM: token embedding, ``len(layer_types)`` pattern layers (each
    one of :func:`layer_kinds`), a final RMS norm of the layers' kind, and
    a head: untied (a ``head`` parameter), or with ``sizes.tied_head`` the
    embedding's own ``attend`` (no ``head`` parameter: the one tensor's
    gradient is the gather's scatter-add plus the head's product).  The
    embeddings are multiplied by ``sizes.embedding_scale`` in the compute
    dtype and the logits divided by ``sizes.logits_divisor`` where those are
    not 1."""

    vocab: int
    layer_types: tuple          # one of :func:`layer_kinds` a layer
    sizes: HybridSizes
    dtype: jnp.dtype = jnp.float32   # compute dtype; params stay f32 masters
    remat: bool = False
    # the names ``TransformerLM`` takes, for what the POLICY saves; besides,
    # under every policy, a layer keeps what :func:`remat_keeps` names:
    # ``mixer_out`` (``tokens x d_model x itemsize`` bytes a layer) and, with
    # a dense feed-forward whose sizes say ``ffn_products_kept``, its three
    # products' outputs (``tokens x (2 x ffn_width + d_model) x itemsize``
    # more), so its forward runs once; an
    # expert layer of one sublayer the result of a share that goes by windows
    # and the outputs of its dense products that the backward pass reads;
    # every expert layer its router's logits, picks and the picks' scores;
    # and a layer whose mixer scans by the delta rule its chunks' inverse
    # (at a decay a channel its q / k / v projections' outputs too)
    remat_policy: str = "nothing"
    # the feed-forward arm a layer (names.EXPERT_SHARE / names.DENSE_FFN)
    # where the layers do not share ``sizes.feed_forward`` (leading dense
    # layers before the expert layers): layer ``i`` is built from ``sizes``
    # with ``feed_forward=feed_forwards[i]``
    feed_forwards: Optional[tuple] = None

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        """``tokens [batch, seq] int32`` -> logits ``[batch, seq, vocab]``."""
        z = self.sizes
        unknown = set(self.layer_types) - set(layer_kinds(z))
        if unknown:
            raise ValueError(
                f"layer_types holds {sorted(unknown)}; a layer "
                f"(one_sublayer={z.one_sublayer}) is one of "
                f"{list(layer_kinds(z))}")
        arms = self.feed_forwards or (z.feed_forward,) * len(self.layer_types)
        if len(arms) != len(self.layer_types) or set(arms) - {
                names.EXPERT_SHARE, names.DENSE_FFN}:
            raise ValueError(
                f"feed_forwards is {arms}: one of {names.EXPERT_SHARE!r} and "
                f"{names.DENSE_FFN!r} for each of the "
                f"{len(self.layer_types)} layers")
        # a layer's sizes: the decoder's, with its own arm
        of_layer = [dataclasses.replace(z, feed_forward=arm) for arm in arms]
        keeps = [remat_keeps(zi, kind) if self.remat else ()
                 for zi, kind in zip(of_layer, self.layer_types)]
        kept = [kept_bytes(keep, z, tokens.size, self.dtype)
                for keep in keeps]
        alike = len(set(keeps)) == 1
        # the softmax attention's sizes: a kind where the decoder says them
        # so, else its one set
        attention_sizes = dict(softmax_kinds={
            kind: dict(heads=[a.n_heads, a.n_heads_total or a.n_heads],
                       kv_heads=a.n_kv_heads, window=a.window,
                       rotary_dim=a.rotary_dim, rope_theta=a.rope_theta,
                       yarn_factor=a.yarn and a.yarn.factor,
                       rope_scale=a.yarn.scale if a.yarn else 1.0)
            for kind, a in z.softmax_kinds}) if z.softmax_kinds else dict(
            attn_heads=[z.n_heads, z.n_heads_total or z.n_heads],
            attn_kv_heads=z.n_kv_heads)
        telemetry.event(
            names.MIXER_LAYOUT, kinds=list(self.layer_types),
            one_sublayer=z.one_sublayer, attention=z.attention,
            head_dim=z.head_dim, **attention_sizes,
            linear_heads=[z.linear_value_heads, z.linear_value_heads_total
                          or z.linear_value_heads],
            linear_key_heads=z.linear_key_heads,
            linear_key_dim=z.linear_key_dim,
            linear_value_dim=z.linear_value_dim,
            linear_projections=z.linear_projections,
            beta_scale=z.beta_scale, linear_gate_rank=z.linear_gate_rank,
            latent_rank=z.latent_rank,
            latent_key_dims=list(z.latent_key_dims),
            latent_value_dim=z.latent_value_dim,
            ssm_heads=[z.ssm_heads, z.ssm_heads_total or z.ssm_heads],
            ssm_groups=[z.ssm_groups, z.ssm_groups_total or z.ssm_groups],
            ssm_head_dim=z.ssm_head_dim, ssm_state=z.ssm_state,
            ssm_chunk=z.ssm_chunk,
            ssm_group_members=ssm_group_members(z),
            ssm_norm_over=ssm_norm_axis(z) or names.HELD,
            heads_axis=z.heads_axis,
            softmax_scale=z.softmax_scale, residual_scale=z.residual_scale,
            embedding_scale=z.embedding_scale,
            logits_divisor=z.logits_divisor, tied_head=z.tied_head,
            feed_forward=z.feed_forward, feed_forwards=list(arms),
            norm=z.norm, norm_after=z.norm_after,
            remat_keeps=list(keeps[0]) if alike else list(map(list, keeps)),
            remat_kept_bytes_per_layer=kept[0] if alike else kept,
            dense_products_kept=[names.FFN_UP in keep for keep in keeps])
        embed = nn.Embed(self.vocab, self.sizes.d_model, name="tok_embed",
                         dtype=self.dtype)
        with jax.named_scope(names.EMBED):
            x = embed(tokens)
            if z.embedding_scale != 1.0:
                x = x * z.embedding_scale
        layer_classes = {keep: remat_module(HybridLayer, self.remat_policy,
                                            keep=keep)
                         if self.remat else HybridLayer for keep in set(keeps)}
        for i, kind in enumerate(self.layer_types):
            x = layer_classes[keeps[i]](
                kind, of_layer[i], self.dtype,
                name=f"{names.PATTERN_LAYER}_{i}")(x)
        with jax.named_scope(names.HEAD):
            x = NORMS[z.norm](z.eps, name="final_norm")(x)
            logits = embed.attend(x) if z.tied_head else nn.Dense(
                self.vocab, use_bias=False, name="head", dtype=self.dtype)(x)
            if z.logits_divisor != 1.0:
                logits = logits / z.logits_divisor
            return logits
