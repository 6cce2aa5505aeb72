"""Pipeline-parallel TransformerLM: the GPipe schedule of
:mod:`tpudist.parallel.pipeline` applied to the LM block stack, composed
with data parallelism on a ``(data, stage)`` mesh.

Placement: token/position embeddings and the final-norm/head run
replicated on every device (they are a sliver of the FLOPs; replicating
them avoids two extra pipeline hops), while the N transformer blocks are
stacked ``[n_stages, layers_per_stage, ...]`` and sharded one stage per
device along the ``stage`` axis.  Activations move stage-to-stage with
``lax.ppermute`` over ICI; the whole schedule — fill, steady state, drain
— is one ``lax.scan`` inside one jitted ``shard_map``, differentiable
end-to-end (the backward is the reverse-ring schedule XLA derives).

The reference's only model parallelism is the manual 2-stage split of
``demo_one_model_multi_gpu.py:17-42``; this is its scalable TPU-native
generalization, and it composes with DP the same way the reference's
DDP(model-split) composition does (``demo_one_model_multi_gpu.py:96-98``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.parallel.pipeline import pipeline_1f1b_shard, pipeline_shard
from tpudist.runtime.mesh import AXIS_DATA, AXIS_STAGE

# NOTE: tpudist.models.transformer is imported lazily inside the builders —
# it imports tpudist.parallel for the attention references, so a module-level
# import here would be circular.


class _LMEmbed(nn.Module):
    """Embedding head whose param names match TransformerLM's tree."""

    vocab: int
    d_model: int
    max_len: int
    rope: bool = False  # rope models carry no pos_embed table
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.d_model, name="tok_embed",
                     dtype=self.dtype)(tokens)
        if not self.rope:
            pos = nn.Embed(self.max_len, self.d_model, name="pos_embed",
                           dtype=self.dtype)(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)
            )
            x = x + pos[None]
        return x


class _LMHead(nn.Module):
    """Final norm + vocab projection, names matching TransformerLM."""

    vocab: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        # same precision split as TransformerLM: f32 norm, dtype projection
        x = nn.LayerNorm(use_bias=False, dtype=jnp.float32)(x)  # 'LayerNorm_0'
        return nn.Dense(self.vocab, use_bias=False, name="head",
                        dtype=self.dtype)(x)


_EMBED_KEYS = ("tok_embed", "pos_embed")
_HEAD_KEYS = ("LayerNorm_0", "head")


def stack_block_params(params, n_stages: int):
    """TransformerLM params → pipeline layout.

    Returns ``{"blocks": stacked, "rest": {...}}`` where ``stacked`` leaves
    have shape ``[n_stages, layers_per_stage, ...]`` (stage axis sharded,
    inner axis walked sequentially per stage) and ``rest`` holds the
    embeddings/norm/head unchanged.
    """
    p = dict(params["params"])
    block_keys = sorted(
        (k for k in p if k.startswith("block_")),
        key=lambda k: int(k.split("_")[1]),
    )
    n_layers = len(block_keys)
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} blocks do not split into {n_stages} stages")
    per_stage = n_layers // n_stages
    blocks = [p.pop(k) for k in block_keys]
    stacked = jax.tree.map(
        lambda *leaves: jnp.stack(leaves).reshape(
            (n_stages, per_stage) + leaves[0].shape
        ),
        *blocks,
    )
    return {"blocks": stacked, "rest": p}


def unstack_block_params(pp_params):
    """Inverse of :func:`stack_block_params` (checkpoint/parity interop)."""
    stacked = pp_params["blocks"]
    shape = jax.tree.leaves(stacked)[0].shape
    n_stages, per_stage = shape[0], shape[1]
    p = dict(pp_params["rest"])
    for s in range(n_stages):
        for j in range(per_stage):
            p[f"block_{s * per_stage + j}"] = jax.tree.map(
                lambda a, s=s, j=j: a[s, j], stacked
            )
    return {"params": p}


def stack_block_params_interleaved(params, n_dev: int, n_chunks: int):
    """TransformerLM params → the interleaved pipeline layout: blocks
    stacked to ``n_dev·n_chunks`` virtual stages, then depth-strided so
    device ``d`` holds global stages ``{c·n_dev + d}``
    (:func:`tpudist.parallel.pipeline_interleaved.interleave_block_params`).
    For checkpoint interop with the contiguous layout, apply
    ``deinterleave_block_params`` to ``blocks`` before
    :func:`unstack_block_params`."""
    from tpudist.parallel.pipeline_interleaved import interleave_block_params

    pp = stack_block_params(params, n_dev * n_chunks)
    return {"blocks": interleave_block_params(pp["blocks"], n_dev),
            "rest": pp["rest"]}


def pp_state_sharding(mesh: Mesh, tree, *, axis_name: str = AXIS_STAGE):
    """Shardings for a pipeline ``ModelState`` pytree: every leaf under a
    ``blocks`` key is stage-sharded on its leading axis, everything else
    (embeddings, head, Adam's scalar count) replicated."""
    staged = NamedSharding(mesh, P(axis_name))
    repl = NamedSharding(mesh, P())

    def shard_for(path, leaf):
        keys = [getattr(e, "key", getattr(e, "name", None)) for e in path]
        if "blocks" in keys and getattr(leaf, "ndim", 0) >= 1:
            return staged
        return repl

    return jax.tree_util.tree_map_with_path(shard_for, tree)


def _lm_pipeline_parts(module):
    """Shared sub-modules + stage fn for the pipelined TransformerLM:
    ``(embed_mod, head_mod, stage_fn)`` — one construction point so the
    GPipe apply and the 1F1B train step cannot drift."""
    from tpudist.models.transformer import Block
    from tpudist.ops.attention import (
        default_attention,
        make_length_aware_attention,
    )

    # Honor the model's sliding window: TransformerLM guarantees
    # attention_fn is None when sliding_window is set, so rebuild the
    # windowed default here exactly as the unpipelined model would.
    if module.sliding_window is not None:
        attn = make_length_aware_attention(module.sliding_window)
    else:
        attn = module.attention_fn or default_attention
    block_mod = Block(
        module.d_model, module.n_heads, module.d_ff, attn,
        n_experts=module.n_experts, moe_fn=module.moe_fn,
        dtype=module.dtype, rope=module.rope,
        n_kv_heads=module.n_kv_heads,
        sliding_window=module.sliding_window,
    )
    embed_mod = _LMEmbed(module.vocab, module.d_model, module.max_len,
                         rope=module.rope, dtype=module.dtype)
    head_mod = _LMHead(module.vocab, dtype=module.dtype)

    def stage_fn(stage_params, x):
        # stage_params leaves: [layers_per_stage, ...]; apply sequentially.
        per_stage = jax.tree.leaves(stage_params)[0].shape[0]
        for j in range(per_stage):
            layer = jax.tree.map(lambda a, j=j: a[j], stage_params)
            x = block_mod.apply({"params": layer}, x)
        return x

    return embed_mod, head_mod, stage_fn


def make_pp_lm_apply(
    mesh: Mesh,
    module,  # a tpudist.models.transformer.TransformerLM
    *,
    n_stages: int,
    num_microbatches: int = 4,
    axis_name: str = AXIS_STAGE,
    data_axis: Optional[str] = AXIS_DATA,
    remat: bool = False,
):
    """Build ``apply(pp_params, tokens) -> logits`` with the block stack
    pipelined over ``axis_name`` and the batch sharded over ``data_axis``.

    ``pp_params`` comes from :func:`stack_block_params`.  Feed the result
    to :func:`tpudist.train.make_lm_train_step` together with
    :func:`pp_state_sharding` — the loss/grad/optimizer path needs no
    pipeline awareness.  (Training through this apply is the GPipe
    schedule: autodiff replays every microbatch's backward after all
    forwards.  For the memory-bounded 1F1B alternative, use
    :func:`make_pp_lm_train_step` with ``schedule='1f1b'``.)
    """
    embed_mod, head_mod, stage_fn = _lm_pipeline_parts(module)

    data_in_spec = P(None, data_axis) if data_axis else P()
    out_spec = (
        P(axis_name, None, data_axis) if data_axis else P(axis_name)
    )

    def apply(pp_params, tokens):
        rest = pp_params["rest"]
        x = embed_mod.apply(
            {"params": {k: rest[k] for k in _EMBED_KEYS if k in rest}},
            tokens
        )
        b, s, d = x.shape
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} must divide into {num_microbatches} microbatches"
            )
        xm = x.reshape(num_microbatches, b // num_microbatches, s, d)

        def body(sp, xmb):
            return pipeline_shard(
                sp, xmb, stage_fn=stage_fn, axis_name=axis_name, remat=remat
            )[None]

        out = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), data_in_spec),
            out_specs=out_spec, check_vma=False)(pp_params["blocks"], xm)
        # Last stage's block only — one stage's data moves, not a psum of
        # the whole [n_stages, ...] stack.
        x = out[-1].reshape(b, s, d)
        return head_mod.apply(
            {"params": {k: rest[k] for k in _HEAD_KEYS}}, x
        )

    return apply


def make_pp_lm_train_step(
    mesh: Mesh,
    module,  # a tpudist.models.transformer.TransformerLM
    tx,      # optax.GradientTransformation
    *,
    n_stages: int,
    num_microbatches: int = 4,
    schedule: str = "1f1b",
    n_chunks: int = 1,
    axis_name: str = AXIS_STAGE,
    data_axis: Optional[str] = AXIS_DATA,
    donate_state: bool = True,
    state_sharding=None,
):
    """Build the jitted pipeline-parallel LM train step
    ``step(state, tokens) -> (state, loss)`` with a selectable schedule.

    ``schedule='gpipe'``: training through :func:`make_pp_lm_apply` +
    ``make_lm_train_step`` — all microbatch forwards, then autodiff's
    backward replay; peak activation memory grows with ``num_microbatches``.

    ``schedule='1f1b'``: the hand-interleaved one-forward-one-backward
    schedule (:func:`tpudist.parallel.pipeline.pipeline_1f1b_shard`) —
    backward of each microbatch starts the tick its loss exists, so peak
    residual memory is O(n_stages), CONSTANT in ``num_microbatches``.
    Raise ``num_microbatches`` to amortize the pipeline bubble for free.
    Loss/grad numerics match GPipe up to summation order (tests assert
    parity).  MoE blocks are not supported under 1F1B (their expert
    all_to_all would nest inside this shard_map); use GPipe there.

    ``schedule='interleaved'``: virtual-stage 1F1B
    (:mod:`tpudist.parallel.pipeline_interleaved`) — each device holds
    ``n_chunks`` depth-strided model chunks, shrinking the fill/drain
    bubble ~÷``n_chunks`` at the cost of more (smaller) activation hops.
    Requires ``num_microbatches % n_stages == 0`` and a state over the
    :func:`stack_block_params_interleaved` layout.  MoE unsupported, as
    for 1f1b.

    ``state``: ``ModelState`` over the :func:`stack_block_params` layout
    (:func:`stack_block_params_interleaved` for ``schedule='interleaved'``),
    sharded per :func:`pp_state_sharding`.
    """
    import optax

    from tpudist.models.transformer import lm_loss
    from tpudist.train.step import ModelState

    if schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(
            f"schedule must be gpipe|1f1b|interleaved, got {schedule!r}")
    if n_chunks != 1 and schedule != "interleaved":
        raise ValueError(
            f"n_chunks={n_chunks} requires schedule='interleaved'")
    if schedule == "gpipe":
        from tpudist.train.lm import make_lm_train_step

        apply_fn = make_pp_lm_apply(
            mesh, module, n_stages=n_stages,
            num_microbatches=num_microbatches, axis_name=axis_name,
            data_axis=data_axis,
        )
        return make_lm_train_step(
            apply_fn, tx, mesh, donate_state=donate_state,
            state_sharding=state_sharding,
        )
    if module.n_experts > 0:
        raise ValueError(f"schedule={schedule!r} does not support MoE blocks")

    embed_mod, head_mod, stage_fn = _lm_pipeline_parts(module)
    data_in_spec = P(None, data_axis) if data_axis else P()

    def micro_loss(head_params, act, toks):
        logits = head_mod.apply({"params": head_params}, act)
        return lm_loss(logits, toks)

    if schedule == "interleaved":
        from tpudist.parallel.pipeline_interleaved import (
            interleaved_schedule, pipeline_interleaved_shard)

        sched = interleaved_schedule(n_stages, n_chunks, num_microbatches)

        def body(blocks, head_params, xm, tm):
            return pipeline_interleaved_shard(
                blocks, head_params, xm, tm, stage_fn=stage_fn,
                loss_fn=micro_loss, schedule=sched, axis_name=axis_name,
                data_axis=data_axis,
            )
    else:
        def body(blocks, head_params, xm, tm):
            return pipeline_1f1b_shard(
                blocks, head_params, xm, tm, stage_fn=stage_fn,
                loss_fn=micro_loss, axis_name=axis_name, data_axis=data_axis,
            )

    sharded_body = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(), data_in_spec, data_in_spec),
        out_specs=(P(), P(axis_name), P(), data_in_spec), check_vma=False)

    def step(state: ModelState, tokens):
        pp_params = state.params
        rest = pp_params["rest"]
        embed_params = {k: rest[k] for k in _EMBED_KEYS if k in rest}
        head_params = {k: rest[k] for k in _HEAD_KEYS}
        b = tokens.shape[0]
        m = num_microbatches
        if b % m:
            raise ValueError(
                f"batch {b} must divide into {m} microbatches")

        x, embed_vjp = jax.vjp(
            lambda ep: embed_mod.apply({"params": ep}, tokens), embed_params)
        _, s, d = x.shape
        xm = x.reshape(m, b // m, s, d)
        tm = tokens.reshape(m, b // m, s)

        loss_sum, stage_g, head_g, dxm = sharded_body(
            pp_params["blocks"], head_params, xm, tm)

        # The shard body returns per-microbatch SUMS (data-axis already
        # mean-reduced inside); the step's loss is the mean over the m
        # equal microbatches, so every gradient scales by 1/m too.
        loss = loss_sum / m
        head_g = jax.tree.map(lambda g: g / m, head_g)
        stage_g = jax.tree.map(lambda g: g / m, stage_g)
        # dx was NOT data-mean-reduced inside (each shard's activations
        # are its own): the global cotangent is d(global mean)/dx =
        # local_sum / (m · data_axis_size); the embed vjp under jit's
        # global view then inserts the cross-shard embedding-grad psum.
        d_size = mesh.shape[data_axis] if data_axis else 1
        dx = dxm.reshape(b, s, d) / (m * d_size)
        (embed_g,) = embed_vjp(dx)

        grads = {"blocks": stage_g,
                 "rest": {**embed_g, **head_g}}
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = ModelState(params=new_params, opt_state=new_opt)
        return new_state, loss

    return jax.jit(
        step,
        in_shardings=(state_sharding, None) if state_sharding is not None
        else None,
        out_shardings=(state_sharding, None) if state_sharding is not None
        else None,
        donate_argnums=(0,) if donate_state else (),
    )
