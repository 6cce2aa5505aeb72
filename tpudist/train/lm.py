"""Jitted LM train step for the Transformer family — the long-context
sibling of :mod:`tpudist.train.step`.

Same design stance (no wrapper object, one pure jitted function, explicit
shardings) on a 2-D ``(data, seq)`` mesh: the token batch is sharded over
BOTH axes (``P(data, seq)``), parameters and optimizer state are
replicated, ring attention inside the model handles the sequence-sharded
contraction, and XLA inserts the gradient all-reduce over both mesh axes.
Per-chip activation memory is O(batch/data_n × seq/seq_n) — context length
scales with the ``seq`` axis at constant memory, which is the point.
"""

from __future__ import annotations

import functools

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.models.transformer import lm_loss
from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ
from tpudist.telemetry import names
from tpudist.train.step import ModelState


def token_sharding(mesh: Mesh) -> NamedSharding:
    """``[batch, seq]`` tokens sharded over every mesh axis present."""
    data = AXIS_DATA if AXIS_DATA in mesh.axis_names else None
    seq = AXIS_SEQ if AXIS_SEQ in mesh.axis_names else None
    return NamedSharding(mesh, P(data, seq))


def init_lm_state(params, tx: optax.GradientTransformation) -> ModelState:
    return ModelState(params=params, opt_state=tx.init(params))


def _scoped_loss(loss_fn: Callable, logits, tokens):
    """``loss_fn`` under the ``loss`` scope: the name every operation of the
    vocabulary-wide loss (and, as ``transpose(jvp(loss))``, of its gradient)
    carries in a device trace.  The model names its own sublayers
    (``embed``/``attn``/``mlp``/``head``, ``tpudist.models.transformer``)."""
    with jax.named_scope(names.LOSS):
        return loss_fn(logits, tokens)


def _scoped_update(tx: optax.GradientTransformation, state: ModelState,
                   grads) -> ModelState:
    """The optimizer update under the ``optimizer`` scope."""
    with jax.named_scope(names.OPTIMIZER):
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return ModelState(params=new_params, opt_state=new_opt)


def _under_mesh(fn: Callable, mesh: Mesh) -> Callable:
    """Trace ``fn`` with ``mesh`` as JAX's ambient (abstract) mesh, so
    model code that does not know the mesh can see how the step is laid
    out — the flash kernel's per-shard wrapper in
    ``tpudist.models.transformer`` needs it on more than one chip."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return traced


def _make_lm_train_step_compressed(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    donate_state: bool,
    reduce_dtype,
    loss_fn: Callable = lm_loss,
):
    """The ``grad_reduce_dtype`` body of :func:`make_lm_train_step`:
    per-shard grads inside ``shard_map``, explicit narrow-dtype ``pmean``
    on the wire, f32 update outside."""
    repl = NamedSharding(mesh, P())
    tok_shard = token_sharding(mesh)

    def shard_body(params, toks):
        # pcast-to-varying FIRST: differentiating w.r.t. replicated
        # (unvarying) inputs makes shard_map's transpose insert its own
        # full-width f32 psum for the cotangents — the very reduce this
        # path exists to narrow.  Varying params keep the grads local,
        # so the explicit narrow pmean below is the ONLY wire traffic
        # (the audit asserts exactly this).
        params = jax.tree.map(
            lambda p: lax.pcast(p, (AXIS_DATA,), to="varying"), params)
        # Local mean over this shard's rows; equal shards (the sharded
        # batch contract) make pmean-of-means the exact global mean.
        loss, grads = jax.value_and_grad(
            lambda p: _scoped_loss(loss_fn, apply_fn(p, toks), toks))(params)
        narrow = jax.tree.map(
            lambda g: lax.pmean(g.astype(reduce_dtype), AXIS_DATA), grads)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), narrow)
        return lax.pmean(loss, AXIS_DATA), grads

    sharded_grad = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(AXIS_DATA)),
        out_specs=(P(), P()), check_vma=False)

    def step(state: ModelState, tokens):
        loss, grads = sharded_grad(state.params, tokens)
        return _scoped_update(tx, state, grads), loss

    return jax.jit(
        step,
        in_shardings=(repl, tok_shard),
        out_shardings=(repl, repl),
        donate_argnums=(0,) if donate_state else (),
    )


def make_lm_eval_step(
    apply_fn: Callable,
    mesh: Mesh,
    *,
    loss_fn: Callable = lm_loss,
    params_sharding=None,
):
    """Jitted no-grad evaluation: ``eval_step(params, tokens) -> loss``.

    Same sharded-batch contract as the train step; ``params_sharding``
    matches whatever layout the train step keeps (replicated default, or
    e.g. an FSDP/TP sharding tree for ``ModelState.params``)."""
    repl = NamedSharding(mesh, P())
    p_shard = repl if params_sharding is None else params_sharding

    def eval_step(params, tokens):
        return loss_fn(apply_fn(params, tokens), tokens)

    return jax.jit(
        _under_mesh(eval_step, mesh),
        in_shardings=(p_shard, token_sharding(mesh)),
        out_shardings=repl,
    )


def make_lm_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    donate_state: bool = True,
    state_sharding=None,
    aux: bool = False,
    moe_balance_weight: float = 0.0,
    accum_steps: int = 1,
    grad_reduce_dtype=None,
    loss_fn: Callable = lm_loss,
):
    """Build ``step(state, tokens) -> (state, loss)``, compiled once.

    ``apply_fn(params, tokens) -> logits`` is the TransformerLM apply with
    whatever attention op the caller injected (ring for multi-chip).

    The step names its phases for a device trace (``jax.named_scope``,
    names in :mod:`tpudist.telemetry.names`): ``loss``, ``optimizer`` and,
    under ``accum_steps``, ``grad_accum``; the model names its sublayers,
    and JAX marks the backward pass itself (``transpose(jvp(...))``).
    Metadata only: the compiled step is the same program.

    ``state_sharding`` (a pytree of ``NamedSharding`` matching the
    ``ModelState``, e.g. from
    :func:`tpudist.models.transformer.transformer_tp_sharding`) overrides
    the default replicated parameter layout — tensor parallelism composed
    with the data/seq sharding of the batch.

    ``aux=True`` runs the model with flax ``intermediates`` collection and
    returns ``step(state, tokens) -> (state, loss, aux_dict)`` where
    ``aux_dict`` carries MoE routing stats averaged over layers
    (``moe_dropped_fraction`` scalar, ``moe_expert_load`` ``[n_experts]``,
    ``moe_balance_loss`` scalar; from a dropless share layer
    ``moe_expert_tokens`` ``[layers, held]``, the assignments each held
    expert got this step, ``moe_windows`` ``[layers, blocks]``, the
    windows each block of tokens' arrivals took, and ``moe_strips``
    ``[layers, blocks]``, the strips of a window (``strip_rows`` of the
    layer's ``moe_layout`` event) each block scatter-added into its
    result: a windowed share scatters ``moe_strips * strip_rows`` rows for
    the ``moe_expert_tokens`` that arrived) — empty when the model sows
    nothing.
    Requires ``apply_fn`` to accept flax's ``mutable=`` kwarg (i.e. a
    ``Module.apply``).

    ``moe_balance_weight`` > 0 adds that multiple of the mean sown
    ``moe_balance_loss`` (the differentiable Switch/GShard auxiliary) to
    the training loss — router load balancing trains even when ``aux`` is
    False; the reported loss stays the plain LM cross entropy.

    ``accum_steps`` > 1 splits the batch into that many microbatches and
    accumulates their gradients in a ``lax.scan`` before the single
    optimizer update — big effective batches at 1/``accum_steps`` peak
    activation memory, numerics equal to the full-batch step up to
    summation order.  Batch size must divide evenly.

    ``grad_reduce_dtype`` (e.g. ``jnp.bfloat16``) compresses the DP
    gradient all-reduce: each shard's local gradients are cast down, the
    cross-device mean rides the wire at that dtype, and the result is
    cast back to f32 for the optimizer update — halving the per-step DP
    wire bytes (the first thing that binds when the data axis crosses
    DCN; see ``benchmarks/scaling_model.py``).  Master weights, loss and
    optimizer state stay f32; only the reduce payload narrows (the
    gradient stochasticity the mean averages over is far larger than
    bf16's rounding at trained scales — tests bound the drift).
    Implementation: the default path lets XLA insert the f32 psum from
    the global batch mean; this path instead computes per-shard grads in
    a ``shard_map`` and reduces them explicitly at the narrow dtype, so
    it requires the pure-DP layout (replicated state, no
    ``state_sharding``, no ``aux``/``accum_steps`` composition yet) and a
    mesh whose only batch axis is ``data``.
    """
    if grad_reduce_dtype is not None:
        if state_sharding is not None or aux or moe_balance_weight > 0.0 \
                or accum_steps != 1:
            raise ValueError(
                "grad_reduce_dtype requires the pure-DP step (replicated "
                "state; no aux/moe_balance_weight/accum_steps)")
        if AXIS_DATA not in mesh.axis_names:
            raise ValueError("grad_reduce_dtype needs a 'data' mesh axis")
        extra = [a for a in mesh.axis_names
                 if a != AXIS_DATA and mesh.shape[a] > 1]
        if extra:
            raise ValueError(
                f"grad_reduce_dtype supports data-only meshes; axes "
                f"{extra} have size > 1")
        return _make_lm_train_step_compressed(
            apply_fn, tx, mesh, donate_state=donate_state,
            reduce_dtype=grad_reduce_dtype, loss_fn=loss_fn)
    repl = NamedSharding(mesh, P())
    tok_shard = token_sharding(mesh)
    state_out = repl if state_sharding is None else state_sharding
    need_inters = aux or moe_balance_weight > 0.0

    def _collect_aux(inters) -> dict:
        by_name: dict = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(inters)[0]:
            keys = [getattr(e, "key", getattr(e, "name", None)) for e in path]
            for name in ("moe_dropped_fraction", "moe_expert_load",
                         "moe_balance_loss"):
                if name in keys:
                    by_name.setdefault(name, []).append(leaf)
        out = {
            name: jnp.mean(jnp.stack(vals), axis=0)
            for name, vals in by_name.items()
        }
        # a dropless share layer (tpudist.models.hybrid) sows the
        # assignments each held expert got, the windows each block of
        # tokens took and the strips it scattered: kept a row a layer, not
        # averaged
        for name in ("moe_expert_tokens", "moe_windows", "moe_strips"):
            rows = [leaf for path, leaf in
                    jax.tree_util.tree_flatten_with_path(inters)[0]
                    if any(getattr(e, "key", None) == name for e in path)]
            if rows:
                out[name] = jnp.stack(rows)
        return out

    def grad_of(params, toks):
        """((lm_loss, collected), grads) for one microbatch."""
        if need_inters:
            def loss_of(p):
                logits, mut = apply_fn(p, toks, mutable=["intermediates"])
                # flax omits the collection entirely when nothing was sown
                collected = _collect_aux(mut.get("intermediates", {}))
                lm = _scoped_loss(loss_fn, logits, toks)
                total = lm
                if moe_balance_weight > 0.0 and "moe_balance_loss" in collected:
                    total = total + moe_balance_weight * collected[
                        "moe_balance_loss"]
                # grads flow from total; the reported loss stays plain LM CE
                return total, (lm, collected)

            (_, out), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            return out, grads

        def loss_of(p):
            return _scoped_loss(loss_fn, apply_fn(p, toks), toks)

        loss, grads = jax.value_and_grad(loss_of)(params)
        return (loss, {}), grads

    def step(state: ModelState, tokens):
        if accum_steps == 1:
            (loss, collected), grads = grad_of(state.params, tokens)
        else:
            b = tokens.shape[0]
            if b % accum_steps:
                raise ValueError(
                    f"batch {b} must divide into {accum_steps} accum steps"
                )
            chunks = tokens.reshape(accum_steps, b // accum_steps,
                                    *tokens.shape[1:])
            acc_shape = jax.eval_shape(grad_of, state.params, chunks[0])
            acc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                acc_shape)

            def body(acc, chunk):
                out = grad_of(state.params, chunk)
                with jax.named_scope(names.GRAD_ACCUM):
                    return jax.tree.map(jnp.add, acc, out), None

            ((loss, collected), grads), _ = lax.scan(body, acc0, chunks)
            scale = 1.0 / accum_steps
            loss = loss * scale
            collected = jax.tree.map(lambda a: a * scale, collected)
            grads = jax.tree.map(lambda g: g * scale, grads)
        new_state = _scoped_update(tx, state, grads)
        if aux:
            return new_state, loss, collected
        return new_state, loss

    if aux:
        out_shardings = (state_out, repl, None)  # aux: XLA-chosen (replicated scalars)
    else:
        out_shardings = (state_out, repl)
    return jax.jit(
        _under_mesh(step, mesh),
        in_shardings=(state_out, tok_shard),
        out_shardings=out_shardings,
        donate_argnums=(0,) if donate_state else (),
    )


def fsdp_overlap_mlp_fn(mesh: Mesh, *, axis_name: str = AXIS_DATA,
                        overlap: str | None = None):
    """Knob-driven overlapped FSDP layer compute for the LM train step.

    The FSDP path (``state_sharding=fsdp_sharding(mesh, state)``) is a
    pure layout: the SPMD partitioner all-gathers each FFN kernel whole
    BEFORE the matmul that consumes it — exposed wire time on the
    critical path.  This helper resolves the ``TPUDIST_OVERLAP`` knob
    (``off``/``ring``/``bidir``; ``overlap`` overrides) and returns the
    pipelined ppermute MLP closure for ``create_transformer(mlp_fn=...)``
    — or ``None`` when off, keeping the byte-identical default.  Wiring::

        mlp_fn = fsdp_overlap_mlp_fn(mesh)              # knob decides
        module, params = create_transformer(rng, mlp_fn=mlp_fn, ...)
        state = init_lm_state(params, tx)
        sharding = fsdp_sharding(mesh, state)
        step = make_lm_train_step(module.apply, tx, mesh,
                                  state_sharding=sharding)

    The step function itself needs no change: the closure carries its
    own ``shard_map`` whose in-specs MATCH the FSDP layout of the FFN
    kernels, so they stream into the ring sharded — no monolithic
    all-gather is ever emitted for them (``benchmarks/comm_audit.py``'s
    ``fsdp_overlap_*`` regimes assert it from optimized HLO).  Numerics:
    the column gather is bit-exact; the contraction gather reassociates
    (bound documented in :mod:`tpudist.parallel.overlap`; tests pin the
    end-to-end step drift).
    """
    from tpudist.parallel.fsdp import overlap_fsdp_mlp

    return overlap_fsdp_mlp(mesh, axis_name=axis_name, overlap=overlap)


def chunk_token_sharding(mesh: Mesh) -> NamedSharding:
    """``[K, batch, seq]`` token windows: iteration axis replicated, the
    rest sharded like :func:`token_sharding`."""
    data = AXIS_DATA if AXIS_DATA in mesh.axis_names else None
    seq = AXIS_SEQ if AXIS_SEQ in mesh.axis_names else None
    return NamedSharding(mesh, P(None, data, seq))


def make_scanned_lm_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    loss_fn: Callable = lm_loss,
    donate_state: bool = True,
    state_sharding=None,
):
    """The chunked (``lax.scan``) LM train step — K optimizer steps per
    dispatch, the same amortization that makes the toy headline fast
    (``make_scanned_train_step``), for the LM family.

    Returns ``chunk_step(state, tokens_chunk) -> (state, losses)`` with
    ``tokens_chunk: [K, batch, seq] int32`` (sharded per
    :func:`chunk_token_sharding`) and ``losses: (K,)`` per-iteration
    values — per-step logging semantics preserved while dispatch and
    host sync amortize K×.  Numerics are bit-identical to K calls of the
    plain step (tests assert it).  The plain step's extras (MoE aux,
    accum, grad_reduce_dtype) are out of scope here — use it for the
    small-model regime they don't apply to.
    """
    from jax import lax as _lax

    repl = NamedSharding(mesh, P())
    state_out = repl if state_sharding is None else state_sharding

    def chunk(state: ModelState, tokens_chunk):
        def body(st, toks):
            loss, grads = jax.value_and_grad(
                lambda p: _scoped_loss(loss_fn, apply_fn(p, toks), toks)
            )(st.params)
            return _scoped_update(tx, st, grads), loss

        return _lax.scan(body, state, tokens_chunk)

    return jax.jit(
        _under_mesh(chunk, mesh),
        in_shardings=(state_out, chunk_token_sharding(mesh)),
        out_shardings=(state_out, repl),
        donate_argnums=(0,) if donate_state else (),
    )
