#!/usr/bin/env python3
"""Compile-time collective audit over every multi-chip sharding regime.

For each regime the driver's ``dryrun_multichip`` exercises (plus pure DP),
this lowers the full jitted train step at n=8 on the virtual CPU mesh,
parses the optimized HLO (:mod:`tpudist.utils.hlo_audit`), and checks the
emitted collectives against analytic predictions:

- **dp**           one gradient all-reduce of exactly param+loss bytes
                   (wire cost 2(n−1)/n × payload — the DP scaling law)
- **ring**         2(ring−1) K/V collective-permutes forward (+ the
                   reversed ring in backward), each of one KV-shard
- **windowed ring** the ring stops early: strictly fewer permutes than
                   dense at the same geometry
- **moe**          2 all_to_alls forward (dispatch/return) + 2 backward,
                   each of the [experts, capacity, d] buffer
- **fsdp**         per-use all-gather of sharded params + reduce-scatter
                   of their grads (ZeRO-3's manual machinery, emitted by
                   the SPMD partitioner from the layout alone)
- **zero1**        plain-DP gradient all-reduce + all-gather of exactly
                   the sharded updated params (weight-update sharding,
                   arXiv:2004.13336)
- **gpipe/1f1b/interleaved**  stage-boundary collective-permutes inside
                   the scan loop (per-tick activation hop), not unrolled

Writes ``COMM_AUDIT_r{NN}.json`` (NN = the round being built,
``benchmarks/_round.py``) and exits nonzero if any check fails.
This is the no-hardware half of the multi-chip scaling story: the
collective *structure* is exactly what a pod would execute; only the link
bandwidths need hardware.  (VERDICT r3 #3; SURVEY.md §2.4.)
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import numpy as np  # noqa: E402


def _force_cpu_mesh(n: int = 8) -> None:
    """A CPU virtual mesh of at least ``n`` devices for a standalone run
    (this script works on the CPU by design and never touches a chip).
    Embedded in a process whose backend is already up (pytest: the
    conftest's 8-device mesh) the device count cannot change any more,
    and the embedder's devices stand."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", max(n, 8))
    except RuntimeError:
        pass  # backends already initialized


def collect_ops(step, ex_args, info):
    """One collection point for regime HLO (used by main() AND the test
    suite so the shipped artifact and the asserted audit can never
    measure different programs): optimized HLO normally, pre-opt HLO for
    regimes whose checked property a backend pass rewrites."""
    from tpudist.utils.hlo_audit import (
        collect_collectives,
        lower_preopt_hlo,
        parse_collectives,
    )

    if info.get("pre_opt"):
        return parse_collectives(lower_preopt_hlo(step, *ex_args))
    return collect_collectives(step, *ex_args)


# ---------------------------------------------------------------------------
# Regime builders: each returns (jitted_step, example_args, info) where
# info carries the analytic quantities the checks consume.
# ---------------------------------------------------------------------------


def _toy_models():
    import jax
    import optax

    from tpudist.models import create_toy_model
    from tpudist.train import init_model_states

    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    mx, px = create_toy_model(kx)
    my, py = create_toy_model(ky)
    models = {"model_X": (mx.apply, px), "model_Y": (my.apply, py)}
    tx = optax.adam(1e-3)
    states = init_model_states(models, tx)
    return models, tx, states


def regime_dp(devices):
    """Pure DP on (8,): the DDP-parity regime (reference demo.py)."""
    import jax
    from jax.sharding import Mesh

    from tpudist.runtime.mesh import AXIS_DATA
    from tpudist.train import make_multi_model_train_step
    from tpudist.train.step import batch_sharding
    from tpudist.utils.hlo_audit import tree_bytes

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
    models, tx, states = _toy_models()
    step = make_multi_model_train_step(
        {k: f for k, (f, _) in models.items()}, tx, mesh
    )
    bs = batch_sharding(mesh)
    x = jax.device_put(np.zeros((32, 2), np.float32), bs)
    y = jax.device_put(np.zeros((32, 1), np.float32), bs)
    info = {
        "mesh": {"data": 8},
        "param_bytes": tree_bytes({k: s.params for k, s in states.items()}),
        "n_loss_scalars": 2,
    }
    return step, (states, x, y), info


def regime_dp_bf16_reduce(devices):
    """(8,) pure DP with grad_reduce_dtype=bf16: the gradient all-reduce
    must ride the wire at HALF the f32 payload (tpudist/train/lm.py
    compressed path; the DCN-scaling lever of scaling_model.py)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from tpudist.models import create_transformer
    from tpudist.runtime.mesh import AXIS_DATA
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding
    from tpudist.utils.hlo_audit import tree_bytes

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
        n_layers=1, n_heads=2, d_ff=64, max_len=16)
    tx = optax.adam(1e-3)
    state = init_lm_state(params, tx)
    step = make_lm_train_step(module.apply, tx, mesh,
                              grad_reduce_dtype=jnp.bfloat16)
    toks = np.random.default_rng(0).integers(0, 32, size=(8, 16)) \
        .astype(np.int32)
    args = (state, jax.device_put(toks, token_sharding(mesh)))
    return step, args, {
        "mesh": {"data": 8},
        "param_bytes": tree_bytes(state.params),
        # Audit the PRE-optimization HLO: the CPU backend's all-reduce
        # promotion pass re-widens bf16 reduces to f32 (no native bf16
        # reduction on CPU); TPU executes the bf16 width as requested.
        "pre_opt": True,
        "note": "cpu backend promotes bf16 all-reduce to f32; "
                "pre-opt HLO carries the requested wire dtype",
    }


def regime_dp_model_split(devices):
    """(4,2) dp × model — the model-split demo's sharding-spec split."""
    import jax
    from jax.sharding import Mesh

    from tpudist.models.split_mlp import split_state_sharding
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL
    from tpudist.train import make_multi_model_train_step
    from tpudist.train.step import batch_sharding
    from tpudist.utils.hlo_audit import tree_bytes

    mesh = Mesh(np.asarray(devices).reshape(4, 2),
                axis_names=(AXIS_DATA, AXIS_MODEL))
    models, tx, states = _toy_models()
    sharding = split_state_sharding(mesh, states)
    states = jax.device_put(states, sharding)
    step = make_multi_model_train_step(
        {k: f for k, (f, _) in models.items()}, tx, mesh,
        state_sharding=sharding,
    )
    bs = batch_sharding(mesh)
    x = jax.device_put(np.zeros((32, 2), np.float32), bs)
    y = jax.device_put(np.zeros((32, 1), np.float32), bs)
    info = {
        "mesh": {"data": 4, "model": 2},
        "param_bytes": tree_bytes({k: s.params for k, s in states.items()}),
    }
    return step, (states, x, y), info


def _lm_regime(mesh, *, attention_fn=None, moe_fn=None, mlp_fn=None,
               n_layers=1, n_experts=0, seq_len=64, batch=8,
               state_sharding_fn=None, aux=False, seed=0):
    import jax
    import optax

    from tpudist.models import create_transformer
    from tpudist.train import init_lm_state, make_lm_train_step, token_sharding
    from tpudist.utils.hlo_audit import tree_bytes

    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq_len, attention_fn=attention_fn,
        moe_fn=moe_fn, mlp_fn=mlp_fn, vocab=32, d_model=32,
        n_layers=n_layers, n_heads=2, d_ff=64, max_len=seq_len,
        n_experts=n_experts,
    )
    tx = optax.adam(1e-3)
    state = init_lm_state(params, tx)
    sharding = None
    if state_sharding_fn is not None:
        sharding = state_sharding_fn(mesh, state)
        state = jax.device_put(state, sharding)
    step = make_lm_train_step(module.apply, tx, mesh,
                              state_sharding=sharding, aux=aux)
    toks = np.random.default_rng(seed).integers(
        0, 32, size=(batch, seq_len)).astype(np.int32)
    gtoks = jax.device_put(toks, token_sharding(mesh))
    return step, (state, gtoks), {"param_bytes": tree_bytes(state.params)}


def regime_dp_sp_ring(devices, window=None):
    """(2,4) dp × sp — ring attention, dense causal (xla carry body)."""
    from jax.sharding import Mesh

    from tpudist.parallel import make_ring_attention
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_SEQ

    mesh = Mesh(np.asarray(devices).reshape(2, 4),
                axis_names=(AXIS_DATA, AXIS_SEQ))
    ring = 4
    seq_len, batch = 64, 4
    attn = make_ring_attention(mesh, causal=True, batch_axis=AXIS_DATA,
                               window=window, kernel="xla")
    step, args, info = _lm_regime(mesh, attention_fn=attn, seq_len=seq_len,
                                  batch=batch)
    # One KV head-split shard: [b_local, heads, seq/ring, head_dim] f32.
    b_local = batch // 2
    kv_shard_bytes = b_local * 2 * (seq_len // ring) * 16 * 4
    # Hops the ring actually executes (the windowed ring breaks early —
    # tpudist/parallel/ring_attention.py:190).
    block = seq_len // ring
    hops = 0
    for s in range(ring):
        if window is not None and window - (s + 1) * block <= -(block - 1):
            break
        if s + 1 < ring:
            hops += 1
    info.update({
        "mesh": {"data": 2, "seq": ring},
        "kv_shard_bytes": kv_shard_bytes,
        "ring_hops_fwd": hops,
        "window": window,
    })
    return step, args, info


def regime_dp_sp_tp(devices):
    """(2,2,2) dp × sp × tp — ring attention + Megatron-style TP weights."""
    from jax.sharding import Mesh

    from tpudist.models.transformer import transformer_tp_sharding
    from tpudist.parallel import make_ring_attention
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL, AXIS_SEQ

    mesh = Mesh(np.asarray(devices).reshape(2, 2, 2),
                axis_names=(AXIS_DATA, AXIS_SEQ, AXIS_MODEL))
    attn = make_ring_attention(mesh, causal=True, batch_axis=AXIS_DATA,
                               kernel="xla")

    def shard_fn(mesh, state):
        return transformer_tp_sharding(mesh, state)

    step, args, info = _lm_regime(mesh, attention_fn=attn, seq_len=32,
                                  batch=4, state_sharding_fn=shard_fn)
    info["mesh"] = {"data": 2, "seq": 2, "model": 2}
    return step, args, info


def regime_dp_ep_moe(devices):
    """(4,2) dp × ep — MoE with all_to_all token exchange."""
    from jax.sharding import Mesh

    from tpudist.models.transformer import moe_expert_fn
    from tpudist.parallel import make_moe
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL

    mesh = Mesh(np.asarray(devices).reshape(4, 2),
                axis_names=(AXIS_DATA, AXIS_MODEL))
    ep = 2
    seq_len, batch, d_model = 16, 8, 32
    capacity_factor = 2.0
    moe_fn = make_moe(mesh, moe_expert_fn, batch_axis=AXIS_DATA,
                      capacity_factor=capacity_factor)
    step, args, info = _lm_regime(mesh, moe_fn=moe_fn, seq_len=seq_len,
                                  batch=batch, n_experts=ep, aux=True)
    # moe_shard tokens: per-device batch rows × seq flattened =
    # (batch/dp)·seq; capacity = cf·k·tokens/experts; buffer [ep, cap, d].
    tokens_local = (batch // 4) * seq_len
    capacity = int(capacity_factor * 1 * tokens_local / ep + 0.5)
    info.update({
        "mesh": {"data": 4, "model": ep},
        "a2a_buffer_bytes": ep * capacity * d_model * 4,
        "capacity": capacity,
    })
    return step, args, info


def regime_fsdp(devices):
    """(8,) ZeRO-3: fully-sharded params/opt-state as a pure layout."""
    from jax.sharding import Mesh

    from tpudist.parallel import fsdp_sharding
    from tpudist.runtime.mesh import AXIS_DATA
    from tpudist.utils.hlo_audit import tree_bytes

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
    min_size = 64

    holder = {}

    def shard_fn(mesh, state):
        sh = fsdp_sharding(mesh, state, min_size=min_size)
        holder["sharding"] = sh
        holder["state"] = state
        return sh

    step, args, info = _lm_regime(mesh, seq_len=16, batch=8,
                                  state_sharding_fn=shard_fn)
    # Analytic split: bytes of param leaves that actually shard vs replicate.
    import jax as _jax
    from jax.sharding import NamedSharding

    sharded_b = repl_b = 0
    for leaf, sh in zip(
        _jax.tree.leaves(holder["state"].params),
        _jax.tree.leaves(holder["sharding"].params,
                         is_leaf=lambda x: isinstance(x, NamedSharding)),
    ):
        b = int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
        if all(a is None for a in tuple(sh.spec)):
            repl_b += b
        else:
            sharded_b += b
    info.update({
        "mesh": {"data": 8},
        "sharded_param_bytes": sharded_b,
        "replicated_param_bytes": repl_b,
    })
    return step, args, info


def regime_dp_zero1(devices):
    """(8,) ZeRO-1: replicated params, data-sharded optimizer state — the
    weight-update sharding of arXiv:2004.13336 as a pure layout."""
    from jax.sharding import Mesh

    from tpudist.parallel import zero1_sharding
    from tpudist.runtime.mesh import AXIS_DATA

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))

    holder = {}

    def shard_fn(mesh, state):
        sh = zero1_sharding(mesh, state, min_size=64)
        holder["sharding"] = sh
        holder["state"] = state
        return sh

    step, args, info = _lm_regime(mesh, seq_len=16, batch=8,
                                  state_sharding_fn=shard_fn)
    import jax as _jax
    from jax.sharding import NamedSharding

    sharded_opt = 0
    for leaf, sh in zip(
        _jax.tree.leaves(holder["state"].opt_state),
        _jax.tree.leaves(holder["sharding"].opt_state,
                         is_leaf=lambda x: isinstance(x, NamedSharding)),
    ):
        if not all(a is None for a in tuple(sh.spec)):
            sharded_opt += int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
    info.update({"mesh": {"data": 8}, "sharded_opt_bytes": sharded_opt,
                 "param_bytes": info.get("param_bytes")})
    return step, args, info


def _pp_regime(devices, schedule):
    import jax
    import optax

    from jax.sharding import Mesh

    from tpudist.models import create_transformer
    from tpudist.parallel import (
        make_pp_lm_train_step,
        pp_state_sharding,
        stack_block_params,
    )
    from tpudist.runtime.mesh import AXIS_DATA, AXIS_STAGE
    from tpudist.train import init_lm_state, token_sharding
    from tpudist.utils.hlo_audit import tree_bytes

    interleaved = schedule == "interleaved"
    n_chunks = 2 if interleaved else 1
    dp, stages = 2, 4
    # Interleaved needs M % stages == 0 (Megatron grouping) and layers
    # divisible into stages*n_chunks virtual stages.
    micro, batch, n_layers = ((4, 8, 8) if interleaved else (2, 4, 4))
    mesh = Mesh(np.asarray(devices).reshape(dp, stages),
                axis_names=(AXIS_DATA, AXIS_STAGE))
    seq_len, d_model = 16, 32
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=seq_len, vocab=32, d_model=d_model,
        n_layers=n_layers, n_heads=2, d_ff=64, max_len=seq_len,
    )
    if interleaved:
        from tpudist.parallel import stack_block_params_interleaved

        pp_params = stack_block_params_interleaved(params, stages, n_chunks)
    else:
        pp_params = stack_block_params(params, n_stages=stages)
    tx = optax.adam(1e-3)
    state = init_lm_state(pp_params, tx)
    sharding = pp_state_sharding(mesh, state)
    state = jax.device_put(state, sharding)
    step = make_pp_lm_train_step(
        mesh, module, tx, n_stages=stages, num_microbatches=micro,
        schedule=schedule, n_chunks=n_chunks, state_sharding=sharding,
    )
    toks = np.random.default_rng(2).integers(
        0, 32, size=(batch, seq_len)).astype(np.int32)
    args = (state, jax.device_put(toks, token_sharding(mesh)))
    # Per-hop payload: one microbatch's activations [b/dp/micro, seq, d].
    act_bytes = (batch // dp // micro) * seq_len * d_model * 4
    return step, args, {
        "mesh": {"data": dp, "stage": stages},
        "param_bytes": tree_bytes(state.params),
        "microbatch_act_bytes": act_bytes,
        "n_stages": stages,
        "num_microbatches": micro,
    }


def _tp_mlp_regime(devices, overlap):
    """(8,) model axis: the explicit TP MLP (column→row pair), fwd+bwd.

    ``overlap=None`` audits the default psum body — ONE exposed
    all-reduce of the output.  ``overlap='ring'/'bidir'`` audits the
    collective-matmul body: the wire traffic must have moved whole into
    OVERLAP_SCOPE-tagged ppermute chunks (pipelined against the chunk
    matmuls), with no monolithic all-gather/all-reduce left.
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudist.parallel import init_mlp_params, mlp_param_sharding
    from tpudist.parallel.tensor_parallel import (tp_mlp_overlap_shard,
                                                  tp_mlp_shard)
    from tpudist.runtime.mesh import AXIS_MODEL

    n = 8
    batch, d, f = 64, 32, 128
    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_MODEL,))
    params = init_mlp_params(jax.random.PRNGKey(0), d, f)
    gparams = jax.device_put(params, mlp_param_sharding(mesh, params))
    param_specs = {"w1": P(None, AXIS_MODEL), "b1": P(AXIS_MODEL),
                   "w2": P(AXIS_MODEL, None), "b2": P()}
    if overlap is None:
        body = functools.partial(tp_mlp_shard, axis_name=AXIS_MODEL)
        x_spec = P(None, None)
    else:
        body = functools.partial(tp_mlp_overlap_shard, axis_name=AXIS_MODEL,
                                 mode=overlap)
        x_spec = P(AXIS_MODEL, None)

    def shard_loss(p, x):
        def local_loss(pp):
            out = body(pp, x)
            loss = jnp.sum(out * out)
            if overlap is not None:
                # batch rows are sharded here; the default body's loss is
                # already replicated (post-psum output)
                loss = lax.psum(loss, AXIS_MODEL)
            return loss

        return jax.value_and_grad(local_loss)(p)

    sharded = jax.shard_map(
        shard_loss, mesh=mesh, in_specs=(param_specs, x_spec),
        out_specs=(P(), param_specs), check_vma=False)
    step = jax.jit(sharded)
    x = jax.device_put(
        jnp.asarray(np.random.default_rng(1).standard_normal((batch, d)),
                    jnp.float32),
        NamedSharding(mesh, x_spec))
    info = {
        "mesh": {"model": n},
        "overlap": overlap or "off",
        "out_bytes": batch * d * 4,
        # one pipelined chunk: a [batch/n, d] row block (x hops in the
        # gather ring, accumulator hops in the reduce-scatter ring,
        # cotangents retrace both — all the same chunk shape)
        "chunk_bytes": (batch // n) * d * 4,
        "ring": n,
    }
    return step, (gparams, x), info


def regime_tp_mlp(devices):
    return _tp_mlp_regime(devices, None)


def regime_tp_mlp_overlap_ring(devices):
    return _tp_mlp_regime(devices, "ring")


def regime_tp_mlp_overlap_bidir(devices):
    return _tp_mlp_regime(devices, "bidir")


def _fsdp_overlap_regime(devices, mode):
    """(8,) ZeRO-3 LM with the overlapped FFN compute: the FFN kernels
    stream into the ppermute pipeline SHARDED — the partitioner's
    monolithic pre-matmul all-gather of wi/wo must be gone, its bytes
    moved into OVERLAP_SCOPE-tagged chunk permutes."""
    from jax.sharding import Mesh

    from tpudist.parallel import fsdp_sharding
    from tpudist.runtime.mesh import AXIS_DATA
    from tpudist.train import fsdp_overlap_mlp_fn

    mesh = Mesh(np.asarray(devices), axis_names=(AXIS_DATA,))
    min_size = 64
    n = 8
    d_model, d_ff, n_layers = 32, 64, 1

    holder = {}

    def shard_fn(mesh, state):
        sh = fsdp_sharding(mesh, state, min_size=min_size)
        holder["sharding"] = sh
        holder["state"] = state
        return sh

    mlp_fn = fsdp_overlap_mlp_fn(mesh, overlap=mode)
    step, args, info = _lm_regime(mesh, seq_len=16, batch=8,
                                  state_sharding_fn=shard_fn,
                                  mlp_fn=mlp_fn)
    import jax as _jax
    from jax.sharding import NamedSharding

    sharded_b = repl_b = 0
    for leaf, sh in zip(
        _jax.tree.leaves(holder["state"].params),
        _jax.tree.leaves(holder["sharding"].params,
                         is_leaf=lambda x: isinstance(x, NamedSharding)),
    ):
        b = int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
        if all(a is None for a in tuple(sh.spec)):
            repl_b += b
        else:
            sharded_b += b
    ffn_kernel_bytes = d_model * d_ff * 4  # each of wi / wo, per layer
    info.update({
        "mesh": {"data": n},
        "overlap": mode,
        "sharded_param_bytes": sharded_b,
        "replicated_param_bytes": repl_b,
        "n_layers": n_layers,
        "ffn_kernel_bytes": ffn_kernel_bytes,
        "ffn_shard_bytes": ffn_kernel_bytes // n,
        "ring": n,
    })
    return step, args, info


def regime_fsdp_overlap_ring(devices):
    return _fsdp_overlap_regime(devices, "ring")


def regime_fsdp_overlap_bidir(devices):
    return _fsdp_overlap_regime(devices, "bidir")


def _serve_decode_regime(devices, overlap):
    """(1,4) serving mesh: the slot engine's fused ``decode_block``
    program with params + dense slot KV TP-sharded over kv-heads/output
    dims (tpudist/serve/spmd.py — the byte-identity layout).

    ``overlap=None`` audits the layout-only path: the column-sharded
    ``wi`` leaves the FFN activation sharded on ``d_ff``, so the
    partitioner all-gathers it whole BEFORE the replicated ``wo``
    matmul — exposed wire on the decode critical path.  ``'ring'``/
    ``'bidir'`` route both FFN matmuls through ``ag_matmul`` (the
    serve mlp_fn): the kernels stay sharded at rest and ride
    OVERLAP_SCOPE-tagged ppermute chunks pipelined under the chunk
    matmuls — no monolithic kernel-or-activation gather in the FFN, and
    the decode path's collective bytes classify overlapped."""
    import jax
    import jax.numpy as jnp

    from tpudist.models import create_transformer
    from tpudist.models.generate import make_slot_decode
    from tpudist.serve import spmd
    from tpudist.utils.hlo_audit import tree_bytes

    n = 4
    cfg = spmd.ServeMeshConfig(shape=f"1x{n}",
                               tp_overlap=overlap or "off")
    mesh = spmd.build_serve_mesh(cfg)
    d_model, d_ff, n_layers, n_heads = 32, 128, 2, 4
    mlp_fn = (spmd.serve_overlap_mlp_fn(mesh, mode=overlap)
              if overlap else None)
    module, params = create_transformer(
        jax.random.PRNGKey(0), seq_len=16, vocab=64, d_model=d_model,
        n_layers=n_layers, n_heads=n_heads, n_kv_heads=n_heads,
        d_ff=d_ff, max_len=64, mlp_fn=mlp_fn)
    psh = spmd.serve_param_sharding(mesh, params,
                                    overlap=overlap is not None)
    gparams = jax.device_put(params, psh)

    def constraint(tree):
        return jax.lax.with_sharding_constraint(
            tree, spmd.serve_cache_sharding(mesh, tree))

    S, pad, k = 4, 8, 4
    fns = make_slot_decode(module, gparams, S, pad,
                           cache_constraint=constraint)
    state = jax.device_put(
        fns.init_state(), spmd.serve_state_sharding(mesh, fns.init_state()))
    cache = jax.device_put(
        fns.init_slots(),
        spmd.serve_cache_sharding(mesh, fns.init_slots()))
    wi_shard = d_model * d_ff * 4 // n
    wo_shard = d_ff * d_model * 4 // n
    info = {
        "mesh": {"data": 1, "model": n},
        "overlap": overlap or "off",
        "ring": n,
        "n_layers": n_layers,
        "decode_k": k,
        "param_bytes": tree_bytes(params),
        "ffn_kernel_bytes": d_model * d_ff * 4,
        "wi_shard_bytes": wi_shard,
        "wo_shard_bytes": wo_shard,
        # the FFN activation the layout-only path must gather whole:
        # [S, 1, d_ff] f32
        "ff_act_bytes": S * d_ff * 4,
    }
    return fns.decode_block, (state, cache, k), info


def regime_serve_decode_tp(devices):
    return _serve_decode_regime(devices, None)


def regime_serve_decode_tp_ring(devices):
    return _serve_decode_regime(devices, "ring")


def regime_serve_decode_tp_bidir(devices):
    return _serve_decode_regime(devices, "bidir")


def regime_dp_pp_gpipe(devices):
    return _pp_regime(devices, "gpipe")


def regime_dp_pp_1f1b(devices):
    return _pp_regime(devices, "1f1b")


def regime_dp_pp_interleaved(devices):
    return _pp_regime(devices, "interleaved")


REGIMES = {
    "dp": regime_dp,
    "dp_bf16_reduce": regime_dp_bf16_reduce,
    "dp_model_split": regime_dp_model_split,
    "dp_sp_ring": regime_dp_sp_ring,
    "dp_sp_ring_window": lambda d: regime_dp_sp_ring(d, window=12),
    "dp_sp_tp": regime_dp_sp_tp,
    "dp_ep_moe": regime_dp_ep_moe,
    "fsdp": regime_fsdp,
    "dp_zero1": regime_dp_zero1,
    "dp_pp_gpipe": regime_dp_pp_gpipe,
    "dp_pp_1f1b": regime_dp_pp_1f1b,
    "dp_pp_interleaved": regime_dp_pp_interleaved,
    # collective-matmul overlap family (tpudist/parallel/overlap.py):
    # the default TP psum body vs the ppermute-pipelined twins, and the
    # FSDP LM step with the FFN gathers moved into the pipeline.  fsdp
    # MUST precede fsdp_overlap_* (their checks compare against it).
    "tp_mlp": regime_tp_mlp,
    "tp_mlp_overlap_ring": regime_tp_mlp_overlap_ring,
    "tp_mlp_overlap_bidir": regime_tp_mlp_overlap_bidir,
    "fsdp_overlap_ring": regime_fsdp_overlap_ring,
    "fsdp_overlap_bidir": regime_fsdp_overlap_bidir,
    # the TP serving decode path (tpudist/serve/spmd.py): layout-only
    # baseline (exposed activation gather) vs the ag_matmul-routed
    # variants (kernel bytes in overlap-tagged ppermute chunks)
    "serve_decode_tp": regime_serve_decode_tp,
    "serve_decode_tp_ring": regime_serve_decode_tp_ring,
    "serve_decode_tp_bidir": regime_serve_decode_tp_bidir,
}


# ---------------------------------------------------------------------------
# Checks: analytic predictions vs measured HLO profile.  Each returns a
# list of {check, expected, measured, ok}.
# ---------------------------------------------------------------------------


def _c(name, expected, measured, ok=None):
    if ok is None:
        ok = expected == measured
    return {"check": name, "expected": expected, "measured": measured,
            "ok": bool(ok)}


def check_dp(prof, info):
    ar = prof.get("all-reduce",
                  {"count": 0, "bytes_total": 0, "count_in_loop": 0})
    payload = info["param_bytes"] + 4 * info["n_loss_scalars"]
    n = info["mesh"]["data"]
    from tpudist.utils.hlo_audit import ring_allreduce_wire_bytes

    info["predicted_wire_bytes_per_device"] = ring_allreduce_wire_bytes(
        payload, n)
    return [
        _c("only collective kind is all-reduce", ["all-reduce"],
           sorted(prof)),
        _c("one combined gradient all-reduce", 1, ar["count"]),
        _c("all-reduce payload = grad + loss bytes", payload,
           ar["bytes_total"]),
        _c("no loop-resident collectives", 0, ar["count_in_loop"]),
    ]


def check_dp_bf16_reduce(prof, info):
    ar = prof.get("all-reduce",
                  {"count": 0, "bytes_total": 0, "count_in_loop": 0,
                   "instructions": []})
    # Wire payload: every f32 param-grad rides at 2 bytes (half) + the
    # f32 loss scalar's 4.  Checked on the pre-opt HLO (info["pre_opt"])
    # — exactly one f32 instruction (the loss) and the rest bf16.
    payload = info["param_bytes"] // 2 + 4
    f32_instrs = [i for i in ar["instructions"] if "f32[" in i["shape"]]
    return [
        _c("only collective kind is all-reduce", ["all-reduce"],
           sorted(prof)),
        _c("all-reduce payload = bf16 grads + f32 loss", payload,
           ar["bytes_total"]),
        _c("single f32 scalar reduce (the loss); grads all narrow", 1,
           len(f32_instrs)),
        _c("no loop-resident collectives", 0, ar["count_in_loop"]),
    ]


def check_dp_model_split(prof, info):
    ar = prof.get("all-reduce", {"count": 0, "bytes_total": 0})
    # Split weights: grads of model-sharded leaves all-reduce over the data
    # groups only (payload counts the SHARD bytes on the wire schedule, but
    # HLO operand shapes are global) — so payload stays >= param bytes and
    # < param bytes + slack for losses/boundary activations.
    lo = info["param_bytes"]
    hi = info["param_bytes"] + 4096
    checks = [
        _c("collective kinds", True,
           sorted(prof),
           ok=set(prof) <= {"all-reduce", "all-gather",
                            "collective-permute"}),
        _c("all-reduce payload within [params, params+4KB]",
           {"lo": lo, "hi": hi}, ar["bytes_total"],
           ok=lo <= ar["bytes_total"] <= hi),
    ]
    return checks


def check_ring(prof, info):
    cp = prof.get("collective-permute",
                  {"count": 0, "bytes_total": 0, "count_in_loop": 0,
                   "instructions": []})
    ar = prof.get("all-reduce", {"instructions": []})
    hops = info["ring_hops_fwd"]
    kv = info["kv_shard_bytes"]
    # Forward: K and V hop once per executed ring step → 2·hops permutes;
    # the backward retraces the reversed ring with the K/V cotangents →
    # 2·hops more.  Every one moves exactly one KV shard.  (Anything else —
    # e.g. sub-KV-size bookkeeping permutes — must stay tiny.)
    kv_sized = [i for i in cp["instructions"] if i["bytes"] == kv]
    extras = [i for i in cp["instructions"] if i["bytes"] != kv]
    grad_ar = max((i["bytes"] for i in ar["instructions"]), default=0)
    return [
        _c("4·hops KV-shard permutes (K,V × fwd,bwd)", 4 * hops,
           len(kv_sized)),
        _c("non-KV permutes are bookkeeping (<512B)", True,
           all(i["bytes"] < 512 for i in extras)),
        _c("permutes are unrolled (none loop-resident)", 0,
           cp["count_in_loop"]),
        _c("largest all-reduce = grad+loss bytes",
           info["param_bytes"] + 4, grad_ar),
        _c("no all_to_all / reduce-scatter", True,
           not ({"all-to-all", "reduce-scatter"} & set(prof))),
    ]


def check_ring_window(prof, info, dense_prof):
    cp = prof.get("collective-permute", {"count": 0})
    dense_cp = dense_prof.get("collective-permute", {"count": 0})
    checks = check_ring(prof, info)
    checks.append(
        _c("windowed ring needs fewer permutes than dense",
           {"dense": dense_cp["count"]}, cp["count"],
           ok=cp["count"] < dense_cp["count"]))
    return checks


def check_tp(prof, info):
    ar = prof.get("all-reduce", {"count": 0, "bytes_total": 0})
    return [
        _c("all-reduce present (TP activations + grads)", True,
           ar["count"] > 0),
        _c("ring permutes present (sp axis)", True,
           prof.get("collective-permute", {"count": 0})["count"] > 0),
        _c("no all_to_all", True, "all-to-all" not in prof),
    ]


def check_moe(prof, info):
    a2a = prof.get("all-to-all",
                   {"count": 0, "bytes_total": 0, "instructions": []})
    buf = info["a2a_buffer_bytes"]
    per_instr_ok = all(i["bytes"] == buf for i in a2a["instructions"])
    return [
        _c("4 all_to_alls (dispatch+return, fwd+bwd)", 4, a2a["count"]),
        _c("each all_to_all moves the capacity buffer", True, per_instr_ok),
        _c("grad all-reduce present", True, "all-reduce" in prof),
    ]


def check_zero1(prof, info):
    ar = prof.get("all-reduce",
                  {"count": 0, "bytes_total": 0, "count_in_loop": 0})
    ag = prof.get("all-gather",
                  {"count": 0, "bytes_total": 0, "count_in_loop": 0})
    # ZeRO-1's wire signature: plain-DP gradient all-reduce (params are
    # replicated, so backward is untouched) + one all-gather per sharded
    # updated param — total exactly the sharded param bytes, i.e. half
    # the sharded Adam-moment bytes (mu + nu mirror the params).
    return [
        _c("collective kinds are all-reduce + all-gather",
           ["all-gather", "all-reduce"], sorted(prof)),
        _c("one combined gradient all-reduce", 1, ar["count"]),
        _c("all-reduce payload = grad + loss bytes",
           info["param_bytes"] + 4, ar["bytes_total"]),
        _c("all-gathered update bytes = sharded param bytes",
           info["sharded_opt_bytes"] // 2, ag["bytes_total"]),
        _c("no loop-resident collectives", 0,
           ar["count_in_loop"] + ag["count_in_loop"]),
    ]


def check_fsdp(prof, info):
    ag = prof.get("all-gather", {"count": 0, "bytes_total": 0})
    rs = prof.get("reduce-scatter", {"count": 0, "bytes_total": 0})
    ar = prof.get("all-reduce", {"count": 0, "bytes_total": 0})
    sb = info["sharded_param_bytes"]
    # Gradient reduction: the partitioner may emit either the ZeRO-canonical
    # reduce-scatter (each device keeps its shard) or a full all-reduce it
    # then slices (profitable at small sizes) — record which, require the
    # sharded-grad bytes covered either way.
    info["grad_reduction_form"] = (
        "reduce-scatter" if rs["bytes_total"] >= sb else
        "all-reduce" if ar["bytes_total"] >= sb else "missing"
    )
    return [
        # Exactly one gather per sharded param: XLA keeps the gathered f32
        # copy live across fwd+bwd at this model size instead of
        # re-gathering (the ZeRO-3 memory/traffic trade, chosen by the
        # compiler).  Equality is the strong claim.
        _c("all-gather bytes == sharded param bytes (gathered once)",
           sb, ag["bytes_total"]),
        _c("sharded grads reduced (reduce-scatter or all-reduce)", True,
           info["grad_reduction_form"] != "missing"),
    ]


def check_tp_mlp(prof, info, split):
    ar = prof.get("all-reduce", {"count": 0, "bytes_total": 0})
    # The psum body: the output all-reduce is the regime's whole wire
    # story, and it is EXPOSED — the matmul that feeds it must finish
    # first, nothing runs under it.  (The backward may add small
    # bias-grad reduces; the floor is the fwd output psum.)
    return [
        _c("output psum present (>= out bytes, all exposed)", True,
           ar["bytes_total"] >= info["out_bytes"]
           and split["overlapped_bytes"] == 0),
        _c("no ppermute pipeline in the default body", True,
           "collective-permute" not in prof),
        _c("no all-gather", True, "all-gather" not in prof),
    ]


def check_tp_mlp_overlap(prof, info, split):
    cp = prof.get("collective-permute",
                  {"count": 0, "bytes_total": 0, "instructions": []})
    ar = prof.get("all-reduce", {"count": 0, "bytes_total": 0,
                                 "instructions": []})
    chunk = info["chunk_bytes"]
    n = info["ring"]
    # Fwd floor: the input gather ring (n-1 chunk hops) + the
    # reduce-scatter ring (n-1 chunk hops); the backward retraces both.
    floor = 2 * (n - 1) * chunk
    # Remaining all-reduces must be bookkeeping-sized (the scalar loss
    # psum and bias-grad reductions), never the [batch, d] output.
    big_ar = [i for i in ar["instructions"] if i["bytes"] >= info["out_bytes"]]
    return [
        _c("monolithic output psum GONE (no out-sized all-reduce)", 0,
           len(big_ar)),
        _c("no monolithic all-gather", True, "all-gather" not in prof),
        _c("wire moved into ppermute chunks (>= 2(n-1) chunk bytes)",
           {"floor": floor}, cp["bytes_total"],
           ok=cp["bytes_total"] >= floor),
        _c("every permute is overlap-pipeline-tagged", True,
           cp["count"] > 0 and all(i["overlapped"]
                                   for i in cp["instructions"])),
        _c("exposed bytes are bookkeeping only (< 1 chunk)", True,
           split["exposed_bytes"] < chunk),
        _c("no loop-resident collectives (chains unrolled)", 0,
           cp.get("count_in_loop", 0)),
    ]


def check_fsdp_overlap(prof, info, split, dense_prof):
    ag = prof.get("all-gather", {"count": 0, "bytes_total": 0,
                                 "instructions": []})
    cp = prof.get("collective-permute",
                  {"count": 0, "bytes_total": 0, "instructions": []})
    kb = info["ffn_kernel_bytes"]
    shard = info["ffn_shard_bytes"]
    n = info["ring"]
    layers = info["n_layers"]
    # Per layer: wi column ring (n-1 shard hops) + wo contraction ring
    # (n-1 shard hops) in forward; backward retraces both.
    floor = layers * 2 * (n - 1) * shard
    dense_ag = dense_prof.get("all-gather", {"bytes_total": 0})
    # The layout-only fsdp regime gathers every sharded param once
    # (its check asserts equality); here the two FFN kernels per layer
    # must be OUT of the gather budget — they stream sharded into the
    # ppermute pipeline instead.
    budget = info["sharded_param_bytes"] - layers * 2 * kb
    ffn_gathers = [i for i in ag["instructions"]
                   if "/wi/" in i["op_name"] or "/wo/" in i["op_name"]
                   or i["bytes"] == kb]
    return [
        _c("no all-gather of an FFN kernel (by provenance or size)", 0,
           len(ffn_gathers)),
        _c("all-gather bytes fit the non-FFN budget",
           {"budget": budget}, ag["bytes_total"],
           ok=ag["bytes_total"] <= budget),
        _c("FFN wire moved into ppermute chunks (>= 2·layers·(n-1) shards)",
           {"floor": floor}, cp["bytes_total"],
           ok=cp["bytes_total"] >= floor),
        _c("every permute is overlap-pipeline-tagged", True,
           cp["count"] > 0 and all(i["overlapped"]
                                   for i in cp["instructions"])),
        _c("strictly fewer gathered bytes than layout-only fsdp",
           {"fsdp": dense_ag["bytes_total"]}, ag["bytes_total"],
           ok=(dense_ag["bytes_total"] == 0
               or ag["bytes_total"] < dense_ag["bytes_total"])),
        _c("overlapped bytes dominate the permute traffic", True,
           split["overlapped_bytes"] >= cp["bytes_total"]),
    ]


def check_serve_decode_tp(prof, info, split):
    ag = prof.get("all-gather", {"count": 0, "bytes_total": 0,
                                 "instructions": []})
    # The layout-only decode path: the partitioner moves the sharded
    # FFN/attention activations however it likes (observed on this
    # backend: reshard collective-permutes plus a partial-sum
    # all-reduce of each layer's FFN output) — but every one of those
    # bytes is EXPOSED: scheduled on the decode critical path with
    # nothing structurally hidden under compute.  That is the number
    # the overlap routing exists to kill.  (The quoted
    # exposed_fraction lands on the regime row — main() computes it for
    # every regime from the same split.)
    total = split["exposed_bytes"] + split["overlapped_bytes"]
    return [
        _c("decode-path collectives present (TP seams)", True, total > 0),
        _c("ALL collective bytes exposed (nothing pipelined)", 0,
           split["overlapped_bytes"]),
        _c("no kernel ever gathered whole (weights stay sharded)", True,
           all(i["bytes"] < info["ffn_kernel_bytes"]
               for i in ag["instructions"])),
    ]


def check_serve_decode_tp_overlap(prof, info, split):
    cp = prof.get("collective-permute",
                  {"count": 0, "bytes_total": 0, "instructions": []})
    ag = prof.get("all-gather", {"count": 0, "bytes_total": 0,
                                 "instructions": []})
    n, layers = info["ring"], info["n_layers"]
    # Per decode-scan iteration: each layer's wi ring (n-1 chunk hops)
    # + wo ring (n-1 chunk hops).  HLO instruction bytes count the scan
    # body once, so the floor is per-iteration.
    floor = layers * (n - 1) * (info["wi_shard_bytes"]
                                + info["wo_shard_bytes"]) // n
    tagged = sum(i["bytes"] for i in cp["instructions"] if i["overlapped"])
    untagged = cp["bytes_total"] - tagged
    chunk = info["wi_shard_bytes"]
    return [
        _c("FFN kernel bytes ride tagged ppermute chunks (>= floor)",
           {"floor": floor}, tagged, ok=tagged >= floor),
        _c("untagged permutes are partitioner reshards (< 1 chunk)",
           {"chunk": chunk}, untagged, ok=untagged < chunk),
        _c("decode-path collective bytes are majority-overlapped", True,
           split["overlapped_bytes"] > split["exposed_bytes"]),
        _c("no kernel ever gathered whole (weights stay sharded)", True,
           all(i["bytes"] < info["ffn_kernel_bytes"]
               for i in ag["instructions"])),
    ]


def check_pp(prof, info):
    cp = prof.get("collective-permute",
                  {"count": 0, "count_in_loop": 0, "instructions": []})
    act = info["microbatch_act_bytes"]
    # The schedule's stage hops: one activation permute in the forward scan
    # body, one cotangent permute in the backward scan body, each moving
    # one microbatch's activations per tick.  (The off-loop all_to_alls are
    # the dp↔stage microbatch redistribution at the shard_map boundary.)
    loop_act = [i for i in cp["instructions"]
                if i["in_loop"] and i["bytes"] == act]
    return [
        _c("loop-resident stage hops of one microbatch each (fwd+bwd)",
           True, len(loop_act) >= 2),
        _c("all loop permutes are microbatch-sized", True,
           all(i["bytes"] == act for i in cp["instructions"]
               if i["in_loop"])),
        _c("grad all-reduce present (dp axis)", True, "all-reduce" in prof),
        _c("no reduce-scatter", True, "reduce-scatter" not in prof),
    ]


def main(argv=None) -> int:
    from benchmarks._round import current_round  # REPO is on sys.path

    p = argparse.ArgumentParser()
    p.add_argument("--out", default=str(
        REPO / f"COMM_AUDIT_r{current_round():02d}.json"))
    p.add_argument("--only", default=None, help="comma list of regime names")
    p.add_argument("--measure-only", action="store_true",
                   help="print profiles, skip checks")
    args = p.parse_args(argv)

    _force_cpu_mesh(8)
    import jax

    from tpudist.utils.hlo_audit import overlap_split, profile

    devices = jax.devices()[:8]
    wanted = set(args.only.split(",")) if args.only else None

    results, profiles = {}, {}
    n_fail = 0
    for name, builder in REGIMES.items():
        if wanted and name not in wanted:
            continue
        print(f"[comm-audit] lowering {name} ...", flush=True)
        try:
            step, ex_args, info = builder(devices)
            ops = collect_ops(step, ex_args, info)
        except Exception as e:  # noqa: BLE001
            # A regime that cannot BUILD on this box (e.g. a jax API the
            # installed version lacks) is a failed row, not a crashed
            # artifact: later regimes still audit and the file still
            # lands (an error row, the harnesses' convention).
            results[name] = {"error": repr(e), "ok": False}
            n_fail += 1
            print(f"[comm-audit] {name}: ERROR {e!r}", flush=True)
            continue
        prof = profile(ops)
        profiles[name] = prof
        split = overlap_split(ops)
        total = split["exposed_bytes"] + split["overlapped_bytes"]
        row = {"mesh": info.get("mesh"), "info": {
            k: v for k, v in info.items() if k != "mesh"},
            "overlap_split": split,
            "exposed_fraction": (round(split["exposed_bytes"] / total, 4)
                                 if total else None),
            "profile": prof}
        if not args.measure_only:
            if name == "dp":
                checks = check_dp(prof, info)
            elif name == "dp_bf16_reduce":
                checks = check_dp_bf16_reduce(prof, info)
            elif name == "dp_model_split":
                checks = check_dp_model_split(prof, info)
            elif name == "dp_sp_ring":
                checks = check_ring(prof, info)
            elif name == "dp_sp_ring_window":
                checks = check_ring_window(prof, info,
                                           profiles.get("dp_sp_ring", {}))
            elif name == "dp_sp_tp":
                checks = check_tp(prof, info)
            elif name == "dp_ep_moe":
                checks = check_moe(prof, info)
            elif name == "fsdp":
                checks = check_fsdp(prof, info)
            elif name == "dp_zero1":
                checks = check_zero1(prof, info)
            elif name == "tp_mlp":
                checks = check_tp_mlp(prof, info, split)
            elif name.startswith("tp_mlp_overlap"):
                checks = check_tp_mlp_overlap(prof, info, split)
            elif name.startswith("fsdp_overlap"):
                checks = check_fsdp_overlap(prof, info, split,
                                            profiles.get("fsdp", {}))
            elif name == "serve_decode_tp":
                checks = check_serve_decode_tp(prof, info, split)
            elif name.startswith("serve_decode_tp_"):
                checks = check_serve_decode_tp_overlap(prof, info, split)
            else:
                checks = check_pp(prof, info)
            row["checks"] = checks
            row["ok"] = all(c["ok"] for c in checks)
            n_fail += 0 if row["ok"] else 1
            status = "ok" if row["ok"] else "FAIL"
        else:
            status = "measured"
        results[name] = row
        kinds = {k: (v["count"], v["bytes_total"]) for k, v in prof.items()}
        print(f"[comm-audit] {name}: {status}  "
              f"exposed={split['exposed_bytes']} "
              f"overlapped={split['overlapped_bytes']}  {kinds}", flush=True)

    out = {"n_devices": 8, "platform": "cpu-virtual",
           "jax_version": jax.__version__, "regimes": results,
           "failed": n_fail}
    if wanted:
        out["only"] = sorted(wanted)
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps({"regimes": len(results), "failed": n_fail,
                      "out": args.out}))
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
