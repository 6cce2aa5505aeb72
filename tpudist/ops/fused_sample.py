"""Pallas TPU fused-sampling kernels: the decode step's tail in one pass.

After attention, the decode step's tail runs as a string of tiny HLOs —
grammar constrain-mask gather (``_gmask``), greedy argmax, temperature
scale, optional top-k/top-p filtering — each a separate elementwise
dispatch over ``[slots, vocab]``, each round-tripping the logits through
HBM.  :func:`fused_sample_prep` fuses them into one kernel over a
``(slots,)`` grid: the slot's grammar row rides in via a BlockSpec index
map over the scalar-prefetched ``(gidx, gstate)`` coordinates (the same
indirection discipline as the paged-attention block-table walk), and the
kernel emits everything the in-graph tail needs — the constrain-masked
logits (fed unchanged to top-logprobs and the automaton advance), the
temperature-scaled-and-filtered logits (fed to ``categorical``), and the
greedy argmax.

The RANDOM DRAW stays in-graph: ``jax.random.categorical(fold_in(key,
count), scaled)`` consumes the kernel's ``scaled`` output, so the
fold_in substream contract is untouched and sampled streams are
byte-identical to the unfused tail (division by ``max(temp, 1e-6)`` is
the same op either way).  Masking uses ``finfo(dtype).min`` — the same
constant as ``_gmask`` — so greedy streams are byte-identical too.

:func:`fused_residual_prep` is the speculative-verify sibling: it fuses
``_accept``'s per-(slot, draft-position) softmax pair and residual
distribution (``max(p_target - p_draft, 0)``, log with the 1e-30 floor,
``lt/temp`` fallback when the residual is empty) into one kernel over a
``(slots, k)`` grid.  Acceptance tests, clamping, and all draws stay
in-graph — the kernel only replaces elementwise dispatches, so the
accept/reject decisions are bit-identical.

The sort-based filters (top-k / top-p) are NOT in the kernel — Mosaic
lowers neither ``sort`` nor ``top_k`` — they run in-graph on the
kernel's ``scaled`` output (:func:`_filter`, shared with the reference,
so the op order and the results are the unfused tail's).  Rows ride as
``(1, V)`` blocks of ``[S, 1, V]`` views: a second-last block dim of 1
must be the whole axis under Mosaic's (8, 128) block rule.

``interpret=True`` (any non-TPU backend) is the tier-1 CPU path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.telemetry import names


def _sample_kernel(temps_ref, gidx_ref, gstate_ref, lg_ref, ga_ref,
                   masked_ref, scaled_ref, greedy_ref, *, grammar: bool):
    """One slot: grammar mask -> greedy argmax -> temperature scale."""
    s = pl.program_id(0)
    lg = lg_ref[0].astype(jnp.float32)                 # [1, V]
    if grammar:
        allow = ga_ref[0, 0]                           # [1, V] bool
        lg = jnp.where(allow, lg, jnp.finfo(jnp.float32).min)
    masked_ref[0] = lg
    greedy_ref[0] = jnp.argmax(lg, axis=-1, keepdims=True).astype(jnp.int32)
    scaled_ref[0] = lg / jnp.maximum(temps_ref[s], 1e-6)


def _filter(sc: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """Static top-k / top-p filters over scaled logits ``[S, V]``,
    value-space semantics matching ``generate.sample_logits``."""
    S, V = sc.shape
    neg = jnp.finfo(sc.dtype).min
    if top_k > 0 and top_k < V:
        # kth-largest cutoff (ties at the threshold all survive)
        kth = jax.lax.top_k(sc, top_k)[0][..., -1:]
        sc = jnp.where(sc < kth, neg, sc)
    if 0.0 < top_p < 1.0:
        # nucleus: smallest prefix of the sorted probs reaching top_p,
        # the top token force-kept — the shifted-cumsum form
        srt = jnp.sort(sc, axis=-1)[..., ::-1]
        cum = jnp.cumsum(jax.nn.softmax(srt, axis=-1), axis=-1)
        keep = jnp.concatenate(
            [jnp.zeros((S, 1), cum.dtype), cum[..., :-1]], axis=-1) < top_p
        keep = keep.at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        sc = jnp.where(sc < cutoff, neg, sc)
    return sc


def fused_sample_prep(
    logits: jax.Array,
    temps: jax.Array,
    gallow: jax.Array | None = None,
    gidx: jax.Array | None = None,
    gstate: jax.Array | None = None,
    *,
    top_k: int = 0,
    top_p: float = 0.0,
    interpret: bool = False,
):
    """Fused sampling prep over ``logits [S, V]``.

    - ``temps [S]`` f32 — per-slot temperatures (0 = greedy; the caller
      selects greedy vs sampled exactly like ``_slot_sample``);
    - ``gallow [G+1, n_states, V]`` bool / ``gidx [S]`` / ``gstate [S]``
      — the grammar pool's allow table and each slot's (program, state)
      coordinates (``gidx`` rows are always valid — unconstrained slots
      point at the sentinel all-True program), or all ``None`` for no
      grammar;
    - ``top_k`` (0 = off) / ``top_p`` (0.0 = off) — static filters
      applied in-graph to the kernel's scaled logits (:func:`_filter`).

    Returns ``(masked [S, V] f32, scaled [S, V] f32, greedy [S] i32)``:
    ``masked`` is the constrain-masked logits (feed to top-logprobs /
    automaton advance), ``scaled`` the temperature-scaled filtered
    logits (feed to ``categorical``), ``greedy`` the argmax of
    ``masked``.
    """
    S, V = logits.shape
    grammar = gallow is not None
    temps = temps.astype(jnp.float32)

    def row(s, *_):
        return (s, 0, 0)

    if grammar:
        G1, n_states, _ = gallow.shape

        def ga_index(s, t, gi, gs):
            return (jnp.minimum(gi[s], G1 - 1),
                    jnp.minimum(gs[s], n_states - 1), 0, 0)

        scalars = (temps, gidx.astype(jnp.int32), gstate.astype(jnp.int32))
        in_specs = [
            pl.BlockSpec((1, 1, V), row),
            pl.BlockSpec((1, 1, 1, V), ga_index),
        ]
        operands = scalars + (logits[:, None], gallow[:, :, None])
    else:
        zero = jnp.zeros((S,), jnp.int32)
        scalars = (temps, zero, zero)
        in_specs = [pl.BlockSpec((1, 1, V), row)]
        operands = scalars + (logits[:, None],)

    def kernel(*refs):
        if grammar:
            t_ref, gi_ref, gs_ref, lg_ref, ga_ref = refs[:5]
            outs = refs[5:8]
        else:
            t_ref, gi_ref, gs_ref, lg_ref = refs[:4]
            ga_ref = None
            outs = refs[4:7]
        _sample_kernel(t_ref, gi_ref, gs_ref, lg_ref, ga_ref, *outs,
                       grammar=grammar)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, V), row),
            pl.BlockSpec((1, 1, V), row),
            pl.BlockSpec((1, 1, 1), row),
        ],
    )
    masked, scaled, greedy = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((S, 1, V), jnp.float32),
            jax.ShapeDtypeStruct((S, 1, V), jnp.float32),
            jax.ShapeDtypeStruct((S, 1, 1), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        **names.kernel(names.FUSED_SAMPLE),
        interpret=interpret,
    )(*operands)
    return (masked[:, 0], _filter(scaled[:, 0], top_k, top_p),
            greedy[:, 0, 0])


def fused_sample_reference(
    logits: jax.Array,
    temps: jax.Array,
    gallow: jax.Array | None = None,
    gidx: jax.Array | None = None,
    gstate: jax.Array | None = None,
    *,
    top_k: int = 0,
    top_p: float = 0.0,
):
    """Plain-jnp twin of :func:`fused_sample_prep` — the in-graph tail's
    math, spelled out (and the kernel's equivalence oracle)."""
    S, V = logits.shape
    lg = logits.astype(jnp.float32)
    if gallow is not None:
        allow = gallow[jnp.minimum(gidx, gallow.shape[0] - 1),
                       jnp.minimum(gstate, gallow.shape[1] - 1)]
        lg = jnp.where(allow, lg, jnp.finfo(jnp.float32).min)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    sc = lg / jnp.maximum(temps.astype(jnp.float32), 1e-6)[:, None]
    return lg, _filter(sc, top_k, top_p), greedy


def _residual_kernel(temps_ref, lt_ref, ld_ref, pt_ref, pd_ref, lr_ref):
    """One (slot, draft position): softmax pair + residual logits."""
    s = pl.program_id(0)
    temp = jnp.maximum(temps_ref[s], 1e-6)
    lt = lt_ref[0, 0].astype(jnp.float32) / temp       # [1, V]
    ld = ld_ref[0, 0].astype(jnp.float32) / temp
    pt = jax.nn.softmax(lt, axis=-1)
    pd = jax.nn.softmax(ld, axis=-1)
    pt_ref[0, 0] = pt
    pd_ref[0, 0] = pd
    res = jnp.maximum(pt - pd, 0.0)
    has_res = jnp.sum(res) > 0.0
    lr_ref[0, 0] = jnp.where(has_res, jnp.log(res + 1e-30), lt)


def fused_residual_prep(
    lt: jax.Array,
    ld: jax.Array,
    temps: jax.Array,
    *,
    interpret: bool = False,
):
    """Fused speculative-verify prep over ``lt``/``ld [S, k, V]``
    (target/draft logits at the k draft positions).

    Returns ``(pt, pd, res_logits)``, each ``[S, k, V]`` f32 —
    temperature-softmaxed target/draft distributions and the residual
    sampling logits (``log(max(pt - pd, 0) + 1e-30)``, falling back to
    ``lt/temp`` where the residual is empty) — exactly ``_accept``'s
    elementwise block, one kernel instead of a dispatch string.
    """
    S, k, V = lt.shape
    temps = temps.astype(jnp.float32)

    def index(s, j, *_):
        return (s, j, 0, 0)

    # [S, k, 1, V] views: the (1, V) row block's dims are whole-array
    spec = pl.BlockSpec((1, 1, 1, V), index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, k),
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
    )
    outs = pl.pallas_call(
        _residual_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((S, k, 1, V), jnp.float32),) * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        **names.kernel(names.FUSED_RESIDUAL),
        interpret=interpret,
    )(temps, lt[:, :, None], ld[:, :, None])
    pt, pd, lr = (o[:, :, 0] for o in outs)
    return pt, pd, lr


def fused_residual_reference(lt, ld, temps):
    """Plain-jnp twin of :func:`fused_residual_prep` (the `_accept`
    formulas, verbatim)."""
    temp = jnp.maximum(temps.astype(jnp.float32), 1e-6)[:, None, None]
    pt = jax.nn.softmax(lt.astype(jnp.float32) / temp, axis=-1)
    pd = jax.nn.softmax(ld.astype(jnp.float32) / temp, axis=-1)
    res = jnp.maximum(pt - pd, 0.0)
    has_res = jnp.sum(res, axis=-1, keepdims=True) > 0.0
    lr = jnp.where(has_res, jnp.log(res + 1e-30),
                   lt.astype(jnp.float32) / temp)
    return pt, pd, lr
