"""SPMD sharding for the slot-decode serving engine.

The four compiled serving programs (``insert_batch`` / ``prefill_extend``
/ ``decode_block`` / ``evict``) are ordinary ``jax.jit`` programs, so
running them over a multi-chip ``jax.sharding.Mesh`` is a LAYOUT change,
not a code change: params and the KV storage get ``NamedSharding``s, the
host engine keeps issuing the exact same fixed-shape programs, and the
XLA SPMD partitioner splits the work (veScale's eager-SPMD consistency
argument, arXiv:2509.07003 — single-device semantics preserved while
shardings, not programs, vary).  This module owns those layouts plus the
one place serving code *does* change shape: the overlapped TP MLP.

Mesh axes (``data × model``, either may be 1):

- ``model`` — tensor parallelism.  The KV cache/pool shards over the
  **kv-heads** axis (attention is per-head independent, so the dominant
  serving bytes split with zero cross-device reduction), and the
  column-parallel weight matrices (``qkv``, ``wi``, ``head`` — plus
  ``wo`` when the overlapped MLP runs) shard over their OUTPUT dim.
- ``data`` — slot parallelism: the dense per-slot cache arenas shard
  over the slot axis (each device group owns a slice of the lanes).
  The paged pool has no per-slot storage axis; ``data`` is a no-op
  there (pool shards over kv-heads only).

**The byte-identity invariant.**  Every dim these layouts shard is an
*output* or *batch* dim — never a contraction and never a
normalized-reduction dim — so the data movement the layout *requests*
is all slices/gathers (bit-exact).  The partitioner retains latitude in
how it re-replicates a sharded activation (the comm audit shows it
sometimes picks partial sums over a gather), so the contract is pinned
where it matters: the serving test suite asserts greedy output
byte-identical to the single-device sequential oracle at every
supported mesh shape, dense and paged, overlap routing on and off (the
same oracle the paged cache and the fused decode block had to meet).
This is also why the serving layout is
NOT :func:`tpudist.models.transformer.transformer_tp_sharding`: the
Megatron row-parallel halves (``proj``/``wo`` row-split) imply a psum
that reassociates the contraction — fine for training (bounded drift),
disqualifying for a serving engine whose acceptance oracle is bitwise.

**Overlapped TP decode.**  With the column layout alone, ``wi``'s
col-sharded product leaves the FFN activation sharded on ``d_ff``, and
the partitioner must move it before the ``wo`` matmul — whatever form
it picks (on the audited backend: reshard collective-permutes plus a
partial-sum all-reduce of the FFN output), those bytes are EXPOSED:
scheduled on the decode critical path, nothing hidden under compute.
:func:`serve_overlap_mlp_fn` instead routes
both FFN matmuls through :func:`tpudist.parallel.overlap.ag_matmul`
(``gather="rhs"``, the bit-exact column geometry): the weight shards
ride a ``ppermute`` ring one chunk per hop, each hop hidden under the
previous chunk's matmul, every hop tagged ``tpudist_overlap`` so
``benchmarks/comm_audit.py``'s ``serve_decode_tp_*`` regimes can prove
from optimized HLO that the decode path's collective bytes are
overlapped, not exposed.  Selection: the ``TPUDIST_SERVE_TP_OVERLAP``
knob (falls back to ``TPUDIST_OVERLAP``; off by default).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL


@dataclasses.dataclass(frozen=True)
class ServeMeshConfig:
    """Declarative serving-mesh geometry (AMP-style: a future planner
    searches these fields, it does not rewrite engine code).

    ``shape``: ``"DxM"`` (data × model) or a bare ``"M"`` (pure TP,
    data = 1).  ``"1"``/``"1x1"``/empty mean no mesh (single device).
    """

    shape: str = "1"
    tp_overlap: Optional[str] = None  # None: knob decides; "off"/"ring"/...

    @property
    def dims(self) -> tuple:
        s = (self.shape or "1").strip().lower().replace("×", "x")
        parts = [p for p in s.split("x") if p]
        try:
            nums = [int(p) for p in parts]
        except ValueError:
            raise ValueError(
                f"serve mesh shape must be 'DxM' or 'M', got {self.shape!r}")
        if len(nums) == 1:
            nums = [1, nums[0]]
        if len(nums) != 2 or any(n < 1 for n in nums):
            raise ValueError(
                f"serve mesh shape must be 'DxM' or 'M', got {self.shape!r}")
        return tuple(nums)

    @property
    def n_devices(self) -> int:
        d, m = self.dims
        return d * m

    @property
    def enabled(self) -> bool:
        return self.n_devices > 1

    @classmethod
    def from_env(cls) -> "ServeMeshConfig":
        import os

        shape = os.environ.get("TPUDIST_SERVE_MESH", "").strip() or "1"
        overlap = os.environ.get("TPUDIST_SERVE_TP_OVERLAP", "").strip()
        return cls(shape=shape, tp_overlap=overlap or None)


def build_serve_mesh(cfg: ServeMeshConfig):
    """``jax.sharding.Mesh`` of shape ``(data, model)`` over the first
    ``data*model`` local devices, or ``None`` when the config is 1x1."""
    if not cfg.enabled:
        return None
    import numpy as np
    from jax.sharding import Mesh

    d, m = cfg.dims
    devs = jax.devices()
    if len(devs) < d * m:
        raise ValueError(
            f"serve mesh {d}x{m} needs {d * m} devices, have {len(devs)} "
            f"({devs[0].platform}); CPU rigs can emulate more via "
            "tpurun --devices-per-proc / "
            "--xla_force_host_platform_device_count")
    return Mesh(np.asarray(devs[:d * m]).reshape(d, m),
                axis_names=(AXIS_DATA, AXIS_MODEL))


def _axis_or_none(mesh, axis: str, dim_size: int):
    """``axis`` if the mesh has it, its size > 1, and it divides
    ``dim_size`` — else ``None`` (replicate).  Sharding an indivisible
    dim is an error in jax; replicating it is merely less parallel."""
    if axis not in mesh.axis_names:
        return None
    n = mesh.shape[axis]
    if n <= 1 or dim_size % n:
        return None
    return axis


def serve_param_sharding(mesh, params, *, overlap: bool = False):
    """NamedSharding pytree for serving params under the byte-identity
    invariant: column-parallel kernels (``qkv``, ``wi``, ``head``) split
    their OUTPUT dim over ``model``; ``wo`` joins them only when the
    overlapped MLP consumes it inside its own ``shard_map`` (the plain
    path would leave a d-sharded residual feeding LayerNorm — a split
    normalized reduction, exactly the thing the invariant forbids);
    ``proj``, embeddings, norms replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    col_names = {"qkv", "wi", "head"} | ({"wo"} if overlap else set())

    def spec_for(path, leaf):
        keys = [k for k in (getattr(e, "key", getattr(e, "name", None))
                            for e in path) if isinstance(k, str)]
        if "kernel" in keys and any(k in col_names for k in keys) \
                and getattr(leaf, "ndim", 0) == 2:
            axis = _axis_or_none(mesh, AXIS_MODEL, leaf.shape[1])
            if axis is not None:
                return NamedSharding(mesh, P(None, axis))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, params)


def serve_spec_param_sharding(mesh, draft_params):
    """Serving layout for a speculative DRAFT model's parameters:
    column-parallel kernels shard over ``model`` where the axis divides
    (same byte-identity-safe column rule as the target), everything
    else replicates.  A draft is small by construction, so replication
    is always correct and usually cheap — the column split is taken
    opportunistically when the draft's head counts allow it (a
    weight-tied draft shares the target's already-sharded params and
    never reaches this function).  The draft CACHE reuses
    :func:`serve_cache_sharding` / :func:`serve_paged_sharding` — its
    arenas have the same axis meaning as the target's."""
    return serve_param_sharding(mesh, draft_params, overlap=False)


def serve_cache_sharding(mesh, cache):
    """Sharding pytree for a DENSE slot cache: the K/V arenas
    ``[num_slots, 1, n_kv, max_len, dh]`` shard slots over ``data`` and
    kv-heads over ``model``; the tiny meta leaves (cursors, position
    counters) replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(leaf):
        if getattr(leaf, "ndim", 0) == 5:
            data = _axis_or_none(mesh, AXIS_DATA, leaf.shape[0])
            model = _axis_or_none(mesh, AXIS_MODEL, leaf.shape[2])
            return NamedSharding(mesh, P(data, None, model))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, cache)


def serve_paged_sharding(mesh, pkv):
    """Sharding pytree for a :class:`tpudist.models.paged.PagedKV`: the
    pools ``[L, num_blocks, n_kv, block_size, dh]`` shard kv-heads over
    ``model`` (block ids stay global — the host allocator is
    topology-oblivious); scales follow their pool's head axis; table and
    meta replicate (they are the host's decisions, uploaded as data)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = _axis_or_none(mesh, AXIS_MODEL, pkv.pool_k.shape[2])
    pool = NamedSharding(mesh, P(None, None, model))
    scale = NamedSharding(mesh, P(None, None, model))
    repl = NamedSharding(mesh, P())
    return type(pkv)(
        pool_k=pool, pool_v=pool, scale_k=scale, scale_v=scale,
        table=repl, meta=jax.tree.map(lambda _: repl, pkv.meta))


def serve_adapter_sharding(mesh, apool):
    """Sharding pytree for an :class:`tpudist.models.lora.AdapterPool`:
    the B factors whose OUTPUT dim aligns with a column-parallel kernel
    (``b_qkv`` with ``qkv``, ``b_wi`` with ``wi``) shard that dim over
    ``model`` where it divides — the same byte-identity-safe column
    rule as :func:`serve_param_sharding` (slices and gathers only,
    never a split contraction).  The tiny A factors (rank-r outputs)
    and ``b_wo`` (output feeds the replicated residual, like ``proj``)
    replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())

    def col(leaf):
        axis = _axis_or_none(mesh, AXIS_MODEL, leaf.shape[-1])
        if axis is None:
            return repl
        return NamedSharding(mesh, P(None, None, None, axis))

    return type(apool)(
        a_qkv=repl, b_qkv=col(apool.b_qkv),
        a_wi=repl, b_wi=col(apool.b_wi),
        a_wo=repl, b_wo=repl)


def serve_state_sharding(mesh, state):
    """SlotState replicates everywhere: it is tiny (a handful of [S]
    vectors) and the host's admission/budget logic must read it the same
    from any process — the disaggregation coordinator included."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda _: NamedSharding(mesh, P()), state)


def resolve_serve_overlap(cfg: ServeMeshConfig) -> str:
    """The TP-overlap mode for a serving mesh: the config's explicit
    ``tp_overlap`` wins; otherwise ``TPUDIST_SERVE_TP_OVERLAP`` falls
    back to the shared ``TPUDIST_OVERLAP`` knob.  Same forgiving parse
    as :func:`tpudist.parallel.overlap.overlap_mode`."""
    import os

    from tpudist.parallel.overlap import overlap_mode

    v = cfg.tp_overlap
    if v is None:
        v = os.environ.get("TPUDIST_SERVE_TP_OVERLAP", "").strip() or None
    if v is not None:
        v = v.strip().lower()
        return v if v in ("ring", "bidir") else "off"
    return overlap_mode(None)


def serve_overlap_mlp_fn(mesh, *, axis_name: str = AXIS_MODEL,
                         mode: str = "ring"):
    """The overlapped TP decode/prefill MLP for
    ``create_transformer(mlp_fn=...)`` — decode-shaped collective
    matmul.

    Both FFN matmuls run :func:`tpudist.parallel.overlap.ag_matmul`
    with ``gather="rhs"``: the kernel is stored COLUMN-sharded
    (``wi: [d, ff/n]``, ``wo: [ff, d/n]`` per device), activations are
    replicated over the model axis (a decode batch is ``num_slots``
    rows — replicating it costs nothing; sharding weights is the HBM
    win), and each ring hop moves one kernel chunk while the previous
    chunk's matmul runs.  Column gathers assemble disjoint output
    chunks, so the result is **bit-exact** vs the dense MLP — the
    serving oracle stays byte-identical with the pipeline on.  Every
    hop carries the ``tpudist_overlap`` HLO tag the comm audit keys on.

    Returns ``None`` when ``mode`` is off or the mesh has no model
    axis > 1, so call sites keep the plain Dense path by default.
    """
    from jax.sharding import PartitionSpec as P

    from tpudist.parallel.overlap import ag_matmul

    if mode not in ("ring", "bidir"):
        return None
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] <= 1:
        return None

    def body(p, x):
        b, s, d = x.shape
        t = x.reshape(b * s, d)
        h = ag_matmul(t, p["wi"], axis_name=axis_name, mode=mode,
                      gather="rhs")
        h = jax.nn.gelu(h)
        y = ag_matmul(h, p["wo"], axis_name=axis_name, mode=mode,
                      gather="rhs")
        return y.reshape(b, s, d).astype(x.dtype)

    param_specs = {"wi": P(None, axis_name), "wo": P(None, axis_name)}
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False)

    def mlp_fn(params, x):
        return sharded(params, x)

    mlp_fn.overlap = mode
    mlp_fn.axis_name = axis_name
    return mlp_fn


def sharded_param_bytes(params, shardings) -> dict:
    """Accounting for ``spmd_stats``: total param bytes, the bytes that
    actually shard, and the per-device resident estimate."""
    import numpy as np
    from jax.sharding import NamedSharding

    total = sharded = per_dev = 0
    for leaf, sh in zip(
            jax.tree.leaves(params),
            jax.tree.leaves(shardings,
                            is_leaf=lambda x: isinstance(x, NamedSharding))):
        b = int(np.prod(leaf.shape or (1,))) * leaf.dtype.itemsize
        total += b
        axes = [a for a in tuple(sh.spec) if a is not None]
        if axes:
            sharded += b
            n = 1
            for a in axes:
                n *= sh.mesh.shape[a]
            per_dev += b // n
        else:
            per_dev += b
    return {"param_bytes_total": total, "param_bytes_sharded": sharded,
            "param_bytes_per_device": per_dev}
