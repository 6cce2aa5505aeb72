"""Overlapped collective-matmul layer (tpudist/parallel/overlap.py).

Four layers of evidence on the 8-device virtual mesh:

- primitive numerics: every ``ag_matmul`` geometry and ``matmul_rs``,
  ring AND bidirectional, forward AND backward, against the dense
  single-device matmul.  The gather geometries (lhs/rhs) assemble
  disjoint chunks and are gated essentially bit-exact; the accumulating
  forms (contract, reduce-scatter) reassociate the n-way sum and are
  gated at the bound documented in the module (f32 rtol 1e-5 — measured
  ~1e-6 at these shapes).
- hot-path parity: the overlapped TP MLP vs the dense math, and the
  overlapped-FSDP LM train step vs the default layout-only step over
  several optimizer steps (losses and updated params within the
  documented bound).
- knob/structure: ``TPUDIST_OVERLAP`` resolution, and the lowered HLO
  of each path — the default body carries the monolithic collective,
  the overlapped body carries ONLY overlap-tagged ppermute chunks.
- compile hygiene (slow lane): the unrolled ring is ONE compiled
  program — jit cache sizes stay 1 across repeated steps and do not
  grow with ring position.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.parallel import (
    ag_matmul,
    init_mlp_params,
    make_tp_mlp,
    matmul_rs,
    mlp_param_sharding,
    overlap_fsdp_mlp,
    overlap_mode,
)
from tpudist.runtime.mesh import AXIS_DATA, AXIS_MODEL

# Documented numeric bounds (see tpudist/parallel/overlap.py):
# gather forms are chunk-exact; accumulating forms reassociate.
EXACT = dict(rtol=1e-6, atol=1e-6)
REASSOC = dict(rtol=1e-5, atol=1e-5)


@pytest.fixture()
def model_mesh(devices):
    return Mesh(np.asarray(devices), axis_names=(AXIS_MODEL,))


def _sharded(body, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False))


class TestPrimitives:
    def _xw(self, m=16, k=8, f=32, seed=0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, f)), jnp.float32)
        return x, w

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    @pytest.mark.parametrize("gather,x_spec,w_spec", [
        ("lhs", P(AXIS_MODEL, None), P(None, None)),
        ("rhs", P(None, None), P(None, AXIS_MODEL)),
        ("contract", P(None, None), P(AXIS_MODEL, None)),
    ])
    def test_ag_matmul_matches_dense(self, model_mesh, mode, gather,
                                     x_spec, w_spec):
        x, w = self._xw()
        f = _sharded(
            lambda xx, ww: ag_matmul(xx, ww, axis_name=AXIS_MODEL,
                                     mode=mode, gather=gather),
            model_mesh, (x_spec, w_spec), P(None, None))
        tol = REASSOC if gather == "contract" else EXACT
        np.testing.assert_allclose(f(x, w), x @ w, **tol)

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_matmul_rs_matches_dense(self, model_mesh, mode):
        x, w = self._xw()
        f = _sharded(
            lambda xx, ww: matmul_rs(xx, ww, axis_name=AXIS_MODEL,
                                     mode=mode),
            model_mesh, (P(None, AXIS_MODEL), P(AXIS_MODEL, None)),
            P(AXIS_MODEL, None))
        np.testing.assert_allclose(f(x, w), x @ w, **REASSOC)

    # -- decode-shaped variants (the serving TP path's input shapes) --------

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    @pytest.mark.parametrize("gather", ["rhs", "contract"])
    def test_ag_matmul_leading_batch_dims(self, model_mesh, mode, gather):
        """rhs/contract accept ``[..., m, k]`` inputs (the decode step's
        ``[slots, 1, d]`` activations): flattened into the ring, leading
        dims restored — values match the batched dense matmul."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((4, 1, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        w_spec = P(None, AXIS_MODEL) if gather == "rhs" \
            else P(AXIS_MODEL, None)
        f = _sharded(
            lambda xx, ww: ag_matmul(xx, ww, axis_name=AXIS_MODEL,
                                     mode=mode, gather=gather),
            model_mesh, (P(None, None, None), w_spec), P(None, None, None))
        out = f(x, w)
        assert out.shape == (4, 1, 32)
        tol = REASSOC if gather == "contract" else EXACT
        np.testing.assert_allclose(
            out, jnp.einsum("bsk,kf->bsf", x, w), **tol)

    def test_ag_matmul_lhs_rejects_leading_dims(self, model_mesh):
        x = jnp.zeros((2, 4, 8), jnp.float32)
        w = jnp.zeros((8, 16), jnp.float32)
        f = _sharded(
            lambda xx, ww: ag_matmul(xx, ww, axis_name=AXIS_MODEL,
                                     gather="lhs"),
            model_mesh, (P(None, None, None), P(None, None)),
            P(None, None, None))
        with pytest.raises(ValueError, match="2-D"):
            f(x, w)

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_matmul_rs_pad_rows(self, model_mesh, mode):
        """pad_rows: a row count that does not divide the ring (decode
        batches rarely do) zero-pads up, every device returns its chunk
        of the padded rows, and the assembled result sliced back to m
        matches the dense matmul.  Without the flag the same shape
        raises."""
        rng = np.random.default_rng(5)
        m = 12  # 8-ring: pads to 16
        x = jnp.asarray(rng.standard_normal((m, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        f = _sharded(
            lambda xx, ww: matmul_rs(xx, ww, axis_name=AXIS_MODEL,
                                     mode=mode, pad_rows=True),
            model_mesh, (P(None, AXIS_MODEL), P(AXIS_MODEL, None)),
            P(AXIS_MODEL, None))
        out = f(x, w)
        assert out.shape[0] == 16  # the padded row count, chunk-assembled
        np.testing.assert_allclose(out[:m], x @ w, **REASSOC)
        np.testing.assert_allclose(out[m:], 0.0, atol=1e-6)
        g = _sharded(
            lambda xx, ww: matmul_rs(xx, ww, axis_name=AXIS_MODEL,
                                     mode=mode),
            model_mesh, (P(None, AXIS_MODEL), P(AXIS_MODEL, None)),
            P(AXIS_MODEL, None))
        with pytest.raises(ValueError, match="pad_rows"):
            g(x, w)

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_gradients_match_dense(self, model_mesh, mode):
        """Backward through the full gather→matmul→reduce-scatter chain:
        the ppermute transposes must reproduce the dense cotangents."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        w1 = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)

        # the real TP chain: lhs-gather ring into the first matmul,
        # reduce-scatter ring out of the second — grads retrace both
        # ppermute pipelines via their transposes
        def overlap_loss(xx, w1_, w2_):
            def body(xl, w1l, w2l):
                h = ag_matmul(xl, w1l, axis_name=AXIS_MODEL, mode=mode,
                              gather="lhs")
                out = matmul_rs(h, w2l, axis_name=AXIS_MODEL, mode=mode)
                return jax.lax.psum(jnp.sum(out * out), AXIS_MODEL)

            inner = jax.shard_map(
                body, mesh=model_mesh,
                in_specs=(P(AXIS_MODEL, None), P(None, AXIS_MODEL),
                          P(AXIS_MODEL, None)),
                out_specs=P(), check_vma=False)
            return inner(xx, w1_, w2_)

        def dense_loss(xx, w1_, w2_):
            return jnp.sum(((xx @ w1_) @ w2_) ** 2)

        got = jax.jit(jax.value_and_grad(overlap_loss,
                                         argnums=(0, 1, 2)))(x, w1, w2)
        want = jax.jit(jax.value_and_grad(dense_loss,
                                          argnums=(0, 1, 2)))(x, w1, w2)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for g, r in zip(got[1], want[1]):
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)

    def test_rejects_bad_args(self, model_mesh):
        x, w = self._xw()
        with pytest.raises(ValueError, match="mode"):
            _sharded(lambda a, b: ag_matmul(a, b, axis_name=AXIS_MODEL,
                                            mode="spiral"),
                     model_mesh, (P(AXIS_MODEL, None), P(None, None)),
                     P(None, None))(x, w)
        with pytest.raises(ValueError, match="gather"):
            _sharded(lambda a, b: ag_matmul(a, b, axis_name=AXIS_MODEL,
                                            gather="diag"),
                     model_mesh, (P(AXIS_MODEL, None), P(None, None)),
                     P(None, None))(x, w)
        with pytest.raises(ValueError, match="divisible"):
            # 12 rows over an 8-ring
            xx = jnp.zeros((12, 16), jnp.float32)
            ww = jnp.zeros((2, 4), jnp.float32)
            _sharded(lambda a, b: matmul_rs(a, b, axis_name=AXIS_MODEL),
                     model_mesh, (P(None, AXIS_MODEL), P(AXIS_MODEL, None)),
                     P(AXIS_MODEL, None))(xx, ww)


def _dense_mlp(params, x):
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


class TestTPMLPOverlap:
    def _setup(self, mesh, d=32, f=128, batch=64):
        params = init_mlp_params(jax.random.PRNGKey(0), d, f)
        x = jax.random.normal(jax.random.PRNGKey(1), (batch, d), jnp.float32)
        sharded = jax.device_put(params, mlp_param_sharding(mesh, params))
        return params, sharded, x

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_matches_dense(self, model_mesh, mode):
        params, sharded, x = self._setup(model_mesh)
        out = make_tp_mlp(model_mesh, overlap=mode)(sharded, x)
        np.testing.assert_allclose(out, _dense_mlp(params, x), **REASSOC)

    @pytest.mark.skipif(not hasattr(jax, "shard_map"),
                        reason="default TP body needs jax>=0.9 shard_map")
    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_matches_default_path(self, model_mesh, mode):
        """The acceptance gate: overlapped vs default TP MLP on the
        8-way mesh, within the documented reassociation bound."""
        _, sharded, x = self._setup(model_mesh)
        default = make_tp_mlp(model_mesh, overlap="off")(sharded, x)
        out = make_tp_mlp(model_mesh, overlap=mode)(sharded, x)
        np.testing.assert_allclose(out, default, **REASSOC)

    def test_batch_axis_rejected(self, model_mesh):
        with pytest.raises(ValueError, match="batch_axis"):
            make_tp_mlp(model_mesh, batch_axis=AXIS_MODEL, overlap="ring")

    def test_knob_selects_structure(self, model_mesh, monkeypatch):
        """TPUDIST_OVERLAP drives make_tp_mlp: the lowered HLO of the
        knob-on path carries overlap-tagged ppermutes and NO monolithic
        collective; knob-off (or a typo) keeps the psum body."""
        from tpudist.utils.hlo_audit import overlap_split, parse_collectives

        _, sharded, x = self._setup(model_mesh)
        monkeypatch.setenv("TPUDIST_OVERLAP", "ring")
        assert overlap_mode() == "ring"
        f = make_tp_mlp(model_mesh)
        ops = parse_collectives(f.lower(sharded, x).compile().as_text())
        kinds = {o.kind for o in ops}
        assert "collective-permute" in kinds and "all-reduce" not in kinds
        split = overlap_split(ops)
        assert split["overlapped_bytes"] > 0 and split["exposed_bytes"] == 0
        monkeypatch.setenv("TPUDIST_OVERLAP", "sideways")  # typo -> off
        assert overlap_mode() == "off"
        if hasattr(jax, "shard_map"):
            f0 = make_tp_mlp(model_mesh)
            ops0 = parse_collectives(
                f0.lower(sharded, x).compile().as_text())
            assert {o.kind for o in ops0} == {"all-reduce"}
            assert overlap_split(ops0)["overlapped_bytes"] == 0
        with pytest.raises(ValueError, match="overlap"):
            overlap_mode("spiral")  # explicit arg: loud, not silent


class TestFSDPOverlapLM:
    """Overlapped FSDP layer compute vs the layout-only LM train step —
    same params, same tokens, K optimizer steps; the acceptance bound."""

    def _run(self, mesh, mlp_fn, steps=3):
        import optax

        from tpudist.models import create_transformer
        from tpudist.parallel import fsdp_sharding
        from tpudist.train import (init_lm_state, make_lm_train_step,
                                   token_sharding)

        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=2, n_heads=2, d_ff=64, max_len=16, mlp_fn=mlp_fn)
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        sh = fsdp_sharding(mesh, state, min_size=64)
        state = jax.device_put(state, sh)
        step = make_lm_train_step(module.apply, tx, mesh, state_sharding=sh)
        toks = jax.device_put(
            np.random.default_rng(0).integers(0, 32, size=(8, 16))
            .astype(np.int32), token_sharding(mesh))
        losses = []
        for _ in range(steps):
            state, loss = step(state, toks)
            losses.append(float(loss))
        return losses, state, step

    # reference run shared across the parametrized modes (one compile)
    _REF: dict = {}

    @pytest.mark.parametrize("mode", ["ring", "bidir"])
    def test_step_matches_default_path(self, dp_mesh, mode):
        if "ref" not in self._REF:
            self._REF["ref"] = self._run(dp_mesh, None)
        l_ref, s_ref, _ = self._REF["ref"]
        mlp_fn = overlap_fsdp_mlp(dp_mesh, overlap=mode)
        assert mlp_fn is not None and mlp_fn.overlap == mode
        l_ov, s_ov, _ = self._run(dp_mesh, mlp_fn)
        # documented bound: contraction-gather reassociation, amplified
        # by K Adam steps — measured ~6e-6 max param drift at K=3
        np.testing.assert_allclose(l_ov, l_ref, rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(s_ov.params),
                        jax.tree.leaves(s_ref.params)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        assert l_ov[-1] < l_ov[0]  # it trains

    def test_knob_off_returns_none(self, dp_mesh, monkeypatch):
        monkeypatch.delenv("TPUDIST_OVERLAP", raising=False)
        assert overlap_fsdp_mlp(dp_mesh) is None
        monkeypatch.setenv("TPUDIST_OVERLAP", "off")
        assert overlap_fsdp_mlp(dp_mesh) is None
        monkeypatch.setenv("TPUDIST_OVERLAP", "bidir")
        fn = overlap_fsdp_mlp(dp_mesh)
        assert fn is not None and fn.overlap == "bidir"

    def test_ffn_gathers_gone_from_hlo(self, dp_mesh):
        """Structural acceptance on the LM step: with the overlapped
        MLP, no all-gather in the optimized HLO is attributable to the
        FFN kernels, and overlap-tagged ppermute bytes appear."""
        from tpudist.utils.hlo_audit import overlap_split, parse_collectives

        mlp_fn = overlap_fsdp_mlp(dp_mesh, overlap="ring")
        import optax

        from tpudist.models import create_transformer
        from tpudist.parallel import fsdp_sharding
        from tpudist.train import (init_lm_state, make_lm_train_step,
                                   token_sharding)

        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=2, n_heads=2, d_ff=64, max_len=16, mlp_fn=mlp_fn)
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        sh = fsdp_sharding(dp_mesh, state, min_size=64)
        state = jax.device_put(state, sh)
        step = make_lm_train_step(module.apply, tx, dp_mesh,
                                  state_sharding=sh)
        toks = jax.device_put(
            np.random.default_rng(0).integers(0, 32, size=(8, 16))
            .astype(np.int32), token_sharding(dp_mesh))
        ops = parse_collectives(
            step.lower(state, toks).compile().as_text())
        ffn_gathers = [o for o in ops if o.kind == "all-gather"
                       and ("/wi/" in o.op_name or "/wo/" in o.op_name)]
        assert not ffn_gathers
        permutes = [o for o in ops if o.kind == "collective-permute"]
        assert permutes and all(o.overlapped for o in permutes)
        assert overlap_split(ops)["overlapped_bytes"] >= \
            2 * 2 * 7 * (32 * 64 * 4 // 8)  # layers x rings x hops x shard

    def test_mlp_fn_moe_composition_rejected(self):
        from tpudist.models.transformer import Block

        blk = Block(32, 2, 64, lambda q, k, v: q, n_experts=2,
                    mlp_fn=lambda p, x: x)
        with pytest.raises(ValueError, match="MoE"):
            blk.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 8, 32), jnp.float32))


class TestOverlapCompilePinning:
    """Slow lane: the unrolled ppermute chain is ONE compiled program —
    cache sizes stay flat across repeated steps (nothing recompiles per
    ring step), for both hot paths and both modes."""

    def test_tp_mlp_compile_counts_flat(self, devices):
        mesh = Mesh(np.asarray(devices), axis_names=(AXIS_MODEL,))
        params = init_mlp_params(jax.random.PRNGKey(0), 32, 128)
        sharded = jax.device_put(params, mlp_param_sharding(mesh, params))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
        for mode in ("ring", "bidir"):
            f = make_tp_mlp(mesh, overlap=mode)
            for _ in range(4):
                out = f(sharded, x)
            jax.block_until_ready(out)
            assert f._cache_size() == 1, mode

    def test_fsdp_lm_step_compile_counts_flat(self, dp_mesh):
        import optax

        from tpudist.models import create_transformer
        from tpudist.parallel import fsdp_sharding
        from tpudist.train import (init_lm_state, make_lm_train_step,
                                   token_sharding)

        mlp_fn = overlap_fsdp_mlp(dp_mesh, overlap="ring")
        module, params = create_transformer(
            jax.random.PRNGKey(0), seq_len=16, vocab=32, d_model=32,
            n_layers=2, n_heads=2, d_ff=64, max_len=16, mlp_fn=mlp_fn)
        tx = optax.adam(1e-3)
        state = init_lm_state(params, tx)
        sh = fsdp_sharding(dp_mesh, state, min_size=64)
        state = jax.device_put(state, sh)
        step = make_lm_train_step(module.apply, tx, dp_mesh,
                                  state_sharding=sh)
        toks = jax.device_put(
            np.random.default_rng(0).integers(0, 32, size=(8, 16))
            .astype(np.int32), token_sharding(dp_mesh))
        for _ in range(4):
            state, loss = step(state, toks)
        jax.block_until_ready(loss)
        assert step._cache_size() == 1
