"""``tpudist.ops.attention`` is the one place that decides which attention
runs on a device, in which layout and with which tiles — and ``tpudist.ops``
is the lowest layer: nothing in it imports the layers above."""

import ast
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import attention
from tpudist.ops.attention import (BLOCKWISE, FLASH, REFERENCE, Route, Tiles,
                                   computed_over_live, route)
from tpudist.ops.flash_attention import _diagonal_strips, diag_sub
from tpudist.telemetry import names

PKG = Path(__file__).resolve().parent.parent / "tpudist"
V5E = "TPU v5 lite"


def test_the_table_holds_the_two_rows_the_cells_were_measured_with():
    # the v5e row's last: a window under its tiles runs tiles of the
    # window's own width, its edge tiles by squares of 256 (PR 42)
    assert attention.TILES == {
        V5E: Tiles(2048, 1024, 1024, 1024, 8192, 256, 256)}
    assert attention.DEFAULT_TILES == Tiles(1024, 512, 512, 1024, 8192, 256,
                                            0)


# id: (device kind, seq, q heads, kv heads, dh, window) ->
#     (kernel, layout, block_q, block_k, why_not[, what the event says of
#      the band-edge tiles: diag_sub, computed_over_live[, and of a window's
#      grid: grid_kv, live_steps_share]])
ROUTES = {
    # cell cgpt590m-train-1chip: 2,048 positions, 12 heads of 128
    "v5e-gpt2-cell": ((V5E, 2048, 12, 12, 128, None),
                      (FLASH, names.PACKED, 1024, 1024, None, 256, 1.1245)),
    # cell qwen3next-train-ep16share-8k: 8,192 positions, 16/2 heads of 256
    "v5e-share-cell": ((V5E, 8192, 16, 2, 256, None),
                       (FLASH, names.PACKED, 1024, 1024, None, 256, 1.0311)),
    "v5e-below-min-seq": ((V5E, 1024, 8, 8, 128, None),
                          (REFERENCE, names.HEAD_MAJOR, 1024, 1024,
                           names.WHY_SEQ)),
    "v5e-dh64": ((V5E, 2048, 8, 8, 64, None),
                 (FLASH, names.HEAD_MAJOR, 1024, 1024, names.WHY_DH, 256,
                  1.1245)),
    "v5e-tiles-do-not-divide": ((V5E, 2560, 8, 8, 128, None),
                                (REFERENCE, names.HEAD_MAJOR, 1024, 1024,
                                 names.WHY_SEQ)),
    # a window under the row's tiles: tiles of its own width, both edge
    # tiles by squares of 256, two key tiles a query tile (7 of 8 steps a
    # head live; the laguna cell's sliding layers at 8,192: 31 of 32)
    "v5e-windowed": ((V5E, 4096, 8, 2, 128, 512),
                     (FLASH, names.PACKED, 512, 512, None, 256, 1.4998, 2,
                      0.9375)),
    "v5e-laguna-cell-sliding": ((V5E, 8192, 36, 4, 128, 512),
                                (FLASH, names.PACKED, 512, 512, None, 256,
                                 1.4999, 2, 0.9688)),
    # a window of whole row tiles keeps them, its edge tiles by the row's
    # squares; one the row's tiles do not divide keeps them whole
    "v5e-window-of-two-tiles": ((V5E, 8192, 8, 2, 128, 2048),
                                (FLASH, names.PACKED, 1024, 1024, None, 256,
                                 1.1249, 3, 0.875)),
    "v5e-window-across-a-tile": ((V5E, 8192, 8, 2, 128, 1536),
                                 (FLASH, names.PACKED, 1024, 1024, None, 0,
                                  1.9309, 3, 0.875)),
    # a kind nothing was timed on: a windowed call keeps its row's tiles
    "default-row-windowed": (("TPU v6 lite", 4096, 8, 2, 128, 256),
                             (FLASH, names.PACKED, 512, 512, None, 0,
                              3.8705, 2, 0.9375)),
    # unequal tiles: whole
    "default-row-long": (("TPU v6 lite", 8192, 8, 8, 128, None),
                         (FLASH, names.PACKED, 512, 1024, None, 0, 1.1249)),
    "default-row-4096": (("TPU v6 lite", 4096, 8, 8, 128, None),
                         (FLASH, names.PACKED, 512, 512, None, 256, 1.0622)),
    # from long_seq, but the long tile does not divide: the short one
    "default-row-long-tile-does-not-divide": (
        ("TPU v6 lite", 8704, 8, 8, 128, None),
        (FLASH, names.PACKED, 512, 512, None, 256, 1.0293)),
    "unknown-kind-at-1024": (("TPU v99 imaginary", 1024, 4, 4, 128, None),
                             (FLASH, names.PACKED, 512, 512, None, 256,
                              1.2488)),
    "cpu-2048": (("cpu", 2048, 8, 8, 128, None),
                 (BLOCKWISE, names.HEAD_MAJOR, 512, 512,
                  names.WHY_PLATFORM)),
    "cpu-gqa-windowed": (("cpu", 8192, 16, 2, 256, 1024),
                         (BLOCKWISE, names.HEAD_MAJOR, 512, 1024,
                          names.WHY_PLATFORM)),
    "cpu-short": (("cpu", 512, 8, 8, 128, None),
                  (REFERENCE, names.HEAD_MAJOR, 512, 512, names.WHY_SEQ)),
}


@pytest.mark.parametrize("case", sorted(ROUTES))
def test_route_and_dispatch(case, monkeypatch, tmp_path):
    """The lookup names the kernel, the layout, the tiles and the reason;
    the dispatch built on it calls that kernel with those tiles and its
    window, hands only the flash kernels grouped K/V, and says the layout
    in one ``attn_layout`` event.  Shapes only: nothing is computed."""
    from tpudist import telemetry

    (kind, seq, h, kv, dh, window), (kernel, layout, bq, bk, why_not,
                                     *diagonal) = ROUTES[case]
    want = route(kind, seq, dh, window)
    # the row's ``sub`` (``window_sub`` with a window's own tiles) rides
    # with the flash route alone
    row = attention.TILES.get(kind, attention.DEFAULT_TILES)
    sub = row.window_sub if (bq, bk) != (
        row.block_q, route(kind, seq, dh).block_k) else row.sub
    assert want == Route(kernel, bq, bk, why_not, sub * (kernel == FLASH))
    assert want.layout == layout

    called = []   # (kernel, layout, kv heads seen, tiles it was given, window)

    def flash(q, k, v, causal, bq, bk, interpret, win, sub):
        assert causal and not interpret
        called.append((FLASH, names.HEAD_MAJOR, k.shape[1], (bq, bk, sub),
                       win))
        return q

    def flash_packed(qkv, n_heads, n_kv, causal, bq, bk, interpret, win,
                     sub):
        assert causal and not interpret
        called.append((FLASH, names.PACKED, n_kv, (bq, bk, sub), win))
        return qkv[..., : n_heads * dh]

    def reference(q, k, v, *, causal, window):
        assert causal
        called.append((REFERENCE, names.HEAD_MAJOR, k.shape[1], (), window))
        return q

    def blockwise(q, k, v, *, causal, block_k, window):
        assert causal
        called.append((BLOCKWISE, names.HEAD_MAJOR, k.shape[1], (block_k,),
                       window))
        return q

    monkeypatch.setattr(
        jax, "devices", lambda *a, **k: [SimpleNamespace(device_kind=kind)])
    monkeypatch.setattr(attention, "flash_attention", flash)
    monkeypatch.setattr(attention, "flash_attention_packed", flash_packed)
    monkeypatch.setattr(attention, "attention_reference", reference)
    monkeypatch.setattr(attention, "blockwise_attention", blockwise)

    attend = attention.make_length_aware_attention(window)
    assert attend.window == window and attend.supports_gqa
    session = telemetry.start(tmp_path / "tele", rank=0, generation=0)
    try:
        out = jax.eval_shape(
            lambda qkv: attend.packed(qkv, h, kv),
            jax.ShapeDtypeStruct((2, seq, (h + 2 * kv) * dh), jnp.bfloat16))
        events = [(r["layout"], r.get("reason"), r.get("diag_sub"),
                   r.get("computed_over_live"), r.get("grid_kv"),
                   r.get("live_steps_share"), r.get("window"),
                   r.get("tiles")) for r in session.ring
                  if r["name"] == names.ATTN_LAYOUT]
    finally:
        telemetry.finish(write_report=False)
    assert out.shape == (2, seq, h * dh)
    # only the flash kernels take K/V at its own head count, and both tiles
    given = {FLASH: (kv, (want.block_q, want.block_k, want.sub)),
             BLOCKWISE: (h, (want.block_k,)), REFERENCE: (h, ())}[want.kernel]
    assert called == [(want.kernel, want.layout, *given, window)]
    # only where the flash kernels run does the event speak of their tiles,
    # and only of a windowed call of its window, tiles and grid
    said = (list(diagonal) + [None] * 4)[:4]
    if len(diagonal) == 4:
        said += [window, [bq, bk]]
    else:
        assert window is None or kernel != FLASH
        said += [None, None]
    assert events == [(want.layout, want.why_not, *said)]


# (seq, block_q, block_k, sub) -> computed score entries over live pairs
COMPUTED = {
    "gpt2-cell-whole": ((2048, 1024, 1024, 0), 1.50),
    "gpt2-cell-by-256": ((2048, 1024, 1024, 256), 1.125),
    "share-cell-whole": ((8192, 1024, 1024, 0), 1.125),
    "share-cell-by-256": ((8192, 1024, 1024, 256), 1.031),
    "one-tile-whole": ((1024, 1024, 1024, 0), 2.0),
    "one-tile-by-128": ((1024, 1024, 1024, 128), 1.125),
    "unequal-whole": ((2048, 512, 1024, 0), 1.50),
}


@pytest.mark.parametrize("case", sorted(COMPUTED))
def test_computed_over_live(case):
    """The pure count the event quotes, at the cells' shapes, and the same
    number counted entry by entry from the strips the kernels unroll."""
    (seq, bq, bk, sub), want = COMPUTED[case]
    got = computed_over_live(seq, bq, bk, sub)
    assert got == pytest.approx(want, abs=2e-3)
    computed = np.zeros((seq, seq), bool)
    for i in range(seq // bq):
        for j in range(seq // bk):
            rows, cols = slice(i * bq, (i + 1) * bq), slice(j * bk, (j + 1) * bk)
            if rows.stop - 1 < cols.start:
                continue
            if sub and rows.start < cols.stop - 1:
                tile = np.zeros((bq, bk), bool)
                for r, c in _diagonal_strips(bq, sub):
                    assert not tile[r, c].any()
                    tile[r, c] = True
                assert np.tril(np.ones((bq, bk), bool))[~tile].sum() == 0
                computed[rows, cols] = tile
            else:
                computed[rows, cols] = True
    assert computed[np.tril_indices(seq)].all()   # nothing live is left out
    assert got == computed.sum() / (seq * (seq + 1) / 2)


# (block_q, block_k, lo, hi, sub) -> the squares a diagonal tile goes by
DIAG_SUB = {
    "plain-causal": ((1024, 1024, 0, None, 256), 256),
    "plain-causal-by-128": ((512, 512, 0, None, 128), 128),
    "window-across-a-tile": ((1024, 1024, 0, 512, 256), 0),
    "window-of-one-tile": ((512, 512, 0, 512, 128), 128),
    "window-of-whole-tiles": ((1024, 1024, 0, 4096, 256), 256),
    "window-of-one-and-a-half-tiles": ((1024, 1024, 0, 1536, 256), 0),
    "window-unequal-blocks": ((512, 1024, 0, 1024, 256), 0),
    "ring-hop-shifted-band": ((1024, 1024, None, -1024, 256), 0),
    "band-with-both-edges": ((1024, 1024, 256, 2048, 256), 0),
    "ring-hop-band-of-whole-tiles": ((1024, 1024, None, 1024, 256), 0),
    "not-causal": ((1024, 1024, None, None, 256), 0),
    "unequal-blocks": ((512, 1024, 0, None, 256), 0),
    "sub-does-not-divide": ((384, 384, 0, None, 256), 0),
    "sub-is-the-block": ((256, 256, 0, None, 256), 0),
    "no-sub": ((1024, 1024, 0, None, 0), 0),
}


@pytest.mark.parametrize("case", sorted(DIAG_SUB))
def test_only_the_plain_causal_band_over_equal_blocks_is_cut(case):
    """... and, since PR 42, a window over equal blocks whose width the
    block divides: its diagonal tile and its far edge tile are the two
    halves of one staircase."""
    args, want = DIAG_SUB[case]
    assert diag_sub(*args) == want


@pytest.mark.parametrize("row,kernel", [
    (Tiles(128, 64, 64, 64, 8192, 16), REFERENCE),   # below min_seq
    (Tiles(32, 16, 32, 64, 8192, 16), BLOCKWISE),    # long enough, tiles divide
    (Tiles(32, 16, 48, 64, 8192, 16), REFERENCE),    # block_k does not divide 64
], ids=["short", "fits", "tile-does-not-divide"])
def test_every_route_off_the_tpu_gives_the_reference_numbers(
        row, kernel, monkeypatch):
    """A row of the table steers the default attention on this CPU to the
    dense reference or to the blockwise scan; both give the same numbers,
    and a tile that does not divide the length is routed round, never
    handed to a kernel."""
    real = attention.blockwise_attention
    calls = []

    def spy(*a, **kw):
        calls.append(kw["block_k"])
        return real(*a, **kw)

    monkeypatch.setattr(attention, "blockwise_attention", spy)
    monkeypatch.setitem(attention.TILES, jax.devices()[0].device_kind, row)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 8))
    out = attention.default_attention(q, q, q)
    np.testing.assert_allclose(
        out, attention.attention_reference(q, q, q, causal=True),
        atol=2e-5, rtol=2e-5)
    assert calls == ([row.block_k] if kernel == BLOCKWISE else [])


def _imports(path: Path):
    """``(module, name)`` of every import in the file, function-local ones
    too; ``name`` is ``None`` for a plain ``import module``."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None
        elif isinstance(node, ast.ImportFrom):
            for alias in node.names:
                yield node.module or "", alias.name


ABOVE_OPS = ("tpudist.parallel", "tpudist.models", "tpudist.train",
             "tpudist.trainer", "tpudist.serve")


@pytest.mark.parametrize(
    "path", sorted((PKG / "ops").glob("*.py")), ids=lambda p: p.name)
def test_ops_imports_nothing_above_it(path):
    above = [module for module, _ in _imports(path)
             if module.startswith(ABOVE_OPS)]
    assert not above, f"{path.name} imports {above}"


@pytest.mark.parametrize("module", ["models/hybrid.py",
                                    "parallel/pipeline_lm.py"])
def test_no_private_name_is_taken_from_the_transformer_file(module):
    private = [name for source, name in _imports(PKG / module)
               if source == "tpudist.models.transformer"
               and name and name.startswith("_")]
    assert not private, f"{module} imports {private}"
