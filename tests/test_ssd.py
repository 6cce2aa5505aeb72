"""The chunked state-space scan (``tpudist/ops/ssd.py``) against the
recurrence a position at a time, on the CPU at small sizes: heads of 8 with
a state of 16, chunks of 32.

Tolerances, and why.  Float32 against float32 differs only by the order of
sums (products over a chunk against a running state): 1e-5 of the output's
largest entry (1.8e-6 read), 5e-5 of a gradient's (1.8e-5 read: the
gradients pass through ``exp`` of the decay sums twice).  In bf16 the
operands of the four products are rounded to 8 bits: 2e-2 (6e-3 read) and,
for a gradient, 4e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.ops import ssd
from tpudist.ops.ssd import ssd_recurrence, ssd_scan
from tpudist.telemetry import names

CHUNK = 32
#: dtype -> (the output's bound, a gradient's), of the largest entry
BOUNDS = {jnp.float32: (1e-5, 5e-5), jnp.bfloat16: (2e-2, 4e-2)}
OPERANDS = "x dt a_log b c d".split()


@pytest.fixture(autouse=True)
def highest_precision():
    # the CPU multiplies float32 exactly; stated for the reader
    with jax.default_matmul_precision("highest"):
        yield


def worst(got, want) -> float:
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def inputs(dtype=jnp.float32, *, chunks=3, heads=4, groups=2, seed=0):
    """Steps from 1e-3 to 1 a position against ``A`` from 1 to 16: heads
    that forget within a position beside heads that carry their state over
    every chunk."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    b, s, p, n = 2, chunks * CHUNK, 8, 16
    x = jax.random.normal(ks[0], (b, s, heads, p))
    dt = jnp.exp(jax.random.uniform(ks[1], (b, s, heads), minval=-7.0,
                                    maxval=0.0))
    a_log = jnp.log(jnp.linspace(1.0, 16.0, heads))
    bb = jax.random.normal(ks[2], (b, s, groups, n))
    cc = jax.random.normal(ks[3], (b, s, groups, n))
    d = 1.0 + 0.1 * jax.random.normal(ks[4], (heads,))
    return [x.astype(dtype), dt, a_log, bb.astype(dtype), cc.astype(dtype), d]


def scan(*args):
    return ssd_scan(*args, chunk=CHUNK)


@pytest.mark.parametrize("dtype", list(BOUNDS), ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 2, 3, 4])
def test_chunked_scan_gives_the_recurrences_values(dtype, chunks):
    args = inputs(dtype, chunks=chunks)
    got = scan(*args)
    assert got.dtype == dtype and got.shape == args[0].shape
    assert worst(got.astype(jnp.float32),
                 ssd_recurrence(*args)) < BOUNDS[dtype][0]


@pytest.mark.parametrize("dtype", list(BOUNDS), ids=["float32", "bfloat16"])
@pytest.mark.parametrize("operand", range(6), ids=OPERANDS)
def test_chunked_scan_gives_the_recurrences_gradients(dtype, operand):
    args = inputs(dtype, seed=1)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), argnums=operand)(*args)

    assert worst(through(scan).astype(jnp.float32),
                 through(ssd_recurrence)) < BOUNDS[dtype][1]


def test_one_group_serves_all_heads_and_a_head_reads_its_own_group():
    """Two groups of two heads: head ``j`` reads group ``j // 2``.  With the
    groups' ``B`` and ``C`` swapped the result moves; with both groups made
    alike it is what one group gives all four heads."""
    x, dt, a_log, b, c, d = inputs()
    want = scan(x, dt, a_log, b, c, d)
    assert worst(scan(x, dt, a_log, b[:, :, ::-1], c[:, :, ::-1], d),
                 want) > 1e-2
    alike = lambda t: jnp.repeat(t[:, :, :1], 2, axis=2)
    assert worst(scan(x, dt, a_log, alike(b), alike(c), d),
                 scan(x, dt, a_log, b[:, :, :1], c[:, :, :1], d)) < 1e-6


@pytest.mark.parametrize("group", [0, 1])
def test_a_held_groups_heads_give_what_they_give_among_all(group):
    """The share: a caller that holds one group passes its heads, its ``B``
    and ``C`` alone, and gets those heads' part of the whole, to the bit of
    float32's sums (nothing crosses heads or groups)."""
    x, dt, a_log, b, c, d = inputs()
    whole = scan(x, dt, a_log, b, c, d)
    heads = slice(2 * group, 2 * group + 2)
    part = scan(x[:, :, heads], dt[:, :, heads], a_log[heads],
                b[:, :, group:group + 1], c[:, :, group:group + 1], d[heads])
    assert worst(part, whole[:, :, heads]) < 1e-6


def test_a_scan_whose_carried_state_is_zeroed_fails():
    """The planted fault of the cell tests: each chunk scanned alone.  The
    heads that forget slowly show it by far more than the tolerance; one
    chunk alone has nothing carried to lose."""
    args = inputs()
    x, dt, a_log, b, c, d = args
    cut = lambda t: t.reshape(-1, CHUNK, *t.shape[2:])
    forgets = scan(cut(x), cut(dt), a_log, cut(b), cut(c), d).reshape(x.shape)
    want = ssd_recurrence(*args)
    assert worst(forgets, want) > 1e-2
    assert worst(forgets[:, :CHUNK], want[:, :CHUNK]) < BOUNDS[jnp.float32][0]


def test_a_head_that_forgets_at_once_overflows_nothing():
    """``A dt`` of 50 a position: every decay sum is negative before its
    ``exp``, so values and gradients stay finite."""
    x, dt, a_log, b, c, d = inputs()
    dt = jnp.full_like(dt, 50.0)
    got, grads = jax.value_and_grad(
        lambda *a: jnp.sum(scan(*a)), argnums=(0, 1, 2, 3, 4, 5))(
            x, dt, a_log, b, c, d)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


@pytest.mark.parametrize("why, kw", [
    ("groups", dict(heads=3, groups=2)), ("chunks", dict(chunks=1))])
def test_scan_refuses_what_it_does_not_hold_for(why, kw):
    args = inputs(**kw)
    with pytest.raises(ValueError, match=why):
        ssd_scan(*args, chunk=CHUNK if why == "groups" else CHUNK - 1)


def test_the_scan_runs_under_its_scope():
    import re

    text = jax.jit(scan).lower(*inputs()).as_text(debug_info=True)
    found = set(re.findall(r'loc\("([^"]+)"', text))
    assert any(f"/{names.SSD_SCAN}/" in f for f in found)
    assert ssd.ssd_scan.__doc__ and "h % g == 0" in ssd.ssd_scan.__doc__
