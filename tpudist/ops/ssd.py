"""The state-space scan of a Mamba-2 mixer (Dao & Gu 2024, "Transformers
are SSMs": the state-space dual form), computed in chunks.

The recurrence, per head, with a state ``h [p, n]`` (``p`` the head's
width, ``n`` the state size) that starts at zero::

    a_t = exp(-exp(A_log) * dt_t)                 one number a head
    h_t = a_t * h_{t-1} + (dt_t * x_t) (x) B_t
    y_t = h_t C_t + D * x_t

``B_t`` and ``C_t [n]`` belong to a GROUP of heads: head ``j`` of ``h`` reads
group ``j // (h / g)``.  :func:`ssd_recurrence` is that loop as written, a
position at a time.  :func:`ssd_scan` gives the same result from matrix
products over chunks of ``chunk`` positions, in four steps (``G`` the
cumulative sum of ``log a`` inside a chunk):

1. *inside a chunk*: ``Y_diag = ((C B^T) . exp(G_l - G_s) [s <= l]) (dt x)``;
2. *a chunk's own state*: ``sum_s exp(G_last - G_s) (dt x)_s (x) B_s``;
3. *between chunks* a ``lax.scan`` carries the state in float32:
   ``h <- exp(G_last) h + the chunk's own state``;
4. *state to output*: ``Y_off = exp(G_l) (C_l . h)`` with the state the
   chunk started from.

Every decay is ``exp`` of a difference of cumulative sums that is taken
before the ``exp`` and is never positive, so nothing overflows however
strongly a head forgets.  The decay sums and the carried state are
float32; the products take their operands in ``x``'s dtype and accumulate
in float32 (float32 operands at the highest matmul precision).
Differentiable by JAX's own rules; plain XLA, no Pallas kernel; the whole
of it runs under the scope ``names.SSD_SCAN``.

**A share of the heads.**  Nothing crosses heads or groups, so a caller
that holds some heads of some groups passes those alone: the result is
what the same heads give among all of them (``tests/test_ssd.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.telemetry import names


def _check(x, b, chunk: int):
    h, g = x.shape[2], b.shape[2]
    if h % g:
        raise ValueError(f"{h} heads do not divide over {g} groups")
    if chunk and x.shape[1] % chunk:
        raise ValueError(f"{x.shape[1]} positions are not a whole number of "
                         f"chunks of {chunk}")


def ssd_scan(x, dt, a_log, b, c, d, *, chunk: int = 128):
    """``x [b, s, h, p]``, ``dt [b, s, h]`` (positive: after its
    softplus), ``a_log [h]``, ``b, c [b, s, g, n]``, ``d [h]`` ->
    ``y [b, s, h, p]`` in ``x``'s dtype.  Holds for ``h % g == 0`` (each
    group serves ``h / g`` consecutive heads) and ``s % chunk == 0``."""
    _check(x, b, chunk)
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r, nc = h // g, s // chunk
    dtype = x.dtype
    precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                 else lax.Precision.DEFAULT)

    def mm(spec, *operands):
        return jnp.einsum(spec, *(o.astype(dtype) for o in operands),
                          precision=precision,
                          preferred_element_type=jnp.float32)

    def chunks(t):     # [b, s, ...] -> [b, nc, chunk, ...]
        return t.reshape(bsz, nc, chunk, *t.shape[2:])

    with jax.named_scope(names.SSD_SCAN):
        dt = dt.astype(jnp.float32)
        # log a, [b, nc, chunk, g, r]: heads by group from here on
        log_a = chunks(-jnp.exp(a_log.astype(jnp.float32)) * dt).reshape(
            bsz, nc, chunk, g, r)
        cum = jnp.cumsum(log_a, axis=2)                          # G
        last = cum[:, :, -1:]
        xdt = chunks(x.astype(jnp.float32) * dt[..., None]).reshape(
            bsz, nc, chunk, g, r, p)
        b, c = chunks(b), chunks(c)                   # [b, nc, chunk, g, n]
        # 1. inside a chunk.  exp(G_l - G_s) for s <= l and 0 above the
        # diagonal: the difference is masked before the exp.  Heads lead
        # and the chunk's two position axes are minor, as the products
        # take them
        by_head = jnp.moveaxis(cum, 2, -1)            # [b, nc, g, r, chunk]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(
            lower, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        scores = mm("bclgn,bcsgn->bcgls", c, b)
        y = mm("bcgrls,bcsgrp->bclgrp", scores[:, :, :, None] * decay, xdt)
        # 2. each chunk's own state, [b, nc, g, r, p, n]
        own = mm("bcsgrp,bcsgn->bcgrpn",
                 xdt * jnp.exp(last - cum)[..., None], b)
        # 3. between chunks: the state each chunk starts from
        keep = jnp.exp(last[:, :, 0])[..., None, None]    # [b, nc, g, r, 1, 1]

        def carry(state, xs):
            keep_i, own_i = xs
            return state * keep_i + own_i, state

        _, before = lax.scan(
            carry, jnp.zeros((bsz, g, r, p, n), jnp.float32),
            (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(own, 1, 0)))
        # 4. state to output
        y = y + mm("bclgn,bcgrpn->bclgrp", c,
                   jnp.moveaxis(before, 0, 1)) * jnp.exp(cum)[..., None]
        y = y.reshape(bsz, s, h, p) + (
            d.astype(jnp.float32)[:, None] * x.astype(jnp.float32))
        return y.astype(dtype)


def ssd_recurrence(x, dt, a_log, b, c, d):
    """The recurrence as written, one position at a time, in float32: what
    the chunked form is tested against.  Same operands as
    :func:`ssd_scan`."""
    _check(x, b, 0)
    f32 = lambda t: t.astype(jnp.float32)
    x, dt, a_log, b, c, d = map(f32, (x, dt, a_log, b, c, d))
    bsz, s, h, p = x.shape
    r = h // b.shape[2]
    b, c = (jnp.repeat(t, r, axis=2) for t in (b, c))        # [b, s, h, n]

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs                             # [b, h, ...]
        a_t = jnp.exp(-jnp.exp(a_log) * dt_t)
        state = (state * a_t[..., None, None]
                 + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1) + (
            d[:, None] * x_t)

    by_position = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = lax.scan(step, jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32),
                    tuple(map(by_position, (x, dt, b, c))))
    return jnp.moveaxis(y, 0, 1)
