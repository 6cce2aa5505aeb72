"""Gated delta-rule linear attention, computed in chunks.

The recurrence (Yang et al. 2024, "Gated Delta Networks"), per head, with a
state ``S [dk, dv]`` that starts at zero::

    S <- exp(g_t) * S
    S <- S + k_t (x) (beta_t * (v_t - S^T k_t))
    o_t = S^T q_t

:func:`gated_delta_rule_reference` is that loop as written, a position at a
time.  :func:`chunked_gated_delta_rule` gives the same result from matrix
products over chunks of ``chunk`` positions:

- *inside a chunk* the ``chunk`` rank-one corrections are solved together
  (the WY form): with ``A[i, j] = beta_i <k_i, k_j> exp(G_i - G_j)`` for
  ``j < i`` (``G`` the cumulative sum of ``g`` inside the chunk),
  ``T = (I + A)^-1`` turns ``beta * v`` and ``beta * k * exp(G)`` into the
  chunk's pseudo-values ``u`` and the keys ``w`` that read the carried
  state.  ``A`` is strictly lower triangular, so ``(-A)^chunk = 0`` and the
  inverse is a finite product ``(I - A)(I + A^2)(I + A^4)...`` on small
  diagonal blocks, put together by halves: matrix products only, always
  in float32.  How small the blocks are goes by the write strength (see
  **The range of beta** below);
- *between chunks* a ``lax.scan`` carries ``S`` in float32:
  ``v' = u - w S``, ``o = (q exp(G)) S + (q k^T . decay) v'``,
  ``S <- exp(G_last) S + (k exp(G_last - G))^T v'``.

Every decay is ``exp`` of a difference of cumulative sums that is taken
before the ``exp`` and is never positive, so nothing overflows however
strongly a head forgets.

Differentiable by JAX's own rules: the backward pass is autodiff through
the chunked form, with the scan's body under ``jax.checkpoint`` so that
what is kept per chunk is the carried state alone (``[b, h, dk, dv]``
float32 a chunk) and the body's products are recomputed.  Plain XLA, no
Pallas kernel; the whole of it runs under the scope
``names.DELTA_RULE``.

Operands in a low-precision compute dtype (bf16) are multiplied as they
are with float32 accumulation; float32 operands at the highest matmul
precision.  ``g`` and ``beta`` are float32 throughout.

**The range of beta.**  The recurrence holds for any ``beta``; the
transition along ``k_t`` is ``1 - beta_t`` (unit keys), so the state stays
bounded for ``beta`` in ``[0, 2]``: a contraction's in ``[0, 1]``, with a
negative eigenvalue in ``(1, 2]``.  The caller states the largest ``beta``
it will pass (``beta_max``), because the chunk's inverse depends on it.
``T = (I + A)^-1`` itself is bounded by ``beta_max`` in both cases, but the
POWERS of ``A`` in the finite product are not: where a chunk's keys are
alike, ``A^n`` has entries of ``beta^n * C(rows - 1, n - 1)``, which cancel
down to ``T``.  Over 16 rows that is 6,435 at ``beta = 1``, which float32
holds (1.2e-4 of the output's largest entry on a chunk of identical keys,
4e-7 on independent ones), and 1.1e6 at ``beta = 1.9``, which it does not:
5.7e-2 in float32 at ``Precision.HIGHEST``, whatever the precision of the
products (``tests/test_olmo_hybrid.py``).  So for ``beta_max > 1`` the
finite product is taken over 4 rows only (``A^2`` is the last power, its
entries under 8) and the halves do the rest, whose products are of bounded
blocks: 1.5e-6 on the same chunk (9.6e-6 with a decay of 0.999 a position),
in float32.  With bf16 operands the same chunk reads 1.7e-2 (7.6e-2 at that
slow decay, where 64 writes of alternating sign cancel) against 7.5e-3 on
independent keys: that is ``T``, ``u`` and ``w`` rounded to bf16 before the
scan multiplies them, the same for every way of taking the inverse, and
``Precision.HIGH`` (three bf16 passes, 2^-16) for the inverse's own products
is still enough.

Tested at equal key and value widths (16 / 16 here, 128 / 128 compiled for
the chip) and at unequal ones (12 / 24 here, 96 / 192 compiled), ``beta``
drawn from ``(0, 1)`` and from ``(0, 2)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpudist.telemetry import names


def _precision(dtype):
    return (lax.Precision.HIGHEST if dtype == jnp.float32
            else lax.Precision.DEFAULT)


def _inverse_base(beta_max: float) -> int:
    """Rows up to which the inverse is the finite product (above them, by
    halves): 16 for a write strength in ``[0, 1]``, 4 beyond it, where the
    powers of a 16-row block of alike keys outgrow float32 (the module's
    docstring has the readings)."""
    return 16 if beta_max <= 1.0 else 4


def _inverse_by_halves(a: jax.Array, precision, base: int) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a [..., c, c]``.

    A block of ``base`` rows is the finite product
    ``(I + x)(I + x^2)(I + x^4)...`` with ``x = -a`` (``x^c = 0``).  A larger
    one is solved by halves: with ``a = [[a11, 0], [a21, a22]]`` the inverse
    is ``[[t11, 0], [-t22 a21 t11, t22]]``; the two diagonal blocks are one
    batched call.  Matrix products only, an eighth of the multiply-adds the
    product over the whole block takes at 64 rows."""
    c = a.shape[-1]
    if c <= base or c % 2:
        power = -a
        inv = jnp.eye(c, dtype=a.dtype) + power
        span = 2          # ``inv`` holds the powers of x below ``span``
        while span < c:
            power = jnp.matmul(power, power, precision=precision)
            inv = inv + jnp.matmul(inv, power, precision=precision)
            span *= 2
        return inv
    h = c // 2
    diagonal = _inverse_by_halves(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]), precision, base)
    t11, t22 = diagonal[0], diagonal[1]
    t21 = -jnp.matmul(jnp.matmul(t22, a[..., h:, :h], precision=precision),
                      t11, precision=precision)
    return jnp.concatenate(
        [jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
         jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _unit_lower_inverse(a: jax.Array, precision, base: int) -> jax.Array:
    """``(I + a)^-1`` for strictly lower triangular ``a [..., c, c]``
    (float32), :func:`_inverse_by_halves`.  Its backward pass is the
    inverse's own, ``da = -T^T dT T^T``, so that only ``T`` is kept."""
    return _inverse_by_halves(a, precision, base)


def _unit_lower_inverse_fwd(a, precision, base):
    inv = _unit_lower_inverse(a, precision, base)
    return inv, inv


def _unit_lower_inverse_bwd(precision, base, inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, d_inv, precision=precision), t,
                        precision=precision),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunked_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                             beta_max: float = 1.0):
    """``q, k [b, s, h, dk]``, ``v [b, s, h, dv]`` (``dk`` and ``dv`` need
    not be equal), ``g, beta [b, s, h]`` -> ``o [b, s, h, dv]`` in ``v``'s
    dtype.  ``g <= 0`` is the log of the per-position decay, ``beta`` in
    ``[0, beta_max]`` the write strength, ``beta_max`` at most 2 (the
    module's docstring says what it decides); ``q`` and ``k`` come
    normalised and scaled as the caller wants them.  ``s`` must be a whole
    number of chunks."""
    if not 0.0 < beta_max <= 2.0:
        raise ValueError(f"beta_max is {beta_max}; the state is bounded for "
                         f"a write strength in [0, 2]")
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if s % chunk:
        raise ValueError(f"{s} positions are not a whole number of chunks "
                         f"of {chunk}")
    n = s // chunk
    dtype = v.dtype
    precision = _precision(dtype)
    # the inverse is float32 whatever the operands; three bf16 passes hold
    # its products to 2^-16, far under the bf16 it is rounded to before use
    inverse_precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                         else lax.Precision.HIGH)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          precision=precision,
                          preferred_element_type=jnp.float32)

    def chunks(x):     # [b, s, h, ...] -> [n, b, h, chunk, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    with jax.named_scope(names.DELTA_RULE):
        # chunks lead from here on: the scan below slices its operands
        # where they lie, and nothing is re-laid out between the two parts
        q, k, v = chunks(q), chunks(k), chunks(v)
        g = chunks(g.astype(jnp.float32))
        beta = chunks(beta.astype(jnp.float32))
        cum = jnp.cumsum(g, axis=-1)                     # G, [n, b, h, c]
        last = cum[..., -1:]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # exp(G_i - G_j) for j <= i and 0 above the diagonal: the difference
        # is masked before the exp, so no positive number is exponentiated
        decay = jnp.exp(jnp.where(
            lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        grow = jnp.exp(cum)[..., None]
        k_beta = (k.astype(jnp.float32) * beta[..., None]).astype(dtype)
        a = mm("nbhid,nbhjd->nbhij", k_beta, k) * decay
        a = jnp.where(jnp.tril(lower, -1), a, 0.0)
        t = _unit_lower_inverse(a, inverse_precision,
                                _inverse_base(beta_max)).astype(dtype)
        # what the scan multiplies it multiplies in ``dtype``
        u = mm("nbhij,nbhjd->nbhid", t, v.astype(jnp.float32)
               * beta[..., None]).astype(dtype)
        w = mm("nbhij,nbhjd->nbhid", t,
               k_beta.astype(jnp.float32) * grow).astype(dtype)
        intra = (mm("nbhid,nbhjd->nbhij", q, k) * decay).astype(dtype)
        q_in = (q.astype(jnp.float32) * grow).astype(dtype)
        k_out = (k.astype(jnp.float32)
                 * jnp.exp(last - cum)[..., None]).astype(dtype)
        keep = jnp.exp(last)[..., None]                  # [n, b, h, 1, 1]

        @jax.checkpoint
        def body(state, xs):
            u_i, w_i, intra_i, q_i, k_i, keep_i = xs
            v_new = u_i - mm("bhid,bhde->bhie", w_i, state)
            o_i = (mm("bhid,bhde->bhie", q_i, state)
                   + mm("bhij,bhje->bhie", intra_i, v_new))
            state = state * keep_i + mm("bhid,bhie->bhde", k_i, v_new)
            return state, o_i.astype(dtype)

        state0 = jnp.zeros((b, h, dk, dv), jnp.float32)
        _, o = lax.scan(body, state0, (u, w, intra, q_in, k_out, keep))
        # [n, b, h, c, dv] -> [b, s, h, dv]
        return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, s, h, dv)


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence as written, one position at a time, in float32 at the
    highest matmul precision: what the chunked form is tested against."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    b, s, h, dk = q.shape
    hi = lax.Precision.HIGHEST

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs                  # [b, h, ...]
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhde,bhd->bhe", state, k_t, precision=hi)
        write = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t, precision=hi)

    by_position = lambda x: jnp.moveaxis(x, 1, 0)
    state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, state0, tuple(map(by_position,
                                            (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)
