"""Gated delta-rule linear attention, computed in chunks.

The recurrence, per head, with a state ``S [dk, dv]`` that starts at zero,
in two forms that differ in what forgets::

    S <- exp(g_t) * S          a decay a head: g_t one number
                               (Yang et al. 2024, "Gated Delta Networks")
    S <- Diag(exp(g_t)) S      a decay a CHANNEL: g_t [dk], a number a row
                               of S (Kimi Team 2025, "Kimi Linear")
    S <- S + k_t (x) (beta_t * (v_t - S^T k_t))
    o_t = S^T q_t

``g [b, s, h]`` asks for the first and ``g [b, s, h, dk]`` for the second;
a decay a channel that is the same over a head's channels IS the first
(held by a test to float32 rounding).

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

With a decay a head every decay is ``exp`` of a difference of cumulative
sums that is taken before the ``exp`` and is never positive, so nothing
overflows however strongly a head forgets.

**A decay a channel** does not factor out of the contraction over ``dk``:
``A[i, j] = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` (``G [c, dk]``
now), and ``q k^T . decay`` likewise.  The products are taken on operands
scaled by ``exp(G - G_r)`` and ``exp(G_r - G)`` against a reference
position ``r``, and a single ``r`` a chunk would put ``exp`` of a chunk's
whole decay, with the sign that overflows, on one of them.  So a chunk's
rows go by sub-blocks of ``SUB_BLOCK`` = 16 (:func:`_pairs_by_channel`),
each with its first row as ITS reference: a row ``i`` of the sub-block is
scaled by ``exp(G_i - G_r)``, never positive; the keys of EARLIER
sub-blocks by ``exp(G_r - G_j)``, never positive either; only the keys of
the sub-block itself (the diagonal sub-blocks, where both signs meet) take
a positive exponent, at most the sub-block's own span, ``15`` positions'
decay; keys of later sub-blocks are masked before the ``exp``.  One batched
product ``[4, 16, dk] x [4, 64, dk]`` a chunk gives the chunk's ``[64, 64]``
(the multiply-adds of the per-head form's one product), for ``A`` and for
``q k^T`` on the same scaled keys; ``w``, the scan's ``q exp(G)``, ``k
exp(G_last - G)`` and the state's ``exp(G_last)`` (a number a ROW of ``S``)
are elementwise and never positive.  Float32 (and bf16) hold ``e^88``: with
sub-blocks of 16 no exponent passes that for a decay down to ``e^-5.8`` a
position a channel.  Beyond it the gate is CLAMPED: the per-channel form
computes the recurrence of ``max(g, -GATE_FLOOR)``, ``GATE_FLOOR`` = 5 (the
largest exponent is then 75), so a channel that would forget to under
``e^-5`` = 0.7% a position forgets to 0.7% instead (after two positions
4.5e-5 is left where less should be; the gradient through a clamped gate is
zero).  That is a departure from the recurrence as written, stated where a
model's gate can reach it (``-exp(A_log) * softplus(.)`` passes 5 only for
``A_log`` over 1.6 at a softplus of 1); narrower sub-blocks would move the
bound and were not needed.  The per-head path traces to the program it was.

Differentiable by JAX's own rules: the backward pass is autodiff through
the chunked form, with the scan's body under ``jax.checkpoint`` so that
what is kept per chunk is the carried state alone (``[b, h, dk, dv]``
float32 a chunk) and the body's products are recomputed.  The inverse
alone has a backward pass of its own, ``dA = -T^T dT T^T``, whose one
residual is ``T``; differentiated, ``T`` carries the name
``names.DELTA_INVERSE`` (float32, its two minor dimensions as one), so
that a caller that rematerialises the call can keep it
(``tpudist.models.hybrid.remat_keeps``: ``tokens x heads x chunk x 4``
bytes) and its rematerialised forward then solves for nothing: the
inverse's ten products, and with a decay a channel the product that makes
``A``, run once a step.  Everything else of the forward (the decays, ``u``,
``w``, the scan) runs again.  Under no policy, or one that does not name
it, the name is an identity.  Plain XLA, no Pallas kernel; the whole of it
runs under the scope ``names.DELTA_RULE``.

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
drawn from ``(0, 1)`` and from ``(0, 2)``; a decay a channel at 16 / 16 and
8 / 16 here (128 / 128 compiled), against the per-position loop forward and
in every gradient, at mild decays, AT the floor (``g`` in ``[-5, -4.5]``: a
sub-block's span of ``e^-75``), over one chunk and over several, and
constant over channels against the per-head path
(``tests/test_kimi_linear.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpudist.telemetry import names


#: a chunk's rows go by sub-blocks this long where the decay is a number a
#: channel, and the gate is held above ``-GATE_FLOOR`` a position there: the
#: largest exponent taken is a sub-block's span, ``(SUB_BLOCK - 1) *
#: GATE_FLOOR`` = 75, and float32 (bf16 too) holds ``e^88``
SUB_BLOCK = 16
GATE_FLOOR = 5.0


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
    # output and residual are the ONE named value, so that a rematerialised
    # caller whose policy keeps the name has nothing left to solve for (a name
    # on the output alone leaves the residual to be computed again); named
    # with its two minor dimensions as one: as it lies its 64 columns take 128
    # lanes, and a ``T`` kept so would hold twice its bytes
    inv = checkpoint_name(
        _unit_lower_inverse(a, precision, base).reshape(*a.shape[:-2], -1),
        names.DELTA_INVERSE).reshape(a.shape)
    return inv, inv


def _unit_lower_inverse_bwd(precision, base, inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, d_inv, precision=precision), t,
                        precision=precision),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _pairs_by_channel(k, cum, sub: int, mm, dtype):
    """What stands in for ``mm(x, k) * decay`` where the decay is a number a
    channel: ``pairs(x)[i, j] = sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])``
    over the pairs ``j <= i`` of a chunk (entries above the diagonal hold
    what the products leave there: finite, and masked by the caller), for
    ``k [n, b, h, c, dk]`` and ``cum`` its ``G``, as matrix products: the
    chunk goes by sub-blocks of ``sub`` rows, each against ONE reference
    position of its own, its first row ``r``: ``x_i exp(G_i - G_r)`` (the
    exponent is never positive: ``i >= r``) against ``k_j exp(G_r - G_j)``,
    whose exponent is never positive for a ``j`` of an earlier sub-block,
    at most the sub-block's own span for one of the same sub-block, and
    ``-inf`` (masked before the ``exp``) for one of a later sub-block,
    whose pairs are all dead."""
    n, b, h, c, dk = k.shape
    m = c // sub
    blocks = cum.reshape(n, b, h, m, sub, dk)
    ref = blocks[:, :, :, :, 0]                          # G_r, [n, b, h, m, dk]
    ahead = jnp.exp(blocks - ref[:, :, :, :, None]).reshape(n, b, h, c, dk)
    # row block I reads the keys up to its own last row
    reach = jnp.arange(c)[None, :] < (jnp.arange(m)[:, None] + 1) * sub
    behind = jnp.exp(jnp.where(
        reach[:, :, None],
        ref[:, :, :, :, None] - cum[:, :, :, None], -jnp.inf))
    k_behind = (k.astype(jnp.float32)[:, :, :, None] * behind).astype(dtype)

    def pairs(x):
        x = (x.astype(jnp.float32) * ahead).astype(dtype)
        return mm("nbhmid,nbhmjd->nbhmij",
                  x.reshape(n, b, h, m, sub, dk), k_behind).reshape(
                      n, b, h, c, c)

    return pairs


def chunked_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                             beta_max: float = 1.0):
    """``q, k [b, s, h, dk]``, ``v [b, s, h, dv]`` (``dk`` and ``dv`` need
    not be equal), ``beta [b, s, h]`` and ``g [b, s, h]`` (a decay a head)
    or ``g [b, s, h, dk]`` (a decay a channel: told apart by the array's
    rank) -> ``o [b, s, h, dv]`` in ``v``'s dtype.  ``g <= 0`` is the log of
    the per-position decay (a decay a channel is computed for
    ``max(g, -GATE_FLOOR)``: the module's docstring), ``beta`` in
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
    by_channel = g.ndim == 4
    if by_channel and chunk % SUB_BLOCK:
        raise ValueError(f"a chunk of {chunk} is not a whole number of "
                         f"sub-blocks of {SUB_BLOCK}")
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
        if by_channel:
            g = jnp.maximum(g, -GATE_FLOOR)
        cum = jnp.cumsum(g, axis=3)            # G, [n, b, h, c] or [.., dk]
        last = cum[:, :, :, -1:]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        if by_channel:
            pairs = _pairs_by_channel(k, cum, SUB_BLOCK, mm, dtype)
            rows = lambda factor: factor         # [n, b, h, c, dk] as it is
        else:
            # exp(G_i - G_j) for j <= i and 0 above the diagonal: the
            # difference is masked before the exp, so no positive number is
            # exponentiated
            decay = jnp.exp(jnp.where(
                lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
            pairs = lambda x: mm("nbhid,nbhjd->nbhij", x, k) * decay
            rows = lambda factor: factor[..., None]
        grow = rows(jnp.exp(cum))
        k_beta = (k.astype(jnp.float32) * beta[..., None]).astype(dtype)
        a = pairs(k_beta)
        a = jnp.where(jnp.tril(lower, -1), a, 0.0)
        t = _unit_lower_inverse(a, inverse_precision,
                                _inverse_base(beta_max)).astype(dtype)
        # what the scan multiplies it multiplies in ``dtype``
        u = mm("nbhij,nbhjd->nbhid", t, v.astype(jnp.float32)
               * beta[..., None]).astype(dtype)
        w = mm("nbhij,nbhjd->nbhid", t,
               k_beta.astype(jnp.float32) * grow).astype(dtype)
        intra = pairs(q)
        if by_channel:   # the products' own entries above the diagonal
            intra = jnp.where(lower, intra, 0.0)
        intra = intra.astype(dtype)
        q_in = (q.astype(jnp.float32) * grow).astype(dtype)
        k_out = (k.astype(jnp.float32)
                 * rows(jnp.exp(last - cum))).astype(dtype)
        # what is left of the carried state behind the chunk: a number a
        # head [n, b, h, 1, 1], or one a row of the state [n, b, h, dk, 1]
        keep = (jnp.swapaxes(jnp.exp(last), -1, -2) if by_channel
                else jnp.exp(last)[..., None])

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
    highest matmul precision: what the chunked form is tested against.
    ``g [b, s, h]`` (a decay a head) or ``g [b, s, h, dk]`` (a decay a
    channel, as it is: nothing is clamped here)."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    b, s, h, dk = q.shape
    hi = lax.Precision.HIGHEST
    if g.ndim == 3:
        g = g[..., None]

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs                  # [b, h, ...]
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhde,bhd->bhe", state, k_t, precision=hi)
        write = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t, precision=hi)

    by_position = lambda x: jnp.moveaxis(x, 1, 0)
    state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, state0, tuple(map(by_position,
                                            (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)
