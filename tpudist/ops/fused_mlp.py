"""Pallas TPU kernel: the toy 5-layer MLP forward, fused into one kernel.

The reference's entire workload is this MLP (2→10→10→10→10→1, LeakyReLU —
``toy_model_and_data.py:12-22``).  XLA already fuses the chain well; this
kernel is the explicit-VMEM formulation: all five weight matrices are
zero-padded once to lane-aligned ``[128, 128]`` tiles, a batch tile streams
in per grid step, and the five matmul+LeakyReLU stages run back-to-back on
the MXU/VPU with activations never leaving VMEM.  Padding with zeros is
exact: padded input lanes are zero, padded weight rows/cols are zero, and
LeakyReLU(0) = 0, so the extra lanes stay zero through every layer.

Entry points: :func:`pad_params` once per weight set, then
:func:`fused_mlp` per batch; :func:`mlp_reference` is the dense XLA
formulation the tests compare against.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudist.telemetry import names

LANE = 128
NEGATIVE_SLOPE = 0.01  # torch.nn.LeakyReLU default, toy_model_and_data.py:14


def _leaky_relu(x):
    return jnp.where(x >= 0, x, NEGATIVE_SLOPE * x)


def _fused_kernel(x_ref, *refs, n_layers: int):
    """refs = (w_0, b_0, …, w_{n-1}, b_{n-1}, o_ref); everything VMEM."""
    o_ref = refs[-1]
    h = x_ref[:]
    for i in range(n_layers):
        w, b = refs[2 * i][:], refs[2 * i + 1][:]
        # HIGHEST: full-f32 MXU passes — the toy dims are tiny, so the 3-pass
        # cost is noise, and it keeps the kernel bit-comparable to XLA's VPU
        # fallback for small shapes.
        h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST) + b
        if i + 1 < n_layers:  # final layer is the linear regression head
            h = _leaky_relu(h)
    o_ref[:] = h


def pad_params(
    weights: Sequence[Tuple[jax.Array, jax.Array]],
) -> Tuple[Tuple[jax.Array, ...], int, int]:
    """Zero-pad each ``(w [din, dout], b [dout])`` to ``[LANE, LANE]``/
    ``[1, LANE]`` tiles.  Returns (flat padded refs, true d_in, true d_out)."""
    flat = []
    for w, b in weights:
        wp = jnp.zeros((LANE, LANE), jnp.float32).at[: w.shape[0], : w.shape[1]].set(w)
        bp = jnp.zeros((1, LANE), jnp.float32).at[0, : b.shape[0]].set(b)
        flat += [wp, bp]
    return tuple(flat), weights[0][0].shape[0], weights[-1][0].shape[1]


def fused_mlp(
    x: jax.Array,
    padded_params: Tuple[jax.Array, ...],
    d_out: int,
    *,
    block_batch: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Run the fused forward.  ``x: [batch, d_in]`` (batch % block_batch == 0
    or batch < block_batch); params from :func:`pad_params`."""
    n_layers = len(padded_params) // 2
    batch, d_in = x.shape
    bb = min(block_batch, batch)
    if batch % bb:
        raise ValueError(f"block_batch {bb} must divide batch {batch}")
    xp = jnp.zeros((batch, LANE), x.dtype).at[:, :d_in].set(x)

    kernel = functools.partial(_fused_kernel, n_layers=n_layers)
    wspecs = []
    for _ in range(n_layers):
        wspecs += [
            pl.BlockSpec((LANE, LANE), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, LANE), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((batch, LANE), jnp.float32),
        grid=(batch // bb,),
        in_specs=[
            pl.BlockSpec((bb, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM),
            *wspecs,
        ],
        out_specs=pl.BlockSpec((bb, LANE), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        **names.kernel(names.FUSED_MLP),
        interpret=interpret,
    )(xp, *padded_params)
    return out[:, :d_out]


def mlp_reference(x, weights):
    """Dense XLA forward for the same ``[(w, b), …]`` list."""
    h = x
    for i, (w, b) in enumerate(weights):
        h = h @ w + b
        if i + 1 < len(weights):
            h = _leaky_relu(h)
    return h
