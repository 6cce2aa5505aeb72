"""Operation and byte counts from shapes: the yardstick's arithmetic.

A copy of the accounting of ``tpudist/utils/flops.py``
(``transformer_train_flops``, ``attention_live_pairs``) kept with the
benchmark so that it does not move when the program does, plus the
operations and bytes the flash-attention algorithm needs.  Conventions:
one multiply-add is 2 FLOPs; the backward pass costs twice the forward;
causal attention does the work of its live (query, key) pairs only;
embedding lookups, norms, softmax and other vector work are left out;
recomputed operations (remat, the flash backward's second look at the
scores) are never counted as model FLOPs.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> float:
    """Attended (query, key) pairs of one causal sequence: s(s+1)/2."""
    return seq * (seq + 1) / 2.0


def attention_forward_flops(*, batch: int, seq: int, d_model: int) -> float:
    """Scores and values of one layer, all heads: 4 * pairs * d_model."""
    return 4.0 * batch * causal_pairs(seq) * d_model


def lm_forward_flops(*, batch: int, seq: int, d_model: int, n_layers: int,
                     d_ff: int, vocab: int) -> float:
    """Matmul FLOPs of one forward pass of the GPT-2-shaped decoder LM:
    per block qkv 6bsd^2 + proj 2bsd^2 + attention + FFN 4bsdf; head 2bsdV."""
    b, s, d = batch, seq, d_model
    per_block = (8.0 * b * s * d * d
                 + attention_forward_flops(batch=b, seq=s, d_model=d)
                 + 4.0 * b * s * d * d_ff)
    return n_layers * per_block + 2.0 * b * s * d * vocab


def lm_train_flops(**shape) -> float:
    """Model FLOPs of one training step: three forward passes' worth."""
    return 3.0 * lm_forward_flops(**shape)


def lm_train_flops_per_token(*, seq: int, **shape) -> float:
    return lm_train_flops(batch=1, seq=seq, **shape) / seq


#: the names attention's three kernels carry in a trace (the ``kernel`` of a
#: Mosaic custom call's ``kernel_metadata``), spelled once for the yardstick:
#: ``trace_reduce`` and the readers take them from here, and
#: ``tests/test_scope_readers.py`` holds them against the program's
#: (``tpudist/telemetry/names.py``).  A program that renames a kernel does
#: not thereby change what the benchmark takes for attention.
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
FLASH_KERNELS = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)


def flash_kernel_work(*, batch: int, seq: int, d_model: int, n_layers: int,
                      itemsize: int = 2) -> dict:
    """``kernel name -> (FLOPs, bytes)`` the attention ALGORITHM needs in one
    training step, all layers, split over the three kernels it runs as.

    Each kernel owns two of the six matmuls, ``4 * batch * pairs * d_model``
    a layer: forward QK^T and PV, the dq kernel dP and dQ, the dk/dv kernel
    dV and dK.  The flash backward recomputes the scores in both of its
    kernels; that is the kernels' choice and is not counted, so a kernel
    that recomputes less scores higher.  The three sum to forward once and
    backward twice that: ``3 * n_layers * attention_forward_flops``.

    The bytes are those of the algorithm if every tensor crossed HBM once,
    12 tensors of ``batch * seq * d_model`` a layer: forward reads q, k, v
    and writes o (4); backward reads q, k, v, o, do and writes dq, dk, dv
    (8).  Of the backward's 8, each kernel is given its own output (dq: 1,
    dk and dv: 2) and half of the five reads the two share, so the three
    sum to the 12.  That the split into two kernels reads them twice is the
    kernels' choice, like the recomputed scores: a backward kernel taken
    alone has to move 5 (dq) and 6 (dk/dv) tensors, so where the memory
    bound applies (a small head_dim, a short sequence) its share reads lower
    than its own traffic would give, and never higher."""
    f = n_layers * attention_forward_flops(batch=batch, seq=seq,
                                           d_model=d_model)
    tensor = n_layers * float(batch * seq * d_model * itemsize)
    return {FLASH_FWD: (f, 4.0 * tensor),
            FLASH_BWD_DQ: (f, 3.5 * tensor),
            FLASH_BWD_DKV: (f, 4.5 * tensor)}


def roofline_seconds(flops: float, bytes_: float, peak: dict) -> tuple:
    """(least seconds, which bound) for work of ``flops`` and ``bytes_``
    on a chip with the peaks of one entry of ``peaks.json``."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")
