"""Pallas TPU kernel: matmul with reduced-precision chunked accumulation.

TPU-native realization of the paper's technique (DESIGN.md §3): the MXU
accumulates one K-tile (= one *chunk*, n1 = block_k) internally in wide
precision — the paper's ideal intra-chunk accumulation — and the running
carry across K-tiles (the inter-chunk accumulation) is rounded to the
(1, e_acc, m_acc) accumulator format prescribed by the VRR solver after
every chunk.  This is exactly the two-level scheme of Corollary 1 with
n1 = block_k, n2 = K / block_k.

With a wide accumulator format (e>=8, m>=23) the rounding folds to identity
and this is a plain tiled matmul — that degenerate path is what the exact
baseline uses, so kernel and baseline share one code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import register_kernel
from repro.kernels.common import pad2d, quantize_block, resolve_interpret

__all__ = ["qmatmul_pallas"]


def _qmatmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, e_acc: int, m_acc: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # intra-chunk: one MXU tile contraction, ideal (f32) accumulation
    partial = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )
    # inter-chunk: carry update rounded to the (1, e_acc, m_acc) format
    acc_ref[...] = quantize_block(acc_ref[...] + partial, e_acc, m_acc)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@register_kernel("qmatmul")
@functools.partial(
    jax.jit,
    static_argnames=("e_acc", "m_acc", "block_m", "block_n", "block_k", "interpret"),
)
def qmatmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    e_acc: int = 8,
    m_acc: int = 23,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """C[M, N] = A[M, K] @ B[K, N] with chunked (1, e_acc, m_acc) accumulation.

    block_k is the chunk size n1.  Block shapes are MXU-aligned by default
    (128-multiples); inputs are zero-padded up to block multiples (zero
    chunks are exact no-ops for the quantized carry since the quantizer is
    idempotent) and the result is sliced back.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad shapes {a.shape} @ {b.shape}")
    m, k = a.shape
    _, n = b.shape

    a32 = pad2d(a, block_m, block_k)
    b32 = pad2d(b, block_k, block_n)
    mp, kp = a32.shape
    np_ = b32.shape[1]

    out = pl.pallas_call(
        functools.partial(_qmatmul_kernel, e_acc=e_acc, m_acc=m_acc),
        grid=(mp // block_m, np_ // block_n, kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        # f32 VMEM carry tile: the *storage* of the emulated narrow
        # accumulator (its value is always exactly representable in
        # (1, e_acc, m_acc) after the per-chunk rounding).
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a32, b32)
    return out[:m, :n]
