"""Pallas TPU kernel: elementwise (1, e, m) quantization.

Used to cast tensors to the representation format ((1,5,2) in the paper's
experiments) on the way into every GEMM.  VPU-bound elementwise op; blocks
are sized to stream through VMEM with lane-aligned tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.autotune import register_kernel
from repro.kernels.common import quantize_block, resolve_interpret

__all__ = ["quantize_pallas"]


def _quantize_kernel(x_ref, o_ref, *, e: int, m: int):
    o_ref[...] = quantize_block(x_ref[...].astype(jnp.float32), e, m)


@register_kernel("quantize")
@functools.partial(jax.jit, static_argnames=("e", "m", "block_rows", "interpret"))
def quantize_pallas(
    x: jnp.ndarray,
    *,
    e: int,
    m: int,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Quantize ``x`` to (1, e, m), returned as float32.

    The array is processed as a (rows, 128)-tiled 2D stream: 128 is the TPU
    lane width, ``block_rows`` rows of it keep the VMEM working set at
    block_rows * 128 * 4B * 2 (in + out) = 256KB by default.
    """
    shape = x.shape
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    lanes = 128
    rows = -(-n // lanes)
    rows_padded = -(-rows // block_rows) * block_rows
    pad = rows_padded * lanes - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    x2 = flat.reshape(rows_padded, lanes)

    out = pl.pallas_call(
        functools.partial(_quantize_kernel, e=e, m=m),
        grid=(rows_padded // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, lanes), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x2)
    return out.reshape(-1)[:n].reshape(shape)
