"""Pallas TPU kernel: int8 x int8 -> int32 MXU matmul with per-row/col scales.

TPU adaptation of the iMARS crossbar MVM (Sec. III-A2): the analog
current-summed matrix-vector product becomes an int8 systolic matmul on the
MXU with int32 accumulation and per-channel dequantization — the same
quantization contract (int8 weights and activations, higher-precision
accumulate) the paper's crossbars assume.

Blocking: (block_m, block_k) x (block_k, block_n) tiles with an int32 VMEM
scratch accumulator; k is the innermost grid dimension so the accumulator is
revisited consecutively. All block dims default to MXU-aligned multiples of
128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import cdiv


def _matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        out_ref[...] = (
            acc_ref[...].astype(jnp.float32) * sx_ref[...] * sw_ref[...]
        )


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def int8_matmul_pallas(
    x: jax.Array,  # (m, k) int8
    w: jax.Array,  # (k, n) int8
    x_scale: jax.Array,  # (m, 1) f32
    w_scale: jax.Array,  # (1, n) f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2

    mp, np_, kp = (
        cdiv(m, block_m) * block_m,
        cdiv(n, block_n) * block_n,
        cdiv(k, block_k) * block_k,
    )
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    sxp = jnp.pad(x_scale, ((0, mp - m), (0, 0)))
    swp = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))

    grid = (mp // block_m, np_ // block_n, kp // block_k)
    out = pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(xp, wp, sxp, swp)
    return out[:m, :n]
