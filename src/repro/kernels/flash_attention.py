"""Pallas TPU kernel: causal flash attention (forward).

Blocked online-softmax attention with running (m, l, acc) state held in VMEM
scratch across the kv grid dimension. Used for 32k prefill on TPU; the
numerical contract is kernels/ref.py::blocked_attention_ref (and the full
softmax oracle), asserted in tests across shape/dtype sweeps.

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost. Causal blocks past
the diagonal are skipped via pl.when (Pallas has no ragged grids; the skip
makes them no-ops — on TPU Mosaic still schedules the step, so the optimized
serving path additionally clamps the kv extent per q block in the wrapper).

Backward pass: training on TPU uses jax.custom_vjp with the blocked ref as
the bwd rule (remat-style recompute); a hand-written bwd kernel is left as a
documented non-goal — the fwd kernel is the serving hot path this paper
cares about.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import cdiv

_NEG_INF = -1e30


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    out_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # block is active iff its first kv index <= last (offset) q index
        run = ki * block_k <= q_offset + qi * block_q + block_q - 1

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # (bq, bk)

        rows = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        )
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cols < kv_len
        if causal:
            mask = jnp.logical_and(mask, cols <= rows)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        out_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "q_offset", "interpret"
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (bh, sq, d)
    k: jax.Array,  # (bh, sk, d)
    v: jax.Array,  # (bh, sk, d)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_k: int = 256,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """q_offset: position of q[0] within the kv sequence (chunked prefill);
    causal masking compares (q_offset + i) vs kv index j."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = (d**-0.5) if scale is None else scale

    sqp = cdiv(sq, block_q) * block_q
    skp = cdiv(sk, block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0)))

    grid = (bh, sqp // block_q, skp // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=sk,
        q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :sq, :]
