"""Pallas TPU kernel: blocked flash attention (forward, single head).

Canonical FlashAttention-2 schedule on a (Sq/bq, Skv/bk) grid with the kv
axis minor/sequential: fp32 VMEM scratch carries the running max `m`, the
normaliser `l`, and the un-normalised accumulator across kv steps; the output
block is written once on the last kv step.  Supports causal masking, sliding
windows (gemma-style local layers) and logit soft-capping (gemma2).

VMEM tiling: q/o (bq, dh), k/v (bk, dh), scores (bq, bk); defaults
bq = bk = 256, dh <= 256 keep the working set well under 2 MB.

Used by the serving stack; training uses the pure-JAX chunked-scan attention
in repro.models.attention (which lowers on any backend for the dry-run).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, bq: int, bk: int, kv_steps: int, sq: int, skv: int,
    causal: bool, window: int, softcap: float, scale: float,
):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: fully-masked (qi, ki) tiles do no work
    block_needed = True
    if causal:
        block_needed = (ki * bk) <= (qi * bq + bq - 1 + (skv - sq))

    @pl.when(block_needed)
    def _compute():
        s = (
            jnp.dot(q_ref[...], k_ref[...].T, preferred_element_type=jnp.float32)
            * scale
        )
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attn_pallas(
    q: jax.Array,  # (Sq, dh)
    k: jax.Array,  # (Skv, dh)
    v: jax.Array,  # (Skv, dh)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = True,
) -> jax.Array:
    sq, dh = q.shape
    skv = k.shape[0]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad = lambda s, b: (s + b - 1) // b * b
    sq_p, skv_p = pad(sq, bq), pad(skv, bk)
    qp = jnp.pad(q, ((0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, skv_p - skv), (0, 0)))
    vp = jnp.pad(v, ((0, skv_p - skv), (0, 0)))
    # padded kv columns must never win the softmax: causal mask handles the
    # tail automatically when sq==skv; otherwise mask via window of valid len
    kv_steps = skv_p // bk
    grid = (sq_p // bq, kv_steps)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            bq=bq, bk=bk, kv_steps=kv_steps, sq=sq_p, skv=skv_p,
            causal=causal, window=window, softcap=softcap,
            scale=1.0 / (dh ** 0.5),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, dh), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, dh), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:sq]
