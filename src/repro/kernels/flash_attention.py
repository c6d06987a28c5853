"""Pallas TPU flash attention (forward) with GQA, causal and sliding-window masks.

Online-softmax tiling (Dao et al.) adapted to the TPU memory hierarchy: the
grid is (batch, q_heads, q_tiles, kv_tiles) with the kv axis innermost and
sequential, so the running max / denominator / accumulator live in VMEM scratch
that persists across kv tiles.  GQA is expressed in the BlockSpec index maps:
the k/v blocks for q head ``h`` come from kv head ``h // group`` — no KV
duplication in HBM.  Block shapes default to (128, head_dim) tiles: 128 rows
align the MXU systolic array, head_dim (64-256 in the arch pool) is the lane
dimension.

A production TPU deployment would add a causal grid-skip (launching only the
lower-triangular kv tiles); here fully-masked tiles are computed and masked,
which is correct and exercises the same memory traffic pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (TQ, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                   # (TK, Dh)
    v = v_ref[0, 0].astype(jnp.float32)                   # (TK, Dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (TQ,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (b, hq, sq, dh); k, v: (b, hkv, sk, dh).  sq % block_q == 0 and
    sk % block_k == 0 (pad via `ops.flash_attention`)."""
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0 and sq % block_q == 0 and sk % block_k == 0
    group = hq // hkv
    grid = (b, hq, sq // block_q, sk // block_k)
    sm_scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(_flash_kernel, sm_scale=sm_scale, causal=causal,
                               window=window, block_q=block_q, block_k=block_k,
                               kv_len=sk)
    return pl.pallas_call(
        kernel,
        name="flash_attention_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
