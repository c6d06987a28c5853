"""Pallas TPU kernel: single-token GQA decode attention against a KV cache.

The long-context decode hot spot (decode_32k / long_500k shapes): one query
token per sequence attends to a length-``s`` cache.  Memory-bound — the roofline
term is the cache read — so the kernel streams (block_s, dh) cache tiles through
VMEM once, with the whole GQA group (q heads sharing a kv head) processed per
tile to amortize the read across the group.

Grid = (batch, kv_heads, s_tiles); online softmax state for the (group, dh)
output accumulates in VMEM scratch across the sequential s axis.  Per-batch
valid lengths (ragged cache) and sliding windows are masked in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, window: int | None, block_s: int):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                    # (TS, Dh)
    v = v_ref[0, 0].astype(jnp.float32)                    # (TS, Dh)
    length = len_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    kpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    if window is not None:
        mask &= (length - 1 - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(si == ns - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, *, window: int | None = None,
                            block_s: int = 512, interpret: bool = True
                            ) -> jax.Array:
    """q: (b, hq, dh); k, v: (b, hkv, s, dh); lengths: (b,) int32.
    s % block_s == 0 (pad via `ops.decode_attention`).  Returns (b, hq, dh)."""
    b, hq, dh = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0 and s % block_s == 0
    group = hq // hkv
    qg = q.reshape(b, hkv, group, dh)
    grid = (b, hkv, s // block_s)
    sm_scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale, window=window,
                               block_s=block_s)
    out = pl.pallas_call(
        kernel,
        name="decode_attention_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (b,)),
            pl.BlockSpec((1, 1, group, dh), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, dh), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, dh), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(b, hq, dh)
