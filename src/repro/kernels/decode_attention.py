"""GQA decode attention (flash-decoding) as a Pallas TPU kernel.

One new token per sequence attends to a length-``cache_len`` KV cache.
Grid: (batch, kv_heads, cache_blocks); the cache axis is innermost and
accumulates online-softmax state in VMEM scratch.  The q heads of one kv
group (G = H/KV rows) are processed together, so the MXU sees a
(G x hd) @ (hd x block_s) matmul per step; ``cache_len`` arrives in SMEM and
masks the tail block.

VMEM per step: k/v tiles (block_s, hd) + acc (G, hd) + scores (G, block_s);
with block_s=512, hd=128, G<=8: ~0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_S = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, block_s: int, n_s: int, sm_scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[0]
    s_pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)

    @pl.when(si * block_s <= cache_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bs, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(s_pos <= cache_len, s, NEG_INF)         # (G, bs)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_fwd(q, k_cache, v_cache, cache_len, *,
                         block_s: int = DEFAULT_BLOCK_S,
                         interpret: bool = False):
    """q: (B, KV, G, hd); k/v_cache: (B, KV, S, hd); cache_len: () int32 --
    attends to positions [0, cache_len] (inclusive: the new token's K/V must
    already be written at ``cache_len``).  Returns (B, KV, G, hd).
    """
    b, kvh, g, hd = q.shape
    _, _, s, _ = k_cache.shape
    block_s = min(block_s, s)
    assert s % block_s == 0
    n_s = s // block_s
    kernel = functools.partial(_decode_kernel, block_s=block_s, n_s=n_s,
                               sm_scale=hd ** -0.5)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, h_, s_, len_ref: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, s_, len_ref: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_s, hd),
                         lambda b_, h_, s_, len_ref: (b_, h_, s_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda b_, h_, s_, len_ref: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q, k_cache, v_cache)
