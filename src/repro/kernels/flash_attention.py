"""Flash attention (prefill/training fwd) as a Pallas TPU kernel.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
innermost ("arbitrary") axis, accumulating an online softmax in VMEM
scratch.  GQA is handled in the K/V BlockSpec index maps (q head h reads kv
head h // group), so grouped K/V are never materialized H-wide in HBM --
unlike the jnp reference path, which must jnp.repeat them.

VMEM working set per grid step (bf16 in, f32 accumulate):
    q tile (block_q, hd) + k/v tiles (block_k, hd) + acc (block_q, hd)
    + scores (block_q, block_k)
With the default block_q = block_k = 512, hd = 128: ~2.6 MB -- comfortably
inside the ~16 MB v5e VMEM, and all matmul dims are multiples of 128 so the
MXU is fully tiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, block_q: int, block_k: int, n_kv: int,
                  sm_scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    # causal: skip kv blocks strictly above the diagonal
    @pl.when((not causal) or (ki * block_k <= qi * block_q + block_q - 1))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                                  # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd) with H % KV == 0.

    Returns (B, H, Sq, hd) in q.dtype.
    """
    b, h, sq, hd = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    nq, nk = sq // block_q, skv // block_k
    sm_scale = hd ** -0.5

    kernel = functools.partial(_flash_kernel, causal=causal, block_q=block_q,
                               block_k=block_k, n_kv=nk, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
