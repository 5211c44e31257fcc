"""RWKV-6 WKV recurrence as a Pallas TPU kernel.

The jnp reference scans T sequential steps with a (B, H, hd, hd) state --
4096 tiny HLO loop iterations on TPU, each launching VPU work with poor
occupancy.  The kernel instead runs grid (B, H) with the whole per-head
(T, hd) streams resident in VMEM and a fori_loop over T that keeps the
(hd, hd) state in VMEM scratch: one kernel launch, zero HBM traffic for the
state, T*(hd x hd) outer-product updates on the VPU back to back.

VMEM per grid step: 4 streams (T, hd) f32 + state (hd, hd) + out (T, hd):
T=4096, hd=64 -> ~5.3 MB.  For longer T the ops.py wrapper chunks T and
carries the state between calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_out_ref,
                state_ref, *, t_steps: int, hd: int):
    state_ref[...] = s0_ref[0, 0]

    def step(t, _):
        r_t = r_ref[0, t, 0, :]                      # (hd,)
        k_t = k_ref[0, t, 0, :]
        v_t = v_ref[0, t, 0, :]
        w_t = w_ref[0, t, 0, :]
        u = u_ref[0]                                 # (hd,)
        kv = k_t[:, None] * v_t[None, :]             # (hd, hd) outer product
        s = state_ref[...]
        o_ref[0, t, 0, :] = jnp.sum(
            r_t[:, None] * (s + u[:, None] * kv), axis=0)
        state_ref[...] = w_t[:, None] * s + kv
        return ()

    jax.lax.fori_loop(0, t_steps, step, ())
    s_out_ref[0, 0] = state_ref[...]


def rwkv6_wkv_fwd(r, k, v, w, u, s0, *, interpret: bool = False):
    """r,k,v,w: (B, T, H, hd) f32; u: (H, hd); s0: (B, H, hd, hd).

    Returns (out (B, T, H, hd), s_last (B, H, hd, hd)).
    w is the per-step decay in (0, 1) (already exp(-exp(.)) transformed).
    """
    b, t, h, hd = r.shape
    kernel = functools.partial(_wkv_kernel, t_steps=t, hd=hd)
    stream = pl.BlockSpec((1, t, 1, hd), lambda b_, h_: (b_, 0, h_, 0))
    state = pl.BlockSpec((1, 1, hd, hd), lambda b_, h_: (b_, h_, 0, 0))
    out, s_last = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[stream, stream, stream, stream,
                  pl.BlockSpec((1, hd), lambda b_, h_: (h_, 0)),
                  state],
        out_specs=[stream, state],
        out_shape=[jax.ShapeDtypeStruct((b, t, h, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return out, s_last
