"""Backend and tiling rules shared by the Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: f32 sublanes per vreg: the stream/chain axis is tiled by this many rows
ROWS = 8


def default_interpret() -> bool:
    """Only a real TPU runs the compiled Mosaic kernels; every other backend
    (cpu, gpu) gets Pallas interpreter mode."""
    return jax.default_backend() != "tpu"


def row_tile(b: int) -> int:
    """Rows of a ``(b, ...)`` stream/chain axis per grid step.

    Mosaic wants the last two block dims divisible by (8, 128) or equal
    to the array's, so an axis of at most ``ROWS`` rows is taken whole
    and a longer one in ``ROWS``-row tiles (see ``pad_rows``)."""
    return b if b <= ROWS else ROWS


def pad_rows(x, rows: int):
    """Pad axis 0 of ``x`` with zeros up to a multiple of ``rows``."""
    pad = (-x.shape[0]) % rows
    if not pad:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
