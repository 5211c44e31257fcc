"""The benchmark's traffic generator: eight masked scenario families.

A copy of the masked generators of ``repro.core.scenarios`` (commit
0e01feb), at their default knobs, kept here so that a later change to
the program's scenarios does not move the yardstick.  Each family gives
``(speeds f32[B, T, N], active bool[B, T, N])`` from a key; ``fleet``
makes a whole fleet from the seed alone in one jitted call, on the
device, with every rate rounded to ``1 / round_to`` of a consumer's
capacity so that float32 load sums are exact and a float64 reference
packs decision for decision like the program.

Every generator clips speeds to ``>= 0``.  Rates are in units of the
consumer capacity C.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _walk(key, batch, iters, n, step_scale, init):
    steps = jax.random.uniform(key, (batch, iters - 1, n),
                               minval=-1.0, maxval=1.0) * step_scale
    return init[:, None, :] + jnp.concatenate(
        [jnp.zeros((batch, 1, n)), jnp.cumsum(steps, axis=1)], axis=1)


def _clipped_walk(key, batch, iters, n, step_scale, init):
    steps = jax.random.uniform(key, (iters - 1, batch, n),
                               minval=-1.0, maxval=1.0) * step_scale

    def body(s, phi):
        s = jnp.maximum(s + phi, 0.0)
        return s, s

    _, tail = jax.lax.scan(body, init, steps)
    return jnp.concatenate([init[None], tail], axis=0).transpose(1, 0, 2)


def _on(speeds):
    return speeds, jnp.ones(speeds.shape, bool)


def random_walk(key, batch, iters, n):
    """The paper's Eq. 11 walk: s_i = max(0, s_{i-1} + U[-0.1, 0.1])."""
    k_init, k_walk = jax.random.split(key)
    init = jax.random.uniform(k_init, (batch, n), maxval=1.0)
    return _on(_clipped_walk(k_walk, batch, iters, n, 0.1, init))


def diurnal(key, batch, iters, n, period=96, amplitude=0.4, noise=0.02):
    """Day/night cycle per partition plus walk noise."""
    k_mean, k_phase, k_amp, k_noise = jax.random.split(key, 4)
    mean = jax.random.uniform(k_mean, (batch, 1, n), minval=0.1, maxval=0.6)
    phase = jax.random.uniform(k_phase, (batch, 1, n), maxval=2 * jnp.pi)
    amp = jax.random.uniform(k_amp, (batch, 1, n), maxval=amplitude)
    t = jnp.arange(iters, dtype=jnp.float32)[None, :, None]
    wave = mean + amp * jnp.sin(2 * jnp.pi * t / period + phase)
    drift = _walk(k_noise, batch, iters, n, noise, jnp.zeros((batch, n)))
    return _on(jnp.maximum(wave + drift, 0.0))


def ramp(key, batch, iters, n, max_slope=1.5, noise=0.02):
    """Linear growth or decay per partition over the trace."""
    k_init, k_slope, k_noise = jax.random.split(key, 3)
    init = jax.random.uniform(k_init, (batch, 1, n), maxval=0.8)
    slope = jax.random.uniform(k_slope, (batch, 1, n), minval=-max_slope,
                               maxval=max_slope)
    t = jnp.arange(iters, dtype=jnp.float32)[None, :, None] / max(iters - 1, 1)
    drift = _walk(k_noise, batch, iters, n, noise, jnp.zeros((batch, n)))
    return _on(jnp.maximum(init + slope * t + drift, 0.0))


def bursty(key, batch, iters, n, base=0.15, p_spike=0.02, spike=1.0,
           decay=0.8):
    """Flash crowds: Bernoulli spikes decaying geometrically on a calm floor."""
    k_base, k_arrive, k_size = jax.random.split(key, 3)
    floor = jax.random.uniform(k_base, (batch, 1, n), minval=0.2,
                               maxval=1.0) * base
    arrive = jax.random.bernoulli(k_arrive, p_spike, (iters, batch, n))
    size = jax.random.uniform(k_size, (iters, batch, n), minval=0.3,
                              maxval=1.0) * spike

    def body(level, xs):
        hit, s = xs
        level = jnp.maximum(level * decay, jnp.where(hit, s, 0.0))
        return level, level

    _, levels = jax.lax.scan(body, jnp.zeros((batch, n)), (arrive, size))
    return _on(floor + levels.transpose(1, 0, 2))


def churn(key, batch, iters, n, p_flip=0.02, hot=0.5, noise=0.05):
    """Partitions flip between a hot rate and absent (true mask)."""
    k_state, k_flip, k_hot, k_noise = jax.random.split(key, 4)
    state0 = jax.random.bernoulli(k_state, 0.5, (batch, n))
    flips = jax.random.bernoulli(k_flip, p_flip, (iters, batch, n))
    parity = jnp.cumsum(flips.astype(jnp.int32), axis=0) % 2
    on = (state0[None] ^ (parity == 1)).transpose(1, 0, 2)
    level = jax.random.uniform(k_hot, (batch, 1, n), minval=0.5,
                               maxval=1.5) * hot
    jitter = 1.0 + jax.random.uniform(k_noise, (batch, iters, n),
                                      minval=-1.0, maxval=1.0) * noise
    return jnp.maximum(jnp.where(on, level * jitter, 0.0), 0.0), on


def heavy_tail(key, batch, iters, n, sigma=1.2, scale=0.1, noise=0.1):
    """Log-normal per-partition base rates with multiplicative noise."""
    k_base, k_noise = jax.random.split(key)
    base = jnp.exp(jax.random.normal(k_base, (batch, 1, n)) * sigma) * scale
    wob = _walk(k_noise, batch, iters, n, noise, jnp.zeros((batch, n)))
    return _on(base * jnp.exp(wob))


def topic_lifecycle(key, batch, iters, n, p_alive0=0.5, min_life_frac=0.15,
                    hot=0.5, noise=0.1):
    """One lifetime window [birth, death) per partition (true mask)."""
    k_alive0, k_birth, k_life, k_level, k_noise = jax.random.split(key, 5)
    alive0 = jax.random.bernoulli(k_alive0, p_alive0, (batch, n))
    birth = jax.random.uniform(k_birth, (batch, n), maxval=float(iters))
    birth = jnp.where(alive0, 0.0, birth)
    life = jax.random.uniform(k_life, (batch, n),
                              minval=min_life_frac * iters,
                              maxval=float(iters))
    death = birth + life
    t = jnp.arange(iters, dtype=jnp.float32)[None, :, None]
    active = (t >= birth[:, None, :]) & (t < death[:, None, :])
    level = jax.random.uniform(k_level, (batch, 1, n), minval=0.3,
                               maxval=1.5) * hot
    drift = _walk(k_noise, batch, iters, n, noise, jnp.zeros((batch, n)))
    return jnp.where(active, jnp.maximum(level + drift, 0.0), 0.0), active


def adversarial(key, batch, iters, n, base_rate=0.2, tail_sigma=1.0,
                burst_start_frac=0.4, burst_len_frac=0.25, burst_amp=1.5,
                churn_p=0.0, lifecycle_frac=0.0, birth_frac=0.0,
                death_frac=1.0, noise=0.05):
    """Heavy-tailed skew under a timed burst plateau, rates clamped to C."""
    k_tail, k_churn, k_state, k_sel, k_noise = jax.random.split(key, 5)
    w = jnp.exp(jax.random.normal(k_tail, (batch, 1, n)) * tail_sigma)
    w = w / jnp.mean(w, axis=2, keepdims=True)
    t = jnp.arange(iters, dtype=jnp.float32)[None, :, None]
    start = jnp.float32(burst_start_frac) * iters
    stop = start + jnp.float32(burst_len_frac) * iters
    plateau = ((t >= start) & (t < stop)).astype(jnp.float32)
    level = (jnp.float32(base_rate) + jnp.float32(burst_amp) * plateau) * w
    jitter = 1.0 + jax.random.uniform(k_noise, (batch, iters, n),
                                      minval=-1.0, maxval=1.0) * noise
    state0 = jax.random.bernoulli(k_state, 0.9, (batch, n))
    flips = jax.random.bernoulli(k_churn, churn_p, (iters, batch, n))
    parity = jnp.cumsum(flips.astype(jnp.int32), axis=0) % 2
    on = (state0[None] ^ (parity == 1)).transpose(1, 0, 2)
    subject = jax.random.uniform(k_sel, (batch, 1, n)) < lifecycle_frac
    birth = jnp.float32(birth_frac) * iters
    death = jnp.maximum(jnp.float32(death_frac), jnp.float32(birth_frac)) * iters
    in_window = (t >= birth) & (t < death)
    active = on & jnp.where(subject, in_window, True)
    speeds = jnp.clip(level * jitter, 0.0, 1.0)
    return jnp.where(active, speeds, 0.0), active


FAMILIES = {f.__name__: f for f in (random_walk, diurnal, ramp, bursty, churn,
                                    heavy_tail, topic_lifecycle, adversarial)}


def seed_key(seed: int):
    """A PRNG key from any whole-number seed: the low 32 bits seed the key
    and the rest are folded in, so seeds above 2**32 do not collide."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("families", "per_family",
                                             "steps", "n", "round_to"))
def _fleet(key, families, per_family, steps, n, round_to):
    keys = jax.random.split(key, len(families))
    parts = [FAMILIES[f](k, per_family, steps, n)
             for f, k in zip(families, keys)]
    speeds = jnp.concatenate([s for s, _ in parts]).astype(jnp.float32)
    active = jnp.concatenate([a for _, a in parts]).astype(bool)
    return jnp.round(speeds * round_to) / round_to, active


def fleet(seed: int, families, per_family: int, steps: int, n: int,
          round_to: int):
    """``(labels, speeds f32[B, T, N], active bool[B, T, N])`` on the default
    device: ``per_family`` groups of each family in ``families``, in order,
    ``B = per_family * len(families)``."""
    unknown = [f for f in families if f not in FAMILIES]
    if unknown:
        raise ValueError(f"unknown scenario families {unknown}; "
                         f"have {sorted(FAMILIES)}")
    labels = tuple(f for f in families for _ in range(per_family))
    speeds, active = _fleet(seed_key(seed), tuple(families), per_family,
                            steps, n, round_to)
    return labels, speeds, active
