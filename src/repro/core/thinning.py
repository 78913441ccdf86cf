"""Inclusion-probability policies (paper Eq. 2 and Eq. 4) and Bernoulli draws.

All policies are pure element-wise functions of persistence-backed statistics;
none requires in-memory control state, matching the paper's design goal (§4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def naive_inclusion(lam_hat: jax.Array, budget: float | jax.Array,
                    min_p: float = 1e-6) -> jax.Array:
    """Eq. (2):  p = min(1, Lambda / lam_hat).

    Guarantees E[sum Z_i] <= Lambda * t (expected write rate bounded by the
    budget) whenever lam_hat tracks the true intensity.
    """
    p = jnp.minimum(1.0, budget / jnp.maximum(lam_hat, 1e-30))
    return jnp.clip(p, min_p, 1.0)


def _logit(p: jax.Array, eps: float = 1e-6) -> jax.Array:
    p = jnp.clip(p, eps, 1.0 - eps)
    return jnp.log(p) - jnp.log1p(-p)


def variance_aware_inclusion(lam_hat: jax.Array, budget: float | jax.Array,
                             w: jax.Array, mu_w: jax.Array, sigma_w: jax.Array,
                             alpha: float | jax.Array,
                             min_p: float = 1e-6) -> jax.Array:
    """Eq. (4):  p = sigmoid( logit(min(1, Lambda/lam_hat)) + alpha * (w-mu)/sigma ).

    Tilts the naive inclusion logit by the standardized contribution magnitude,
    reallocating write probability toward statistically influential events
    (importance-sampling flavour) while keeping the total budget approximately
    fixed: the tilt is ~zero-mean under the historical contribution law.
    """
    base = jnp.minimum(1.0, budget / jnp.maximum(lam_hat, 1e-30))
    zscore = (w - mu_w) / jnp.maximum(sigma_w, 1e-8)
    # Clip the standardized score: Eq. 4's tilt is meant to *protect* tail
    # events, a +-8 sigma clip keeps logits finite under fp32 without ever
    # mattering statistically.
    zscore = jnp.clip(zscore, -8.0, 8.0)
    p = jax.nn.sigmoid(_logit(base) + alpha * zscore)
    # Events already at p≈1 under the naive rule stay mandatory.
    p = jnp.where(base >= 1.0 - 1e-6, 1.0, p)
    return jnp.clip(p, min_p, 1.0)


def fixed_rate_inclusion(shape, rate: float | jax.Array,
                         min_p: float = 1e-6) -> jax.Array:
    """Naive fixed-rate baseline (global probability, activity-independent)."""
    return jnp.full(shape, jnp.clip(rate, min_p, 1.0), jnp.float32)


def bernoulli_mask(rng: jax.Array, key_ids: jax.Array, seq_ids: jax.Array,
                   p: jax.Array) -> jax.Array:
    """Reproducible, order-independent thinning decisions: ``u < p`` with
    each event's uniform keyed on (entity, counter) by the counter rule of
    ``counters`` below."""
    u = uniform_for_events(rng, key_ids, seq_ids)
    return u < p


def time_bits(t: jax.Array) -> jax.Array:
    """The float32 bit pattern of each timestamp: the default counter."""
    return jax.lax.bitcast_convert_type(t.astype(jnp.float32), jnp.uint32)


def counters(t: jax.Array, ctr=None) -> jax.Array:
    """Per-event RNG counters, by the one rule the engine, the per-event
    oracle (``core.reference``) and the worker (``streaming.worker``) share.

    Each event's thinning uniform is keyed on (entity, counter), so the
    decision for a given event is independent of batch composition, shard
    placement and replay order: the exact and fast modes agree, and a
    worker stores the bytes the engine's sink stores.  The counter is the
    stream's own per-event id ``ctr`` (uint32) where the stream supplies
    one, and otherwise ``time_bits(t)``.  Two events of one entity with
    equal counters draw the same uniform and are written together or not
    at all, which breaks the independence of the Horvitz-Thompson draws:
    a stream whose events can share a timestamp on one entity (event times
    of millisecond resolution, several events a millisecond) has to supply
    ``ctr``.
    """
    return time_bits(t) if ctr is None else jnp.asarray(ctr).astype(
        jnp.uint32)


def uniform_for_events(rng: jax.Array, key_ids: jax.Array,
                       seq_ids: jax.Array) -> jax.Array:
    mixed = jax.vmap(
        lambda k, s: jax.random.fold_in(jax.random.fold_in(rng, k), s)
    )(key_ids.astype(jnp.uint32), seq_ids.astype(jnp.uint32))
    return jax.vmap(lambda k: jax.random.uniform(k, ()))(mixed)
