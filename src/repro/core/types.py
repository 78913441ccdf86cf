"""Core datatypes for persistence-path control.

The durable per-entity state is deliberately minimal: the paper's design goal
(§4) is that thinning decisions read *only* state already persisted for feature
maintenance.  Control statistics are therefore either (a) the filtered KDE
numerator ``v_f`` — one scalar per entity — or (b) *derived* from the decayed
aggregates themselves (mu_w / sigma_w for Eq. 4 come straight from the
count/sum/sumsq columns), never from an auxiliary in-memory plane.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

# Aggregate columns maintained per (entity, tau).
AGG_COUNT = 0
AGG_SUM = 1
AGG_SUMSQ = 2
NUM_AGG_COLS = 3


class Event(NamedTuple):
    """A micro-batch of B events.

    An event updates the profiles of K entities (K = 1 where ``key`` is
    ``[B]``): the engine runs one lane per (event, profile) pair, event by
    event, and returns each lane's outputs as ``[B, K]``.
    """

    key: jax.Array    # int32 [B] or [B, K] entity (state row) index
    q: jax.Array      # float32 [B] quantitative mark (e.g. amount)
    # float32 [B] event time in seconds; float32 keeps distinct
    # milliseconds distinct below 2**14 s (its step there is 2**-10 s)
    t: jax.Array
    valid: jax.Array  # bool [B] padding mask
    # uint32 [B] per-event RNG counter, or None for ``time_bits(t)``: the
    # rule is ``core.thinning.counters``
    ctr: Optional[jax.Array] = None


class ProfileState(NamedTuple):
    """Durable, entity-partitioned profile table (the KV store contents).

    Shapes: E = number of entities, T = number of decay constants.
    """

    last_t: jax.Array   # f32 [E] time of last *persisted* event (-inf if fresh)
    v_f: jax.Array      # f32 [E] filtered KDE numerator  (paper §4.2)
    agg: jax.Array      # f32 [E, T, 3] HT decayed count / sum / sumsq (§3.3)
    # Reference full-stream control column (baseline only; a real deployment
    # of persistence-path control would not maintain these).
    v_full: jax.Array   # f32 [E] unfiltered KDE numerator (Eq. 5)
    last_t_full: jax.Array  # f32 [E] last *event* time (full-stream)

    @property
    def num_entities(self) -> int:
        return self.last_t.shape[0]

    @property
    def num_taus(self) -> int:
        return self.agg.shape[1]


def init_state(num_entities: int, num_taus: int, dtype=jnp.float32) -> ProfileState:
    # Distinct buffers per field (no aliasing): donated-state drivers
    # (core/stream.py) require every leaf to own its storage.
    return ProfileState(
        last_t=jnp.full((num_entities,), -jnp.inf, dtype),
        v_f=jnp.zeros((num_entities,), dtype),
        agg=jnp.zeros((num_entities, num_taus, NUM_AGG_COLS), dtype),
        v_full=jnp.zeros((num_entities,), dtype),
        last_t_full=jnp.full((num_entities,), -jnp.inf, dtype),
    )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Configuration of the feature-engine thinning mechanism.

    Attributes:
      taus: decay constants (seconds) for the maintained aggregations; the
        paper uses windows from 1 minute to 120 days (§6.1).
      h: KDE bandwidth (seconds) for arrival-intensity estimation (Eq. 5).
      budget: user-defined write budget Lambda (expected writes / second / key).
      alpha: variance-aware tilt strength (Eq. 4); 0 disables.
      policy: 'pp' (persistence-path, Eq. 2), 'pp_vr' (persistence-path +
        variance reduction, Eq. 4), 'full' (full-stream control baseline),
        'fixed' (naive fixed-rate baseline), 'unfiltered'.
      fixed_rate: inclusion probability for the 'fixed' policy.
      mu_tau_index: which tau's aggregates supply (mu_w, sigma_w) for Eq. 4.
      min_p: numerical floor on inclusion probabilities (keeps HT weights and
        logits finite; the paper's min(1, Lambda/lam) never reaches 0 for
        finite lam, this enforces it under fp32).
      exact_rounds: static bound on events-per-key-per-microbatch for the
        exact sequential-semantics mode.
    """

    taus: Sequence[float] = (60.0, 3600.0, 86400.0, 30 * 86400.0, 60 * 86400.0, 120 * 86400.0)
    h: float = 3600.0
    budget: float = 0.01
    alpha: float = 0.0
    policy: str = "pp"
    fixed_rate: float = 0.1
    mu_tau_index: int = 2
    min_p: float = 1e-6
    exact_rounds: int = 16

    def __post_init__(self):
        if self.policy not in ("pp", "pp_vr", "full", "fixed", "unfiltered"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if not self.taus:
            raise ValueError("need at least one decay constant")
        # Normalize to a hashable tuple: configs are used as cache / static
        # jit keys (core/stream.py), which a list-valued taus would break.
        object.__setattr__(self, "taus", tuple(self.taus))
        if not 0 <= self.mu_tau_index < len(self.taus):
            # standardization window defaults to the longest maintained
            # decay when the configured index exceeds the tau list (the
            # default index of 2 targets the paper's 1-day window but
            # shorter profiles are common in tests/benchmarks)
            object.__setattr__(self, "mu_tau_index", len(self.taus) - 1)


class StepInfo(NamedTuple):
    """Per-event observability emitted by one engine step; with K profiles
    per event each per-event field has a K axis after B."""

    z: jax.Array         # bool [B] persisted?
    p: jax.Array         # f32 [B] inclusion probability used
    lam_hat: jax.Array   # f32 [B] intensity estimate at decision time
    features: jax.Array  # f32 [B, F] materialized feature vector (pre-update)
    writes: jax.Array    # i32 [] number of persistence ops this batch
