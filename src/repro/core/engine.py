"""Vectorized persistence-path-control feature engine (paper §5).

The paper's worker loop is per-event: retrieve -> materialize -> inclusion
probability -> Bernoulli -> optional write-back.  On an accelerator that loop
becomes a micro-batched tensor program.  Two execution modes are provided:

* ``exact``  — bit-faithful per-event sequential semantics.  Events are sorted
  by (key, t) and processed in *rounds*: round r handles every key's r-th
  event, so all rounds are conflict-free scatters and the loop length is the
  max events-per-key in the batch (static bound), not the batch size.
  The default round schedule is *segment-compacted*: instead of running every
  round over all B lanes under a mask (O(exact_rounds x B) gathers and kernel
  work), the sorted events are re-packed into chunks of ``exact_chunk`` lanes
  such that each chunk holds events of exactly one round (rounds are padded to
  chunk multiples), and a scan walks only the ceil(B/C) + exact_rounds chunks
  that can be non-empty — O(B + exact_rounds * C) total work.  Chunks inherit
  the rounds' conflict-freedom (one event per key per round) and their
  round-major order, so the schedule is a pure re-packing of the same per-lane
  kernel invocations: decisions and state are bit-identical to the masked
  schedule (``exact_impl='masked'`` keeps the reference implementation;
  derived features may differ by 1 ulp where XLA reassociates the std tail
  across the two compiled programs).

* ``fast``   — decisions for the whole micro-batch are taken against the
  batch-start state (decision staleness <= one batch), after which persisted
  contributions fold into the state with a *closed-form segment reduction*:
  because the HT update is a first-order linear recurrence, the end-of-batch
  state needs only a decay-weighted segment sum, no sequential scan.  The
  batch is segmented once by key (one sort of its B keys), the sums run over
  [B] segment buffers, and only the rows of the batch's keys are gathered,
  recomputed and scattered back: the fold is O(B log B) whatever the table
  size, and a row no event touches keeps its bits.  This is
  the production configuration (it is also what any asynchronous real system
  effectively does) and its staleness bias is bounded by the batch horizon.

Both modes route the whole §5.1 decision + read-modify-write through the
fused kernel ``repro.kernels.ops.thinning_rmw`` (Pallas on TPU, the fused
jnp reference on CPU): one pass over the gathered profile rows covers lazy
decay, feature materialization, intensity, inclusion probability, Bernoulli
thresholding, the HT masked update *and* the full-stream control column,
so nothing in this module re-derives the decision math.  Exact mode keeps
its per-round outputs in-place in the scan carry (no [rounds, B, 4T]
stacking), and the per-event uniforms / sort bookkeeping are computed once
per step, not once per round.

For steady-state streaming throughput use ``repro.core.stream.run_stream``,
which scans [n_batches, B] event blocks through one jitted, state-donating
dispatch (zero state copies between blocks).

Both modes use counter-based RNG keyed on (entity, time-bits) so a given event
receives the same thinning decision regardless of batching, ordering or shard
placement.  The step callables accept an optional ``rng_entity`` column for
callers whose ``Event.key`` is a *local* row index rather than the global
entity id: the sharded engine passes ``local_row * n_shards + shard``, and
the bounded-residency drivers (``core.stream.run_stream(residency=...)``)
pass the global id alongside slot-valued keys.  Nothing in either mode
assumes ``Event.key`` spans the entity space — state rows are addressed
purely by index, so the same step runs a dense per-entity table or a
slot-based resident set (``S`` rows, ``S << num_entities``) unchanged,
and thinning decisions are residency-invariant by construction.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import estimators, intensity, thinning
from repro.core.types import (Event, EngineConfig, ProfileState, StepInfo,
                              init_state)
from repro.kernels import ops

__all__ = ["init_state", "make_step", "materialize_features"]

# Finite stand-in for the -inf "never persisted" timestamps in ProfileState:
# the fused kernel masks freshness on `< -1e30` because -inf breaks 0*inf
# masking on the VPU.  exp(-(t + 1e38)/h) underflows to 0 exactly, so the
# substitution is behaviour-preserving on the decay paths.
_FRESH_SENTINEL = -1e38


# Per-event RNG counter (single definition in core.thinning, shared with
# the per-event worker for the persistence byte-parity contract).
_seq_bits = thinning.time_bits


def _fused_kw(cfg: EngineConfig) -> dict:
    """Static kernel parameters derived from the engine config."""
    return dict(h=cfg.h, budget=cfg.budget, alpha=cfg.alpha,
                policy=cfg.policy, fixed_rate=cfg.fixed_rate,
                mu_tau_index=cfg.mu_tau_index, min_p=cfg.min_p)


def _gather_rows(state: ProfileState, key: jax.Array):
    """Gather one profile row per event, sentinel-mapped for the kernel.

    Returns (last_t, v_f, agg_flat[B, 3T], v_full, last_t_full).
    """
    fin = lambda x: jnp.where(jnp.isfinite(x), x, _FRESH_SENTINEL)
    return (fin(state.last_t[key]), state.v_f[key],
            state.agg[key].reshape(key.shape[0], -1),
            state.v_full[key], fin(state.last_t_full[key]))


def _fused_rmw(cfg: EngineConfig, taus, state: ProfileState, key, q, t, u,
               valid):
    """One fused decision+update pass over gathered rows (whole profile row)."""
    last_t, v_f, agg_flat, v_full, last_t_full = _gather_rows(state, key)
    return ops.thinning_rmw(
        taus, last_t, v_f, agg_flat, q, t, u,
        valid.astype(jnp.float32), v_full, last_t_full, **_fused_kw(cfg))


def _sort_by_key_time(ev: Event):
    # Invalid (padding) lanes sort into their own trailing segment: otherwise
    # a padded tail block's key=0/t=0 filler would occupy entity 0's first
    # round slots and push its real events past exact_rounds.
    sort_key = jnp.where(ev.valid, ev.key, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((ev.t, sort_key))
    ev_s = Event(*(x[order] for x in ev))
    key_s = sort_key[order]
    idx = jnp.arange(ev.key.shape[0])
    is_start = jnp.concatenate(
        [jnp.array([True]), key_s[1:] != key_s[:-1]])
    start_idx = jnp.where(is_start, idx, 0)
    seg_start = jax.lax.cummax(start_idx)
    round_id = idx - seg_start  # position within (key)-segment
    return ev_s, order, round_id, seg_start


def _compact_schedule(round_id, valid_s, rounds: int, chunk: int):
    """Re-pack sorted lanes into single-round chunks of ``chunk`` lanes.

    Returns an int32 [n_chunks, chunk] table of sorted-lane indices (B marks
    an empty slot).  Each round's lanes are laid out contiguously, padded up
    to a chunk multiple, so no chunk ever spans two rounds — within a chunk
    every key occurs at most once (rounds are conflict-free) and chunks in
    scan order preserve round order.  sum_r ceil(n_r/C) <= floor(B/C) +
    rounds bounds the static chunk count.
    """
    B = round_id.shape[0]
    n_chunks = -(-B // chunk) + rounds
    rid = jnp.where(valid_s & (round_id < rounds), round_id, rounds)
    comp = jnp.argsort(rid)                      # stable: keeps lane order
    rid_c = rid[comp]
    counts = jnp.bincount(rid_c, length=rounds + 1)[:rounds]
    start = jnp.cumsum(counts) - counts          # exclusive, per round
    padded = -(-counts // chunk) * chunk
    poff = jnp.cumsum(padded) - padded
    rid_cl = jnp.minimum(rid_c, rounds - 1)
    slot = jnp.where(rid_c < rounds,
                     poff[rid_cl] + (jnp.arange(B) - start[rid_cl]),
                     n_chunks * chunk)
    lane_of_slot = jnp.full((n_chunks * chunk,), B, jnp.int32).at[slot].set(
        comp.astype(jnp.int32), mode="drop")
    return lane_of_slot.reshape(n_chunks, chunk)


def _step_exact(cfg: EngineConfig, impl: str, chunk: int, state: ProfileState,
                ev: Event, rng, rng_entity=None):
    taus = jnp.asarray(cfg.taus, jnp.float32)
    ent = ev.key if rng_entity is None else rng_entity
    ev_s, order, round_id, _ = _sort_by_key_time(ev)
    B = ev.key.shape[0]
    num_e = state.num_entities
    n_taus = taus.shape[0]

    # Round-invariant bookkeeping, hoisted out of the scan: the counter-based
    # uniforms depend only on (entity, t) and the inverse sort permutation
    # only on the batch — neither needs recomputation per round.
    u_s = thinning.uniform_for_events(rng, ent[order], _seq_bits(ev_s.t))
    inv = jnp.argsort(order)

    init = (state, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.float32), jnp.zeros((B, 4 * n_taus),
                                                    jnp.float32))

    def chunk_body(carry, lanes):
        # Compacted schedule: each chunk gathers only its (single-round)
        # active lanes, so the kernel pass is C-wide, not B-wide.
        state, p_o, z_o, lam_o, feats_o = carry
        active = lanes < B
        lane = jnp.where(active, lanes, 0)
        key = jnp.where(active, ev_s.key[lane], 0)
        t_lane = ev_s.t[lane]
        (_, new_v_f, new_agg, z, p, feats, lam, new_v_full, _) = _fused_rmw(
            cfg, taus, state, key, ev_s.q[lane], t_lane, u_s[lane], active)

        data_key = jnp.where(z, key, num_e)
        ctrl_key = jnp.where(active, key, num_e)
        state = state._replace(
            agg=state.agg.at[data_key].set(
                new_agg.reshape(lanes.shape[0], n_taus, 3), mode="drop"),
            v_f=state.v_f.at[data_key].set(new_v_f, mode="drop"),
            last_t=state.last_t.at[data_key].set(t_lane, mode="drop"),
            v_full=state.v_full.at[ctrl_key].set(new_v_full, mode="drop"),
            last_t_full=state.last_t_full.at[ctrl_key].set(t_lane,
                                                           mode="drop"),
        )

        # Scatter per-event outputs back to their sorted lane (each event is
        # active in exactly one chunk, so single-write scatters are exact).
        out_lane = jnp.where(active, lane, B)
        p_o = p_o.at[out_lane].set(p, mode="drop")
        z_o = z_o.at[out_lane].set(z, mode="drop")
        lam_o = lam_o.at[out_lane].set(lam, mode="drop")
        feats_o = feats_o.at[out_lane].set(feats, mode="drop")
        return (state, p_o, z_o, lam_o, feats_o), None

    def round_body(carry, r):
        state, p_o, z_o, lam_o, feats_o = carry
        active = (round_id == r) & ev_s.valid
        # Mask inactive lanes to a harmless key-0 gather; their updates are
        # discarded by the OOB-key 'drop' scatters below.
        key = jnp.where(active, ev_s.key, 0)
        (_, new_v_f, new_agg, z, p, feats, lam, new_v_full, _) = _fused_rmw(
            cfg, taus, state, key, ev_s.q, ev_s.t, u_s, active)

        # Conflict-free scatters: within a round each active key occurs once.
        # Persisted columns change only on z; the full-stream control column
        # changes on every active event.
        data_key = jnp.where(z, key, num_e)
        ctrl_key = jnp.where(active, key, num_e)
        state = state._replace(
            agg=state.agg.at[data_key].set(
                new_agg.reshape(B, n_taus, 3), mode="drop"),
            v_f=state.v_f.at[data_key].set(new_v_f, mode="drop"),
            last_t=state.last_t.at[data_key].set(ev_s.t, mode="drop"),
            v_full=state.v_full.at[ctrl_key].set(new_v_full, mode="drop"),
            last_t_full=state.last_t_full.at[ctrl_key].set(ev_s.t,
                                                           mode="drop"),
        )

        # In-place per-round outputs (each event is active in exactly one
        # round, so overwrite-under-mask is exact and nothing is stacked).
        p_o = jnp.where(active, p, p_o)
        z_o = z_o | z
        lam_o = jnp.where(active, lam, lam_o)
        feats_o = jnp.where(active[:, None], feats, feats_o)
        return (state, p_o, z_o, lam_o, feats_o), None

    if impl == "compact":
        schedule = _compact_schedule(round_id, ev_s.valid, cfg.exact_rounds,
                                     max(8, min(chunk, B)))
        (state, p_s, z_s, lam_s, feats_s), _ = jax.lax.scan(
            chunk_body, init, schedule)
    else:  # 'masked' — the O(exact_rounds x B) reference schedule
        (state, p_s, z_s, lam_s, feats_s), _ = jax.lax.scan(
            round_body, init, jnp.arange(cfg.exact_rounds))

    info = StepInfo(z=z_s[inv] & ev.valid, p=p_s[inv], lam_hat=lam_s[inv],
                    features=feats_s[inv],
                    writes=jnp.sum(z_s).astype(jnp.int32))
    return state, info


def _segment(key: jax.Array, valid: jax.Array, num_e: int):
    """Segment a batch by key: one segment per distinct valid key.

    Returns the segment id of each lane (int32 [B]) and the key of each
    segment (int32 [B]).  Segments are numbered in key order; invalid lanes
    share the last segment, whose key is ``num_e``, as is every unused one.
    """
    B = key.shape[0]
    key = jnp.where(valid, key, num_e)
    order = jnp.argsort(key)
    key_s = key[order]
    is_start = jnp.concatenate([jnp.array([True]), key_s[1:] != key_s[:-1]])
    seg_s = jnp.cumsum(is_start, dtype=jnp.int32) - 1
    seg = jnp.zeros((B,), jnp.int32).at[order].set(seg_s)
    seg_key = jnp.full((B,), num_e, key.dtype).at[seg_s].set(key_s)
    return seg, seg_key


def _step_fast(cfg: EngineConfig, state: ProfileState, ev: Event, rng,
               rng_entity=None):
    taus = jnp.asarray(cfg.taus, jnp.float32)
    num_e = state.num_entities
    B = ev.key.shape[0]
    ent = ev.key if rng_entity is None else rng_entity
    safe_key = jnp.where(ev.valid, ev.key, 0)

    # Decision stage: one fused pass against the batch-start state.  Only the
    # decision outputs (p, z, lam, features) are consumed here — the state
    # fold below is the closed-form segment reduction, which subsumes the
    # kernel's single-event RMW when keys repeat within the batch.
    with jax.named_scope("decide"):
        u = thinning.uniform_for_events(rng, jnp.where(ev.valid, ent, 0),
                                        _seq_bits(ev.t))
        (_, _, _, z, p, feats, lam, _, _) = _fused_rmw(
            cfg, taus, state, safe_key, ev.q, ev.t, u, ev.valid)

    # --- closed-form fold over the batch's keys --------------------------
    # Reductions run into [B] segment buffers (one segment per distinct key,
    # summed in lane order) and only the segments' rows are rewritten; the
    # rows of segments with nothing to fold go to row num_e and are dropped.
    with jax.named_scope("fold"):
        seg, seg_key = _segment(ev.key, ev.valid, num_e)
        old = jnp.minimum(seg_key, num_e - 1)     # gather-safe segment rows
        # Final per-key timestamp among persisted events:
        zseg = jnp.where(z, seg, B)
        t_star = jnp.full((B,), -jnp.inf).at[zseg].max(ev.t, mode="drop")
        wrote = jnp.isfinite(t_star)
        t_ref = jnp.where(wrote, t_star, 0.0)
        row = jnp.where(wrote, seg_key, num_e)
        last_t_old = state.last_t[old]

        inv_p = jnp.where(z, 1.0 / p, 0.0)
        # v_f: sum_i (1/p_i) exp(-(t* - t_i)/h) + decay(t* - last_t) * v_f
        w_v = inv_p * intensity.decay(t_ref[seg] - ev.t, cfg.h)
        v_add = jnp.zeros((B,)).at[zseg].add(w_v, mode="drop")
        v_f_rows = (v_add + intensity.decay(t_star - last_t_old, cfg.h)
                    * state.v_f[old])

        # aggregates: same fold per tau/column.
        # [B, T]
        beta_ev = intensity.decay((t_ref[seg] - ev.t)[:, None], taus)
        contrib = (inv_p[:, None, None] * beta_ev[:, :, None] *
                   jnp.stack([jnp.ones_like(ev.q), ev.q, ev.q * ev.q],
                             -1)[:, None, :])
        agg_add = jnp.zeros((B,) + state.agg.shape[1:]).at[zseg].add(
            contrib, mode="drop")
        agg_rows = agg_add + estimators.decay_to(state.agg[old], last_t_old,
                                                 t_star, taus)

        last_t_new = state.last_t.at[row].set(t_star, mode="drop")
        v_f_new = state.v_f.at[row].set(v_f_rows, mode="drop")
        agg_new = state.agg.at[row].set(agg_rows, mode="drop")

    # full-stream control column (every valid event).
    with jax.named_scope("fold_control"):
        vseg = jnp.where(ev.valid, seg, B)
        tf_star = jnp.full((B,), -jnp.inf).at[vseg].max(ev.t, mode="drop")
        saw = jnp.isfinite(tf_star)
        tf_ref = jnp.where(saw, tf_star, 0.0)
        row_f = jnp.where(saw, seg_key, num_e)
        w_full = jnp.where(ev.valid, 1.0, 0.0) * intensity.decay(
            tf_ref[seg] - ev.t, cfg.h)
        vfull_add = jnp.zeros((B,)).at[vseg].add(w_full, mode="drop")
        v_full_rows = (vfull_add + intensity.decay(
            tf_star - state.last_t_full[old], cfg.h) * state.v_full[old])
        v_full_new = state.v_full.at[row_f].set(v_full_rows, mode="drop")
        last_t_full_new = state.last_t_full.at[row_f].set(tf_star,
                                                          mode="drop")

    state = ProfileState(last_t=last_t_new, v_f=v_f_new, agg=agg_new,
                         v_full=v_full_new, last_t_full=last_t_full_new)
    info = StepInfo(z=z, p=p, lam_hat=lam, features=feats,
                    writes=jnp.sum(z).astype(jnp.int32))
    return state, info


def make_step(cfg: EngineConfig, mode: str = "exact", *,
              exact_impl: str = "compact", exact_chunk: int = 256) -> Callable:
    """Build a jit-able engine step: (state, Event, rng) -> (state, StepInfo).

    The step also accepts an optional ``rng_entity`` int32 [B] keyword: the
    entity ids fed to the counter-based thinning RNG when ``Event.key`` is a
    local row index rather than the global entity id (sharded callers).

    ``exact_impl`` selects the exact-mode round schedule: 'compact' (default,
    segment-compacted O(B + rounds * exact_chunk) work) or 'masked' (the
    O(rounds * B) reference).  Both produce bit-identical outputs; 'masked'
    exists as the equivalence oracle and for benchmarking the compaction win.
    """
    if mode == "exact":
        if exact_impl not in ("compact", "masked"):
            raise ValueError(f"unknown exact_impl {exact_impl!r}")
        return functools.partial(_step_exact, cfg, exact_impl, exact_chunk)
    if mode == "fast":
        return functools.partial(_step_fast, cfg)
    raise ValueError(f"unknown mode {mode!r}")


def materialize_features(state: ProfileState, keys: jax.Array, t: jax.Array,
                         taus, out_sharding=None) -> jax.Array:
    """Read-only feature materialization (serving path).

    ``out_sharding``: sharding of the gathered rows, required when the state
    is sharded over a mesh with explicit axes (the sharded engine passes
    replicated)."""
    taus = jnp.asarray(taus, jnp.float32)
    rows = lambda x: x.at[keys].get(out_sharding=out_sharding)
    agg_now = estimators.decay_to(rows(state.agg), rows(state.last_t), t, taus)
    return estimators.materialize(agg_now)
