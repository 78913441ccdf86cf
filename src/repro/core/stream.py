"""Donated-buffer streaming driver for the vectorized engine.

``run_stream`` turns the per-batch Python dispatch loop (one ``jit`` call,
one host round-trip and one state copy per micro-batch) into a single
jitted program: the flat event stream is reshaped to ``[n_batches, B]``
blocks and scanned through the engine step with the profile state as the
scan carry.  The entry state buffers are donated
(``jax.jit(..., donate_argnums=(0,))``), so at steady state the state is
updated in place — zero state copies and one dispatch per event block.

This is the paper's decoupling argument applied to the driver itself: the
per-event worker loop (streaming/worker.py) pays retrieve/serde/dispatch
per event; the vectorized engine pays it per micro-batch; ``run_stream``
pays it once per block of micro-batches.

Donation / aliasing contract
----------------------------
``donate_argnums=(0,)`` hands the caller's state buffers to XLA for in-place
reuse, which imposes two invariants on every caller:

* **No aliased leaves.**  Every ``ProfileState`` leaf must own distinct
  storage.  Two fields sharing one buffer (e.g. a state built by reusing the
  same ``jnp.zeros`` array for ``v_f`` and ``v_full``) make XLA raise
  "Attempt to donate the same buffer twice" at dispatch time —
  ``core.types.init_state`` therefore allocates each leaf separately, and any
  hand-built state must do the same before entering a donating driver.
* **The input state is dead after the call.**  Donation invalidates the
  caller's arrays even on backends that fall back to copying; reusing them
  raises a deleted-buffer error.  Callers that need the pre-stream state must
  copy it first (or pass ``donate=False``).

The same contract applies to ``features.engine.ShardedFeatureEngine.run_stream``,
which drives its mesh-sharded state through the same ``block_runner_for``
machinery below — donation then applies per device shard.

Bounded residency (``run_stream(residency=...)``) replaces the dense
per-entity state with a slot-based resident set: the flush-group driver
gains a hydrate→dispatch→evict schedule (``_drive_with_residency``) that
translates event keys to slots on the host, prefetches the next group's
misses through the write-behind sink's ordered read pipeline while the
current group computes, and recycles victim slots without any device
read-back — see ``streaming/residency.py`` for the contract.

Pipelined execution (``run_stream(pipeline_depth=2)``) moves the host
side of that schedule onto a *prep thread*: while group g runs on
device, the prep thread plans group g+1 (lane routing, valid masks,
oversized-group splitting, slot assignment via the ResidencyMap's
vectorized batch take), issues its hydration reads through the sink's
epoch-gated lane (``WriteBehindSink.stage_epoch`` — the pipelined
replacement for dispatcher-FIFO read ordering), and packs its hydration
arrays into a fresh staging generation.  The dispatch thread only pops
staged groups, dispatches them (JAX async dispatch returns immediately)
and submits their outputs; it never blocks on device results — the only
device sync points are the sink's gather-side ``np.asarray`` conversions
on the flush dispatcher, which is exactly where host pack work hides
(``SinkStats.overlap_frac`` measures it directly).

Staging-generation (ping-pong) contract: the prep thread packs each
group's input arrays into a *fresh* generation of host buffers, holding
a token from a ``pipeline_depth``-deep pool from pack time until the
dispatch thread pops that generation off the ready queue.  Soundness
does not rest on the token: generations are never reused or mutated —
the popped generation stays alive through the jit call via the dispatch
thread's own references, JAX copies committed host operands into device
buffers at dispatch, and donation only ever applies to the state carry,
never to the staged inputs.  The token is purely the memory bound (at
most ``pipeline_depth`` packed generations queued, plus the one being
dispatched).  Releasing at pop time — not after the jit call returns —
is what makes ``pipeline_depth=2`` a true ping-pong: one generation is
consumed by the device while the prep thread fills the next; releasing
after dispatch would hold both tokens for the whole device window and
idle the prep thread exactly when there is compute to hide under.
``pipeline_depth=1`` is the serial driver, byte-for-byte.
"""
from __future__ import annotations

import functools
import queue
import threading
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import make_step
from repro.core.spans import span
from repro.core.types import EngineConfig, Event, ProfileState, StepInfo

__all__ = ["run_stream", "block_runner_for", "sink_step_for",
           "residency_step_for", "gather_rows", "hydrate_scatter"]


def block_runner_for(step, collect_info: bool = True, donate: bool = True):
    """Build a scan-over-blocks driver for an arbitrary engine step.

    ``step``: jit-able (state, Event, rng, *consts) -> (state, StepInfo);
    events are [n_blocks, B] pytrees scanned along axis 0 with the state as
    the (donated) carry.  The block *width* B is the step's layout contract,
    not the runner's: the local engine feeds ``[n_batches, batch]`` blocks,
    the sharded engine ``[n_blocks, n_shards * batch_per_shard]`` blocks
    whose columns are shard-aligned — the runner only fixes the scan axis.

    Trailing ``*consts`` operands are layout side inputs threaded unchanged
    to every step invocation (e.g. the virtual layout's ``gid_of_row``
    table, see ``distributed.rebalance``).  They are ordinary jit arguments
    — **never donated** — so a const may be reused across calls, but it must
    not alias a state leaf (the donation contract above would then donate
    the same buffer twice).

    Each call returns a *fresh* jit wrapper — callers must hold on to it
    across dispatches or they retrace every time (``_block_runner`` below
    memoizes per (cfg, mode, flags); ``ShardedFeatureEngine.run_stream``
    memoizes per engine instance, so the runner's lifetime matches its
    engine rather than pinning it globally).
    """
    def scan_blocks(state: ProfileState, events: Event, rng, *consts):
        def body(st, ev):
            st, info = step(st, ev, rng, *consts)
            return st, (info if collect_info else info.writes)
        return jax.lax.scan(body, state, events)

    return jax.jit(scan_blocks, donate_argnums=(0,) if donate else ())


def gather_rows(state: ProfileState, idx):
    """Gather the sink's post-update rows: ``(scalars[4, N], agg[N, T, 3])``
    at the flat state rows ``idx`` (any shape, flattened), scalar columns
    stacked ``[last_t, v_f, v_full, last_t_full]``."""
    idx = jnp.reshape(idx, (-1,))
    scal = jnp.stack([state.last_t[idx], state.v_f[idx], state.v_full[idx],
                      state.last_t_full[idx]])
    return scal, state.agg[idx]


def sink_step_for(step, collect_info: bool = True, donate: bool = True,
                  gather=None):
    """Per-group jitted step for the write-behind persistence path.

    Unlike ``block_runner_for`` (one scan over all blocks), the sink path
    dispatches one jitted call per *flush group* — a short scan over ``G``
    consecutive event blocks (``run_stream``'s ``sink_group``) — so the
    host can hand each group's outputs to a
    ``streaming.persistence.WriteBehindSink`` between dispatches: device
    compute of group k+1 overlaps serialization and storage of group k.
    Grouping is the group-commit knob: larger ``G`` amortizes per-dispatch
    host overhead, at the price of a longer durability lag (a crash loses
    at most ``G`` blocks plus what the queue holds).

    The returned callable is ``(state, events[G, B], rng,
    gather_idx[G*B], *consts) -> (state, outs, (scalars[4, G*B],
    agg[G*B, T, 3]))`` where the rows are the *post-update* profile rows
    gathered at ``gather_idx`` (state row per lane; the local engine
    passes the group's keys, the sharded engine each lane's row within
    its own shard) —
    scalar columns stacked as ``[last_t, v_f, v_full, last_t_full]`` so
    the host pays two device reads per group, not five.  Rows are
    end-of-group snapshots; since persisted columns only change on a
    key's own z events, each selected key's lane still carries exactly
    the row the per-event worker would have stored last (byte parity is
    window-size-independent).  The gather itself is pure data movement,
    which is what makes the sink's stored bytes bit-identical to the
    engine state.  The donation contract above applies per call: the
    previous group's state is dead after each dispatch.

    ``collect_info=False`` replaces the per-block StepInfo output with the
    ``(z, writes)`` pair the sink actually needs, so XLA dead-code-
    eliminates the per-event p/lam/features materialization exactly like
    the scan path does.

    ``gather`` overrides ``gather_rows``: the sharded engine passes a
    ``shard_map``-wrapped one that gathers each shard's lanes from its own
    rows, returning ``(scalars[4, G, W], agg[G, W, T, 3])``; the sink
    flattens either form on the host.

    The program compiles as ``jit_run``, the name a device trace gives
    its executions; its row gather runs under ``named_scope("sink_gather")``.
    """
    gather = gather or gather_rows

    def run(state: ProfileState, events: Event, rng, gather_idx, *consts):
        def body(st, ev):
            st, info = step(st, ev, rng, *consts)
            return st, (info if collect_info else (info.z, info.writes))
        state, outs = jax.lax.scan(body, state, events)
        with jax.named_scope("sink_gather"):
            return state, outs, gather(state, gather_idx)

    return jax.jit(run, donate_argnums=(0,) if donate else ())


def hydrate_scatter(state: ProfileState, slots, scal, agg) -> ProfileState:
    """Scatter hydrated rows into resident slots (the read half of the
    slot-based residency refactor).

    ``slots``: int32 [H] state rows, padded with an out-of-range index
    (``mode='drop'`` ignores the padding lanes); ``scal``: [4, H] columns
    stacked ``[last_t, v_f, v_full, last_t_full]`` (same order as the
    ``sink_step_for`` gather); ``agg``: [H, T, 3].  Values come straight
    from ``kvstore.SerDe.unpack_rows`` — an exact f32 round-trip of the
    engine state — or the ``init_state`` defaults for keys with no durable
    row yet, so hydration is bit-exact by construction.
    """
    return state._replace(
        last_t=state.last_t.at[slots].set(scal[0], mode="drop"),
        v_f=state.v_f.at[slots].set(scal[1], mode="drop"),
        agg=state.agg.at[slots].set(agg, mode="drop"),
        v_full=state.v_full.at[slots].set(scal[2], mode="drop"),
        last_t_full=state.last_t_full.at[slots].set(scal[3], mode="drop"))


def residency_step_for(step, collect_info: bool = True, donate: bool = True,
                       scatter=None, gather=None):
    """``sink_step_for`` plus a hydration prologue for bounded residency.

    The returned callable is ``(state, events, rng, gather_idx,
    h_slots[H], h_scal[4, H], h_agg[H, T, 3], *consts) -> (state, outs,
    rows)``: hydrated rows are scattered into their assigned slots
    *before* the scan (misses of this flush group, staged by the host
    while the previous group computed), then the group runs exactly like
    the sink path with ``Event.key`` holding *slot* indices.  ``events``
    is whatever pytree ``step`` scans — the residency drivers pass
    ``(Event, rng_entity)`` so thinning stays keyed on global entity ids
    and decisions are residency-invariant.  ``scatter`` overrides the
    hydration scatter (the sharded engine passes a ``shard_map``-wrapped
    one) and ``gather`` the row gather, as in ``sink_step_for``; ``H`` is
    padded to a power of two by the drivers so the jit cache stays small.
    The donation contract of ``sink_step_for`` applies unchanged.
    """
    scatter = scatter or hydrate_scatter
    gather = gather or gather_rows

    def residency_group(state: ProfileState, events, rng, gather_idx,
                        h_slots, h_scal, h_agg, *consts):
        state = scatter(state, h_slots, h_scal, h_agg)

        def body(st, ev):
            st, info = step(st, ev, rng, *consts)
            return st, (info if collect_info else (info.z, info.writes))
        state, outs = jax.lax.scan(body, state, events)
        with jax.named_scope("sink_gather"):
            return state, outs, gather(state, gather_idx)

    return jax.jit(residency_group, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _block_runner(cfg: EngineConfig, mode: str, collect_info: bool,
                  donate: bool, exact_impl: str):
    """One scan-over-blocks program per (cfg, mode, flags)."""
    return block_runner_for(make_step(cfg, mode, exact_impl=exact_impl),
                            collect_info, donate)


@functools.lru_cache(maxsize=None)
def _sink_step(cfg: EngineConfig, mode: str, collect_info: bool,
               donate: bool, exact_impl: str):
    """One per-flush-group sink-path program per (cfg, mode, flags)."""
    return sink_step_for(make_step(cfg, mode, exact_impl=exact_impl),
                         collect_info, donate)


@functools.lru_cache(maxsize=None)
def _residency_step(cfg: EngineConfig, mode: str, collect_info: bool,
                    donate: bool, exact_impl: str):
    """One hydrate+scan+gather program per (cfg, mode, flags): the core
    step scans ``(Event, rng_entity)`` pairs so ``Event.key`` can hold
    slot indices while thinning stays keyed on global entity ids."""
    step = make_step(cfg, mode, exact_impl=exact_impl)

    def estep(st, ev_ent, rng):
        ev, ent = ev_ent
        return step(st, ev, rng, rng_entity=ent)

    return residency_step_for(estep, collect_info, donate)


def hydration_width(m: int) -> int:
    """Padded hydration width for ``m`` miss rows: the next power of two
    (minimum 1), bounding the jit shape cache.  Single definition shared
    by ``pack_hydration`` and the sharded driver's common per-shard
    width — the [n_shards * H] segment packing relies on both using the
    same rule."""
    return 1 << max(int(m) - 1, 0).bit_length() if m else 1


def pack_hydration(rows, miss_slots, serde, n_slots: int, n_taus: int,
                   width: int = None):
    """Decode one group's hydration reads into scatter-ready arrays.

    ``rows``: ``ReadTicket.result()`` output aligned with the miss keys
    (``None`` for keys with no durable row — they get the ``init_state``
    defaults, matching a never-persisted entity).  Returns ``(h_slots[H],
    h_scal[4, H], h_agg[H, T, 3])`` with ``H`` the next power of two of
    the miss count (bounds the jit shape cache) and padding lanes pointed
    at the out-of-range slot ``n_slots`` (dropped by the scatter).
    ``width`` overrides ``H`` (must be >= the miss count) — the sharded
    driver passes one common per-shard width so the segments concatenate
    into a uniform ``[n_shards * H]`` layout.
    """
    m = len(miss_slots)
    H = hydration_width(m) if width is None else int(width)
    h_slots = np.full(H, n_slots, np.int32)
    h_scal = np.zeros((4, H), np.float32)
    h_scal[0] = -np.inf                     # last_t init
    h_scal[3] = -np.inf                     # last_t_full init
    h_agg = np.zeros((H, n_taus, 3), np.float32)
    if m:
        h_slots[:m] = miss_slots
        present = [i for i, r in enumerate(rows) if r is not None]
        if present:
            lt, vf, ag, vfl, ltf = serde.unpack_rows(
                [rows[i] for i in present])
            idx = np.asarray(present)
            h_scal[0, idx] = lt.astype(np.float32)
            h_scal[1, idx] = vf.astype(np.float32)
            h_scal[2, idx] = vfl.astype(np.float32)
            h_scal[3, idx] = ltf.astype(np.float32)
            h_agg[idx] = ag
    return h_slots, h_scal, h_agg


def merge_miss_rows(fresh_mask, rows_fresh, rows_re):
    """Re-interleave the two read lanes' rows back into miss order."""
    it_f, it_r = iter(rows_fresh), iter(rows_re)
    return [next(it_f) if f else next(it_r) for f in fresh_mask]


class _GroupPlan(NamedTuple):
    """One flush group's host-side dispatch plan (residency drivers)."""
    events: object          # pytree the group program scans
    gather_idx: np.ndarray  # flat state rows to gather for the sink
    sink_keys: np.ndarray   # flat global entity ids (sink row keys)
    valid: np.ndarray       # flat padding mask
    # hydration reads, split by ordering need: first-touch keys (no flush
    # of this run can hold them -> the sink's unordered fast lane) vs
    # rehydrations (must ride the FIFO behind earlier flushes)
    fresh_keys: np.ndarray
    rehydrate_keys: np.ndarray
    build_hydration: object  # (rows_fresh, rows_re) -> (h_slots, ...)
    # False on all but the final sub-group of a split oversized flush
    # group (``streaming.residency.split_oversized_group``): the driver
    # merges sub-group outputs back into one per-group output at the
    # ``last`` marker
    last: bool = True


def run_stream(cfg: EngineConfig, state: ProfileState, keys, qs, ts,
               *, batch: int = 4096, mode: str = "fast",
               rng: Optional[jax.Array] = None, collect_info: bool = True,
               donate: bool = True, exact_impl: str = "compact",
               sink=None, sink_group: int = 4, residency=None,
               pipeline_depth: int = 1
               ) -> Tuple[ProfileState, Union[StepInfo, jax.Array]]:
    """Drive the engine over a flat stream in ``[n_batches, batch]`` blocks.

    keys/qs/ts: flat [N] arrays (numpy or jax); the tail is padded with
    invalid events to a full block.  Returns the final state plus either a
    flat StepInfo trimmed back to N events (``collect_info=True``) or the
    per-block write counts [n_batches] (``collect_info=False`` — cheapest:
    nothing per-event leaves the device).

    ``donate=True`` donates the input state's buffers to the call; do not
    reuse ``state`` afterwards.  (On backends without donation support JAX
    silently falls back to copying.)  ``exact_impl`` selects the exact-mode
    round schedule (see ``core.engine.make_step``); benchmarks use 'masked'
    to measure the segment-compaction win.

    ``sink``: an optional ``streaming.persistence.WriteBehindSink``.  When
    given, the stream is driven in flush groups of ``sink_group``
    consecutive blocks (``sink_step_for``) and each group's decisions +
    post-update rows are submitted for durable write-behind flush; device
    compute of the next group overlaps storage of the previous one.
    ``sink_group`` is the group-commit knob: larger groups amortize
    per-dispatch host overhead against a longer durability lag.  With a
    durable-backed sink (``WriteBehindSink(backend="durable")``) that
    boundary is physical, not modeled: each flush group lands on each
    touched partition as one atomic WAL batch under one fsync
    (``streaming/durable.py``), so a crash loses at most the trailing
    unflushed groups and recovery replays the log to exactly a group
    boundary — never half a group.  A sink built with
    ``max_unsynced_bytes=`` adds measured-IO admission on top of the
    bounded queue: this loop is held at ``submit()`` while more than that
    many submitted bytes remain un-landed (un-fsynced, for the durable
    backend), so a slow disk backpressures the engine by real IO
    completion, not by modeled service times.  The caller owns the sink
    lifecycle —
    call ``sink.flush()`` (or close it) to wait for the trailing groups.  State values are identical to the
    single-scan path (the engine numerics are
    compilation-context-invariant — ``kernels/detmath.py``).

    ``residency``: an int slot budget ``S`` or a prebuilt
    ``streaming.residency.ResidencyMap``.  The state then holds ``S``
    *slots* instead of one row per entity (build it with
    ``init_state(S, ...)``; ``S << num_entities``), event keys are
    translated to slots per flush group, misses are hydrated from the
    sink's durable stores with one ordered batched read per group
    (prefetched while the previous group computes; a sink built with
    ``l2=`` answers them from its host-RAM tier first) and victims are
    recycled per the map's eviction policy and demoted into the L2 tier —
    see ``streaming/residency.py`` for the eviction contract and why
    evict→rehydrate is bit-exact.  A flush group with more distinct keys
    than slots no longer raises: it is split into key-complete sub-groups
    that each fit (``split_oversized_group``), dispatched back-to-back
    with per-key FIFO order preserved.  Requires
    ``sink`` (the durable store is the backing level of the hierarchy);
    thinning decisions stay keyed on global entity ids, so ``z``/``p``/
    features and stored bytes are independent of the residency budget.

    ``pipeline_depth``: host/device overlap for the sink and residency
    drivers.  ``1`` (default) is the serial flush-group loop, unchanged.
    ``>= 2`` runs the pipelined plane (see the module docstring): a prep
    thread plans, reads and packs up to ``pipeline_depth`` groups ahead
    of the dispatch thread, with hydration ordering carried by the
    sink's epoch-gated read lane instead of dispatcher FIFO position.
    Outputs (z/p/lam/features and stored bytes) are bit-identical to the
    serial driver for every policy and mode — CI enforces it
    (``tests/test_pipelined.py``).  Requires a sink; the residency form
    additionally requires a threaded sink with the pure-backpressure
    overflow policy (``queue_depth >= 1``, ``overflow="block"``).
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    depth = int(pipeline_depth)
    if depth < 1:
        raise ValueError("pipeline_depth must be >= 1")
    if depth > 1 and sink is None:
        raise ValueError(
            "pipeline_depth > 1 requires a sink: the pipelined plane "
            "overlaps host group prep with device compute across flush "
            "groups, which the single-scan path does not have")
    n = int(np.shape(keys)[0])
    pad = (-n) % batch
    host_blocks = lambda x, fill: np.reshape(
        np.pad(np.asarray(x), (0, pad), constant_values=fill), (-1, batch))
    key_h = host_blocks(np.asarray(keys, np.int32), 0)
    q_h = host_blocks(np.asarray(qs, np.float32), 0.0)
    t_h = host_blocks(np.asarray(ts, np.float32), 0.0)
    valid_h = host_blocks(np.ones(n, bool), False)

    if residency is not None:
        from repro.streaming.residency import (ResidencyMap,
                                               split_oversized_group)
        if sink is None:
            raise ValueError(
                "residency requires a write-behind sink: evicted slots "
                "rely on the durable store for rehydration")
        if isinstance(residency, ResidencyMap):
            rmap = residency
        else:
            num_keys = int(np.max(key_h)) + 1 if n else 1
            rmap = ResidencyMap(num_keys, int(residency))
        if state.num_entities != rmap.n_slots:
            raise ValueError(
                f"state holds {state.num_entities} rows but the resident "
                f"set has {rmap.n_slots} slots; build it with "
                f"init_state(n_slots, ...)")
        bstep = _residency_step(cfg, mode, collect_info, donate, exact_impl)
        serde, n_taus = sink.serde, state.num_taus

        def plan_group(lo, hi):
            kseg, vseg = key_h[lo:hi], valid_h[lo:hi]
            # A group with more distinct keys than slots is split into
            # key-complete sub-groups that each fit; the common case is one
            # segment == the group's own mask.  Sub-groups re-dispatch the
            # same [G, B] block shapes with restricted valid masks (no new
            # jit traces) and flush as separate sink batches, so per-key
            # FIFO order and the fsync boundary are preserved.
            segs = split_oversized_group(kseg, vseg, rmap.n_slots)
            if len(segs) > 1:
                rmap.stats.splits += len(segs) - 1
            plans = []
            for j, vmask in enumerate(segs):
                vm = vmask.reshape(kseg.shape)
                # the pipelined plane plans on its prep thread with the
                # vectorized batch take (bit-identical slots, less host
                # work to hide under the device window)
                asn = rmap.assign_group(kseg, vm, batch_take=depth > 1)
                # victims leave the slot plane -> host L2 tier (no-op for
                # sinks without one).  Safe here at *plan* time, before
                # any sub-group's flush has been submitted: demote only
                # refreshes the recency of entries already in the cache —
                # row bytes enter the tier at flush/read execution time,
                # never from the demote itself (HostL2Cache.demote)
                sink.demote(asn.evicted)
                slots = asn.slot.reshape(kseg.shape)
                ev = Event(key=slots, q=q_h[lo:hi], t=t_h[lo:hi], valid=vm)
                # rng entity ids: the raw key blocks (padding lanes are 0
                # from the packer; the engine masks invalid lanes itself)
                ent = kseg

                def build(rows_fresh, rows_re, asn=asn):
                    rows = merge_miss_rows(asn.miss_fresh, rows_fresh,
                                           rows_re)
                    return pack_hydration(rows, asn.miss_slots, serde,
                                          rmap.n_slots, n_taus)

                plans.append(_GroupPlan(
                    (ev, ent), slots.reshape(-1), kseg.reshape(-1),
                    vmask.reshape(-1), asn.miss_keys[asn.miss_fresh],
                    asn.miss_keys[~asn.miss_fresh], build,
                    last=j == len(segs) - 1))
            return plans

        state, info = _drive_with_residency(
            bstep, state, key_h.shape[0], max(1, int(sink_group)),
            plan_group, rng, sink, collect_info=collect_info,
            pipeline_depth=depth)
    elif sink is not None:
        bstep = _sink_step(cfg, mode, collect_info, donate, exact_impl)

        # groups are fed straight from host memory (one h2d per dispatch);
        # the local engine's gather rows are simply the group's keys
        def group_of(lo, hi):
            ev = Event(key=key_h[lo:hi], q=q_h[lo:hi], t=t_h[lo:hi],
                       valid=valid_h[lo:hi])
            return ev, key_h[lo:hi].reshape(-1)

        state, info = _drive_with_sink(
            bstep, state, key_h.shape[0], max(1, int(sink_group)), group_of,
            rng, sink, sink_keys=key_h, valid_host=valid_h,
            collect_info=collect_info, pipeline_depth=depth)
    else:
        events = Event(key=jnp.asarray(key_h), q=jnp.asarray(q_h),
                       t=jnp.asarray(t_h), valid=jnp.asarray(valid_h))
        state, info = _block_runner(cfg, mode, collect_info, donate,
                                    exact_impl)(state, events, rng)
    if not collect_info:
        return state, info
    if n == 0:                  # degenerate but valid: nothing to trim
        F = 4 * len(cfg.taus)
        return state, StepInfo(
            z=jnp.zeros((0,), bool), p=jnp.zeros((0,), jnp.float32),
            lam_hat=jnp.zeros((0,), jnp.float32),
            features=jnp.zeros((0, F), jnp.float32),
            writes=jnp.zeros((), jnp.int32))
    flat = lambda x: jnp.reshape(x, (-1,) + x.shape[2:])[:n]
    return state, StepInfo(
        z=flat(info.z), p=flat(info.p), lam_hat=flat(info.lam_hat),
        features=flat(info.features),
        writes=jnp.sum(info.writes).astype(jnp.int32))


def _drive_with_sink(bstep, state, n_blocks, group, group_of, rng, sink, *,
                     sink_keys, valid_host, collect_info, consts=(),
                     pipeline_depth=1):
    """Host flush-group loop for the write-behind path (shared with the
    sharded engine).  The driver thread only dispatches and enqueues;
    device arrays are handed to the sink as-is and the device->host
    conversion happens on the flush thread, so storage work (and the
    copies feeding it) overlaps the next group's compute.

    ``group_of(lo, hi)``: the Event pytree for blocks [lo, hi) shaped
    [G, B] (host arrays for the local engine, device-sharded for the mesh
    path) plus the flat [G*B] state rows to gather.  ``sink_keys``:
    [n_blocks, B] host array of *global* entity ids (the local engine's
    keys are already global; the sharded engine reconstructs them from
    its layout).  At most two jit shapes exist per run: the full group
    and one trailing remainder group.
    Returns (state, StepInfo-of-stacked-blocks) shaped like the scan path.

    ``pipeline_depth >= 2`` delegates to ``_drive_pipelined_sink``: a
    prep thread stages up to that many groups' input arrays ahead of the
    dispatch loop (for the sharded engine that includes the h2d
    ``device_put``), bit-identical outputs.
    """
    if pipeline_depth > 1:
        return _drive_pipelined_sink(
            bstep, state, n_blocks, group, group_of, rng, sink,
            sink_keys=sink_keys, valid_host=valid_host,
            collect_info=collect_info, consts=consts, depth=pipeline_depth)
    outs_all = []
    for lo in range(0, n_blocks, group):
        hi = min(lo + group, n_blocks)
        with sink.overlap.host():
            ev, gidx = group_of(lo, hi)
        with span("repro.stream.dispatch", sink.stats, "dispatch_s"):
            state, outs, rows = bstep(state, ev, rng, gidx, *consts)
        # enqueue device arrays; the flush thread converts + packs + stores
        # (the bounded queue backpressures this loop when storage lags)
        z = outs.z if collect_info else outs[0]
        sink.submit(sink_keys[lo:hi].reshape(-1), z,
                    valid_host[lo:hi].reshape(-1), rows)
        outs_all.append(outs)

    return state, _stack_group_outs(outs_all, collect_info, sink.stats)


def _drive_pipelined_sink(bstep, state, n_blocks, group, group_of, rng,
                          sink, *, sink_keys, valid_host, collect_info,
                          depth, consts=()):
    """Pipelined write-behind driver: group staging overlaps dispatch.

    The prep thread builds each group's Event pytree (+ gather rows) and
    parks it on the ready queue; the dispatch thread (the caller) pops,
    dispatches and submits.  A ``depth``-token pool bounds how many
    staged input generations exist at once — the ping-pong contract in
    the module docstring: a token returns only after the jit call has
    dispatched (operands copied to device buffers), so a staged
    generation is never reclaimed while something can still read it.
    There are no hydration reads on this path, so no epoch gating is
    needed; flushes still ride the sink queue in dispatch order.
    """
    ready: queue.Queue = queue.Queue()
    tokens = threading.BoundedSemaphore(depth)
    stop = threading.Event()

    def prep():
        try:
            for lo in range(0, n_blocks, group):
                hi = min(lo + group, n_blocks)
                while not tokens.acquire(timeout=0.1):
                    if stop.is_set():
                        return
                if stop.is_set():
                    tokens.release()
                    return
                with sink.overlap.host():
                    ev, gidx = group_of(lo, hi)
                ready.put(("group", lo, hi, ev, gidx))
            ready.put(("done",))
        except BaseException as e:   # surfaced on the dispatch thread
            ready.put(("error", e))

    th = threading.Thread(target=prep, name="pipeline-prep", daemon=True)
    th.start()
    outs_all = []
    try:
        while True:
            item = ready.get()
            if item[0] == "done":
                break
            if item[0] == "error":
                raise item[1]
            _, lo, hi, ev, gidx = item
            # popping hands this generation's liveness to the local refs
            # below; releasing the token *before* the jit call is what lets
            # the prep thread stage the next group under this dispatch —
            # holding it through the call would idle prep exactly during
            # the device window (see the ping-pong contract, module
            # docstring)
            tokens.release()
            with span("repro.stream.dispatch", sink.stats, "dispatch_s"):
                state, outs, rows = bstep(state, ev, rng, gidx, *consts)
            z = outs.z if collect_info else outs[0]
            sink.submit(sink_keys[lo:hi].reshape(-1), z,
                        valid_host[lo:hi].reshape(-1), rows)
            outs_all.append(outs)
    finally:
        stop.set()
        th.join()
    return state, _stack_group_outs(outs_all, collect_info, sink.stats)


def _stack_group_outs(outs_all, collect_info, stats):
    """Stack per-group outputs back into the scan path's output shape.

    Runs on the host (span ``repro.stream.outputs``, ``stats.outputs_s``);
    ``stats.outputs_d2h_bytes`` counts every output leaf brought off the
    device, ``z`` included (the sink's wait on it made the one copy)."""
    if not outs_all:                    # empty stream: no groups ran
        if not collect_info:
            return jnp.zeros((0,), jnp.int32)
        return StepInfo(z=jnp.zeros((0, 0), bool),
                        p=jnp.zeros((0, 0), jnp.float32),
                        lam_hat=jnp.zeros((0, 0), jnp.float32),
                        features=jnp.zeros((0, 0, 0), jnp.float32),
                        writes=jnp.zeros((0,), jnp.int32))
    stats.outputs_d2h_bytes += sum(
        int(x.nbytes) for x in jax.tree.leaves(outs_all)
        if isinstance(x, jax.Array))
    with span("repro.stream.outputs", stats, "outputs_s"):
        if not collect_info:
            return jnp.asarray(np.concatenate(
                [np.asarray(o[1], np.int32) for o in outs_all]))
        outs_all = [jax.tree.map(np.asarray, o) for o in outs_all]
        cat = lambda f: jnp.asarray(np.concatenate(
            [getattr(o, f) for o in outs_all], axis=0))
        return StepInfo(z=cat("z"), p=cat("p"), lam_hat=cat("lam_hat"),
                        features=cat("features"), writes=cat("writes"))


def _drive_with_residency(bstep, state, n_blocks, group, plan_group, rng,
                          sink, *, collect_info, consts=(),
                          pipeline_depth=1):
    """Hydrate→dispatch→evict flush-group schedule for bounded residency
    (shared with the sharded engine via the ``plan_group`` callback).

    Pipeline per group g: wait on g's prefetched hydration read, scatter
    the rows and dispatch the group program, hand the group's decisions +
    post-update rows to the write-behind sink, then *plan group g+1*
    (slot assignment + eviction on the host ResidencyMap) and enqueue its
    hydration read — which rides the sink's FIFO behind g's flush, the
    ordering that guarantees a rehydrated key always reads its latest
    durable row.  Eviction itself moves no device data: durable columns
    only change on persisted events, so the store already holds every
    victim's current row (see ``streaming/residency.py``).

    ``plan_group(lo, hi)`` returns the list of ``_GroupPlan`` sub-groups
    for blocks [lo, hi) — length 1 unless the group held more distinct
    keys than slots and was split (``split_oversized_group``); the final
    sub-group carries ``last=True``.  It must be called in stream order
    (the ResidencyMap mutates).  Sub-group k+1's hydration reads are
    submitted only after sub-group k's flush, so a key flushed by one
    sub-group and rehydrated by the next still reads its latest row.

    ``pipeline_depth >= 2`` delegates to ``_drive_pipelined_residency``,
    which moves planning, reads and packing onto a prep thread and
    replaces read-behind-flush FIFO position with the sink's epoch lane
    — same ordering guarantee, proven differently (see there).
    """
    if pipeline_depth > 1:
        return _drive_pipelined_residency(
            bstep, state, n_blocks, group, plan_group, rng, sink,
            collect_info=collect_info, consts=consts, depth=pipeline_depth)

    def reads_of(plan):
        # first-touch misses skip the FIFO (nothing in flight can hold
        # them); rehydrations wait their turn behind earlier flushes
        return (sink.submit_read(plan.fresh_keys, ordered=False),
                sink.submit_read(plan.rehydrate_keys))

    if n_blocks == 0:
        return state, _stack_group_outs([], collect_info, sink.stats)
    # Drain anything a previous run left in flight: the fast lane's
    # safety argument is "this run never wrote a first-touch key", which
    # only covers writes submitted after this point.  A reused sink
    # (chunked streaming without an explicit flush between chunks) would
    # otherwise let an unordered read overtake the previous chunk's
    # queued flush of the same key.
    sink.flush()
    outs_all = []
    part_outs = []          # finished sub-groups of the current group
    with sink.overlap.host():
        pending = plan_group(0, min(group, n_blocks))
    next_lo = min(group, n_blocks)
    i = 0
    t_fresh, t_re = reads_of(pending[0])
    while True:
        plan = pending[i]
        rows_f, rows_r = t_fresh.result(), t_re.result()
        with sink.overlap.host():
            h_slots, h_scal, h_agg = plan.build_hydration(rows_f, rows_r)
        with span("repro.stream.dispatch", sink.stats, "dispatch_s"):
            state, outs, rows = bstep(state, plan.events, rng,
                                      plan.gather_idx, h_slots, h_scal,
                                      h_agg, *consts)
        z = outs.z if collect_info else outs[0]
        sink.submit(plan.sink_keys, z, plan.valid, rows)
        part_outs.append((outs, plan.valid))
        if plan.last:
            outs_all.append(_merge_subgroup_outs(part_outs, collect_info))
            part_outs = []
        i += 1
        if i == len(pending):
            if next_lo >= n_blocks:
                break
            with sink.overlap.host():
                pending = plan_group(next_lo, min(next_lo + group,
                                                  n_blocks))
            next_lo = min(next_lo + group, n_blocks)
            i = 0
        t_fresh, t_re = reads_of(pending[i])
    return state, _stack_group_outs(outs_all, collect_info, sink.stats)


def _drive_pipelined_residency(bstep, state, n_blocks, group, plan_group,
                               rng, sink, *, collect_info, depth,
                               consts=()):
    """Pipelined hydrate→dispatch→evict driver (``pipeline_depth >= 2``).

    Thread split:

    * **prep thread** — in stream order: plan the group (slot assignment
      with the vectorized batch take, splitting, demotes), submit its
      hydration reads (first-touch misses on the unordered fast lane,
      rehydrations on the epoch-gated ``staged=True`` lane), *then*
      ``stage_epoch`` the group (reads first — a group must never gate
      on its own flush).  Reads are issued for up to ``depth`` groups
      before the oldest group's tickets are waited on — the lookahead
      that keeps several batched reads in flight at the partition
      workers at once, so storage latency pipelines group-to-group
      instead of serializing.  Completion is oldest-first: wait the
      tickets, pack the hydration arrays into a fresh staging
      generation, park the staged group on the ready queue.
    * **dispatch thread** (the caller) — pop, dispatch the jit call
      (async: it returns as soon as operands are copied), release the
      staging token, and ``submit(..., seq=epoch)`` so the epoch marker
      trails the group's puts on every partition.

    Ordering under overlap, re-proven:

    * *per-key FIFO* — groups are planned, staged, dispatched and
      submitted in stream order by construction (one prep thread, one
      FIFO ready queue, one dispatch thread), and within a group the
      engine scan preserves lane order; splits are key-complete.
    * *evict→rehydrate reads the latest durable row* — a rehydration
      read of key k carries ``need = max staged epoch over its keys``;
      the store worker parks it until its partition has applied that
      epoch, i.e. until every flush staged before the read has executed
      its puts there.  That is exactly the guarantee dispatcher-FIFO
      position gave the serial driver, without the read ever queueing
      behind unrelated flush conversion work.
    * *deadlock-freedom* — a parked read's need names an epoch that was
      staged before the read was submitted, hence a group at or before
      the one the dispatch thread is currently draining the ready queue
      toward; the dispatch thread never waits on read tickets, so every
      staged epoch's flush is eventually submitted and every parked
      read drains.  The prep thread's token wait polls ``stop`` so an
      erroring dispatch thread can always shut the pipeline down.
    * *fsync group boundary* — unchanged: each sub-group still flushes
      as one atomic sink batch; the epoch marker is bookkeeping behind
      it, not part of the WAL record.

    Requires a threaded sink with pure backpressure: the serial sink
    executes reads inline on the submitting thread and the degrade
    overflow policy flushes inline on the dispatch thread — both would
    break the one-thread-per-store invariant once a prep thread exists.
    """
    if getattr(sink, "_serial", False):
        raise ValueError(
            "pipeline_depth > 1 requires a threaded sink "
            "(WriteBehindSink queue_depth >= 1): the serial sink "
            "executes reads inline on the submitting thread")
    if getattr(sink, "_overflow", "block") != "block":
        raise ValueError(
            "pipeline_depth > 1 requires overflow='block': a degraded "
            "inline flush on the dispatch thread would race the prep "
            "thread's reads on the partition stores")
    if n_blocks == 0:
        return state, _stack_group_outs([], collect_info, sink.stats)
    sink.flush()   # same fast-lane safety barrier as the serial driver
    ready: queue.Queue = queue.Queue()
    tokens = threading.BoundedSemaphore(depth)
    stop = threading.Event()

    def prep():
        # Issued-but-unpacked groups, oldest first.  Issuing reads for up
        # to ``depth`` groups before waiting the oldest ticket is what
        # pipelines storage latency: the partition workers hold several
        # batched reads back-to-back instead of idling between groups.
        inflight: list = []

        def complete_oldest():
            plan, t_fresh, t_re, seq = inflight.pop(0)
            rows_f, rows_r = t_fresh.result(), t_re.result()
            with sink.overlap.host():
                h = plan.build_hydration(rows_f, rows_r)
            ready.put(("group", plan, h, seq))

        try:
            for lo in range(0, n_blocks, group):
                hi = min(lo + group, n_blocks)
                with sink.overlap.host():
                    plans = plan_group(lo, hi)
                for plan in plans:
                    while not tokens.acquire(timeout=0.1):
                        if stop.is_set():
                            return
                    if stop.is_set():
                        tokens.release()
                        return
                    # reads before stage_epoch: the group's own misses
                    # must not wait on the group's own (future) flush
                    t_fresh = sink.submit_read(plan.fresh_keys,
                                               ordered=False)
                    t_re = sink.submit_read(plan.rehydrate_keys,
                                            staged=True)
                    seq = sink.stage_epoch(plan.sink_keys, plan.valid)
                    inflight.append((plan, t_fresh, t_re, seq))
                    # Drain before the token pool can block: when the
                    # acquire above parks, everything issued is either in
                    # the ready queue or in flight here with
                    # len(inflight) < depth — so the ready queue is
                    # non-empty and the dispatch thread's next pop frees
                    # a token (no prep<->dispatch deadlock).
                    if len(inflight) >= depth:
                        complete_oldest()
            while inflight:
                complete_oldest()
            ready.put(("done",))
        except BaseException as e:   # surfaced on the dispatch thread
            ready.put(("error", e))

    th = threading.Thread(target=prep, name="pipeline-prep", daemon=True)
    th.start()
    outs_all = []
    part_outs = []
    try:
        while True:
            item = ready.get()
            if item[0] == "done":
                break
            if item[0] == "error":
                raise item[1]
            _, plan, (h_slots, h_scal, h_agg), seq = item
            # release before dispatch (not after): this generation's
            # liveness is carried by the local refs the jit call reads,
            # and freeing the slot now is what lets prep plan/read/pack
            # the next group *under* this group's device window instead
            # of after it (ping-pong contract, module docstring)
            tokens.release()
            with span("repro.stream.dispatch", sink.stats, "dispatch_s"):
                state, outs, rows = bstep(state, plan.events, rng,
                                          plan.gather_idx, h_slots, h_scal,
                                          h_agg, *consts)
            z = outs.z if collect_info else outs[0]
            sink.submit(plan.sink_keys, z, plan.valid, rows, seq=seq)
            part_outs.append((outs, plan.valid))
            if plan.last:
                outs_all.append(_merge_subgroup_outs(part_outs,
                                                     collect_info))
                part_outs = []
    finally:
        stop.set()
        if th.is_alive():
            # abnormal exit with the prep thread possibly parked on a
            # staged read whose epoch's flush will now never be
            # submitted: advance every partition past all staged epochs
            # so the ticket resolves (the run is erroring out — the rows
            # it returns are never used) and the thread can observe
            # ``stop`` and exit
            try:
                for sq in sink._store_qs:
                    sq.put(("epoch", sink._staged_seq))
            except BaseException:   # pragma: no cover - best effort
                pass
            th.join()
        else:
            th.join()
    return state, _stack_group_outs(outs_all, collect_info, sink.stats)


def _merge_subgroup_outs(parts, collect_info):
    """Merge a split group's sub-group outputs back into one per-group
    output.  Every real event lane is valid in exactly one sub-group (the
    split partitions the valid mask), so each sub-group is authoritative
    for its own lanes — later sub-groups overwrite lanes they own — and
    per-block write counts sum.  The unsplit common case passes the single
    sub-group's device output through untouched.
    """
    if len(parts) == 1:
        return parts[0][0]
    if not collect_info:
        z = np.asarray(parts[0][0][0]).copy()
        w = np.asarray(parts[0][0][1], np.int32)
        for outs, vmask in parts[1:]:
            m = np.asarray(vmask, bool).reshape(z.shape)
            z[m] = np.asarray(outs[0])[m]
            w = w + np.asarray(outs[1], np.int32)
        return (jnp.asarray(z), jnp.asarray(w))
    o0 = jax.tree.map(np.asarray, parts[0][0])
    z, p = o0.z.copy(), o0.p.copy()
    lam, feat = o0.lam_hat.copy(), o0.features.copy()
    w = o0.writes
    for outs, vmask in parts[1:]:
        o = jax.tree.map(np.asarray, outs)
        m = np.asarray(vmask, bool).reshape(z.shape)
        z[m] = o.z[m]
        p[m] = o.p[m]
        lam[m] = o.lam_hat[m]
        feat[m] = o.features[m]
        w = w + o.writes
    return StepInfo(z=jnp.asarray(z), p=jnp.asarray(p),
                    lam_hat=jnp.asarray(lam), features=jnp.asarray(feat),
                    writes=jnp.asarray(w))
