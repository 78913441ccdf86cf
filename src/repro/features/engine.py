"""Entity-partitioned (sharded) feature engine.

The paper's partitioned workers (§5.3) map to SPMD shards: each shard of
the ``data`` mesh axes owns a subset of entities and runs the vectorized
core engine over its own event partition inside a
``jax.shard_map`` — deterministic key routing, per-key ordering
within a shard, no cross-shard collectives on the decision or update path
(the paper's no-coordination design goal, realized in mesh form).  Every
shard routes its decision + read-modify-write through the same fused
``kernels.ops.thinning_rmw`` pass as the local engine (this module holds no
decision math of its own — it only routes events and composes the core
step).

Layouts (``layout=`` constructor option, names in ``LAYOUTS``):

* ``layout="block"`` (default) — shard ``s`` owns entities with
  ``key % n_shards == s`` at local row ``key // n_shards``.  Zero routing
  state, but under heavy key skew the hottest shard sets the stream's block
  count and every other shard pads up to it.
* ``layout="virtual"`` — keys map onto ``V >> n_shards`` virtual shards
  placed with volume-weighted power-of-two-choices
  (``distributed.rebalance``), cutting the padded-block waste on skewed
  streams; an inverse gather at ``materialize`` keeps user-visible entity
  ids unchanged.  See the ``rebalance`` module docstring for the full
  layout contract.

Determinism: the shard body feeds each event's *global* entity id to the
core step's ``rng_entity`` hook — reconstructed arithmetically
(``local_row * n_shards + shard``) under the block layout, gathered from
the layout's ``gid_of_row`` table under the virtual layout — so the
counter-based thinning RNG sees exactly the counters an unsharded engine
would: decisions are bit-identical to ``core.engine`` on the same stream,
for any mesh shape, any layout, and across elastic resharding (the counter
depends only on the global id).

Streaming: ``run_stream`` is the donated-buffer block driver for the
sharded path — the host routes the flat stream into ``[n_blocks,
n_shards * B]`` event blocks (each block row lands shard-aligned on the
mesh) and one jitted dispatch scans all blocks with the mesh-sharded state
as donated carry.  The ``core.stream`` donation contract applies: state
leaves must each own their storage, and the input state is dead after the
call.  Layout tables ride along as non-donated trailing consts (see
``core.stream.block_runner_for``).

Bounded residency: ``run_stream(residency=S)`` swaps the dense per-shard
entity rows for ``S`` resident *slots* per shard (``init_resident_state``)
under either layout — each shard's host-side ``ResidencyMap`` assigns
slots per flush group, misses hydrate from the sink's layout-aligned
partition stores and victims recycle clock/second-chance.  Global entity
ids then ride the scan as data (no ``gid_of_row`` table needed), so the
RNG-identity guarantee above holds for any slot budget.

Without a mesh the engine degrades to a single local shard (CPU tests).
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import EngineConfig, Event, ProfileState, StepInfo
from repro.core import engine as core_engine
from repro.core import stream as core_stream
from repro.core.types import init_state
from repro.distributed import rebalance
from repro.distributed.sharding import axis_sizes
from repro.streaming import persistence

# The sharded layouts this engine supports; README.md documents the
# contract of each and scripts/check_docs.py lints the two lists against
# each other.
LAYOUTS = ("block", "virtual")


def stream_block_counts(shard: np.ndarray, n_shards: int,
                        batch_per_shard: int) -> Tuple[np.ndarray, int]:
    """(per-shard event counts, n_blocks) for a routed stream — the single
    definition of the packer's block-count rule (n_blocks follows the most
    loaded shard), shared by ``route_stream_blocks`` and the
    ``stream_layout_stats`` accounting so they can never diverge."""
    counts = np.bincount(shard, minlength=n_shards)
    n_blocks = max(1, -(-int(counts.max()) // int(batch_per_shard))) \
        if shard.size else 1
    return counts, n_blocks


def route_stream_blocks(shard: np.ndarray, local: np.ndarray, q: np.ndarray,
                        t: np.ndarray, n_shards: int, batch_per_shard: int
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                   np.ndarray, np.ndarray, int]:
    """Pack routed events into flat ``[n_blocks * n_shards * B]`` blocks.

    Pure host-side layout step shared by every layout: shard ``s`` owns
    block columns ``[s*B, (s+1)*B)`` and its events are packed in stream
    order across however many blocks its load requires, so per-key ordering
    is preserved (a key's events all carry the same ``(shard, local)``).
    Every event is retained exactly once — no drops, no duplicates — and
    skew shows up purely as padding: ``n_blocks`` follows the most loaded
    shard.

    Returns ``(key, q, t, valid, slot, n_blocks)`` where the first four are
    flat arrays of length ``n_blocks * n_shards * B`` (``key`` holds local
    rows) and ``slot`` is each input event's flat block-major slot, for
    mapping per-event outputs back to stream order.
    """
    shard = np.asarray(shard)
    n, B = int(n_shards), int(batch_per_shard)
    counts, n_blocks = stream_block_counts(shard, n, B)
    W = n * B
    out_key = np.zeros(n_blocks * W, np.int32)
    out_q = np.zeros(n_blocks * W, np.float32)
    out_t = np.zeros(n_blocks * W, np.float32)
    out_valid = np.zeros(n_blocks * W, bool)
    # rank of each event within its shard, in stream order
    order = np.argsort(shard, kind="stable")
    starts = np.cumsum(counts) - counts
    rank = np.empty(shard.size, np.int64)
    rank[order] = np.arange(shard.size) - starts[shard[order]]
    slot = (rank // B) * W + shard * B + rank % B
    out_key[slot] = local
    out_q[slot] = q
    out_t[slot] = t
    out_valid[slot] = True
    return out_key, out_q, out_t, out_valid, slot, n_blocks


class ShardedFeatureEngine:
    """Vectorized persistence-path control over mesh-partitioned entities."""

    def __init__(self, cfg: EngineConfig, num_entities: int,
                 mesh: Optional[Mesh] = None, data_axes: Tuple[str, ...] =
                 ("data",), mode: str = "fast", layout: str = "block",
                 key_weights: Optional[np.ndarray] = None,
                 n_virtual: Optional[int] = None, seed: int = 0):
        if layout not in LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; choose from "
                             f"{LAYOUTS}")
        self.cfg = cfg
        self.mesh = mesh
        self.data_axes = data_axes
        self.mode = mode
        self.layout = layout
        self.axis_sizes = axis_sizes(mesh, data_axes) if mesh is not None \
            else (1,)
        self.n_shards = int(np.prod(self.axis_sizes))
        if layout == "virtual":
            # Frozen skew-aware layout: key -> (shard, row) via weighted
            # power-of-two-choices over virtual shards; see
            # distributed/rebalance.py for the contract.
            self.vlayout = rebalance.build_layout(
                num_entities, self.n_shards, key_weights=key_weights,
                n_virtual=n_virtual, seed=seed)
            self.entities_per_shard = self.vlayout.entities_per_shard
            self.num_entities = self.vlayout.num_rows
            gid = jnp.asarray(self.vlayout.gid_of_row)
            row_of_key = jnp.asarray(self.vlayout.row_of_key)
            if mesh is not None:
                gid = jax.device_put(
                    gid, NamedSharding(mesh, P(data_axes)))
            self._row_of_key = row_of_key
            self._step_consts = (gid,)
        else:
            self.vlayout = None
            # round entities up so every shard owns the same row count
            self.entities_per_shard = -(-num_entities // self.n_shards)
            self.num_entities = self.entities_per_shard * self.n_shards
            self._row_of_key = None
            self._step_consts = ()
        self._local_step = core_engine.make_step(cfg, mode)
        self._step_raw = None  # (state, ev, rng, *consts); cached
        self._step = None      # public (state, ev, rng) wrapper
        self._step_res = None  # residency step: (state, (ev, ent), rng)
        self._runners = {}  # (collect_info, donate) -> compiled block driver

    # ------------------------------------------------------------ state
    def init_state(self) -> ProfileState:
        state = init_state(self.num_entities, len(self.cfg.taus))
        if self.mesh is None:
            return state
        spec = jax.tree.map(lambda _: P(self.data_axes), state)
        return jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec))

    def init_resident_state(self, slots_per_shard: int) -> ProfileState:
        """Bounded device state: ``slots_per_shard`` resident slots per
        shard instead of one row per owned entity — the state plane for
        ``run_stream(residency=...)``.  Device memory then scales with the
        residency budget, not with ``num_entities``."""
        state = init_state(self.n_shards * int(slots_per_shard),
                           len(self.cfg.taus))
        if self.mesh is None:
            return state
        spec = jax.tree.map(lambda _: P(self.data_axes), state)
        return jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec))

    # ------------------------------------------------ host-side routing
    def route(self, key: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(shard, local row) of each key under the active layout."""
        key = np.asarray(key)
        if self.layout == "virtual":
            return (self.vlayout.shard_of_key[key],
                    self.vlayout.local_of_key[key])
        return key % self.n_shards, key // self.n_shards

    def partition_events(self, key: np.ndarray, q: np.ndarray,
                         t: np.ndarray, batch_per_shard: int) -> Event:
        """Route a host batch to shards under the active layout.  Returns a
        *global* Event whose flat layout is [shard0 rows..., shard1 rows...]
        so a plain ('data',)-sharded batch dimension lands each event on its
        owner."""
        n = self.n_shards
        shard, local = self.route(key)
        B = batch_per_shard
        out_key = np.zeros(n * B, np.int32)
        out_q = np.zeros(n * B, np.float32)
        out_t = np.zeros(n * B, np.float32)
        out_valid = np.zeros(n * B, bool)
        for s in range(n):
            sel = np.nonzero(shard == s)[0][:B]
            m = len(sel)
            sl = slice(s * B, s * B + m)
            out_key[sl] = local[sel]
            out_q[sl] = q[sel]
            out_t[sl] = t[sel]
            out_valid[sl] = True
            # unrouted overflow events are dropped from this micro-batch;
            # production would re-queue them (run_stream does not drop)
        return Event(key=jnp.asarray(out_key), q=jnp.asarray(out_q),
                     t=jnp.asarray(out_t), valid=jnp.asarray(out_valid))

    def partition_stream(self, key, q, t, batch_per_shard: int
                         ) -> Tuple[Event, np.ndarray]:
        """Route a flat host stream into ``[n_blocks, n_shards * B]`` blocks.

        Unlike ``partition_events`` (fixed micro-batch, drops per-batch
        overflow) every event is retained exactly once, packed by
        ``route_stream_blocks`` under the active layout's ``route`` map.
        Skew shows up as padding — n_blocks follows the most loaded shard —
        which is precisely what ``layout="virtual"`` rebalances away (see
        ``stream_layout_stats`` for the accounting).

        Returns (events, slot) where ``slot`` is the flat block-major slot
        of every input event, for mapping per-event outputs back to stream
        order.

        Donation / aliasing: the returned blocks are freshly allocated and
        the gathered-materialization side tables (``gid_of_row`` /
        ``row_of_key``) live outside the event pytree, so feeding the
        result straight into the donating ``run_stream`` driver never
        aliases a donated ``ProfileState`` leaf; only the *state* is dead
        after that call, never the blocks or the layout tables.
        """
        key = np.asarray(key, np.int32)
        q = np.asarray(q, np.float32)
        t = np.asarray(t, np.float32)
        n, B = self.n_shards, int(batch_per_shard)
        shard, local = self.route(key)
        out_key, out_q, out_t, out_valid, slot, n_blocks = \
            route_stream_blocks(shard, local, q, t, n, B)
        W = n * B
        blocks = lambda x: jnp.asarray(x.reshape(n_blocks, W))
        ev = Event(key=blocks(out_key), q=blocks(out_q), t=blocks(out_t),
                   valid=blocks(out_valid))
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(None, self.data_axes))
            ev = Event(*(jax.device_put(x, sh) for x in ev))
        return ev, slot

    def stream_layout_stats(self, key, batch_per_shard: int) -> dict:
        """Host-side padding accounting for a stream under the active layout.

        ``padded_fraction`` is the share of block slots that carry no real
        event — the dispatch work wasted to shard-load imbalance (plus the
        final partial block).  ``bench_engine --suite skew`` records this
        per layout.
        """
        shard, _ = self.route(np.asarray(key, np.int64))
        B = int(batch_per_shard)
        counts, n_blocks = stream_block_counts(shard, self.n_shards, B)
        slots = n_blocks * self.n_shards * B
        return {"n_blocks": n_blocks, "slots": slots,
                "events": int(shard.size),
                "padded_fraction": float(1.0 - shard.size / slots),
                "max_shard_events": int(counts.max()) if shard.size else 0,
                "mean_shard_events": float(counts.mean())}

    # ------------------------------------------------------------- step
    def make_step(self):
        """jit-able (state, Event, rng) -> (state, StepInfo), memoized.

        Under a mesh: ``shard_map`` over the data axes — each shard applies
        the local (fused-kernel) engine step to its own [B_local] slice
        against its own [E_local] state rows.  No collectives are emitted on
        the decision or update path (only the scalar write counter is summed
        for metrics).

        Thinning RNG: the shard reconstructs global entity ids — block
        layout arithmetically, virtual layout via the ``gid_of_row`` table —
        and passes them as the core step's ``rng_entity``, so decisions
        match the unsharded engine bit-for-bit and never collide across
        shards.  Layout tables are bound as closure constants here; the
        streaming driver passes them as explicit non-donated operands
        instead (``run_stream``).
        """
        if self._step is None:
            raw = self._raw_step()
            consts = self._step_consts
            if consts:
                self._step = lambda st, ev, rng: raw(st, ev, rng, *consts)
            else:
                self._step = raw
        return self._step

    def _raw_step(self):
        """The layout-aware step taking consts explicitly, memoized."""
        if self._step_raw is None:
            self._step_raw = self._build_step()
        return self._step_raw

    def _residency_step(self):
        """Layout-agnostic step for the slot-based resident set, memoized.

        Events scan as ``(Event, rng_entity)`` pairs: ``Event.key`` holds
        per-shard *slot* indices (assigned per flush group by the host
        ResidencyMaps) and ``rng_entity`` carries the global entity ids as
        data — both layouts collapse onto one step, because the id no
        longer needs to be reconstructed from a row index (the ``gid``
        table / arithmetic keying exist only for dense row layouts).
        Thinning therefore stays bit-identical to the local engine for any
        residency budget, any mesh and any layout.
        """
        if self._step_res is not None:
            return self._step_res
        local_step = self._local_step
        if self.mesh is None:
            def local0(st, ev_ent, r):
                ev, ent = ev_ent
                return local_step(st, ev, r, rng_entity=ent)
            self._step_res = local0
            return self._step_res
        axes = self.data_axes

        def local(st, ev_ent, r):
            ev, ent = ev_ent
            st2, info = local_step(st, ev, r, rng_entity=ent)
            return st2, info._replace(writes=info.writes[None])

        def sharded(state, ev_ent, rng):
            ev, ent = ev_ent
            st2, info = jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), state),
                          (jax.tree.map(lambda _: P(axes), ev), P(axes)),
                          P()),
                out_specs=(jax.tree.map(lambda _: P(axes), state),
                           StepInfo(z=P(axes), p=P(axes), lam_hat=P(axes),
                                    features=P(axes), writes=P(axes))),
                check_vma=False,
            )(state, (ev, ent), rng)
            return st2, info._replace(writes=info.writes.sum())

        self._step_res = sharded
        return self._step_res

    def _stream_order(self, info: StepInfo, slot: np.ndarray) -> StepInfo:
        """Per-block ``[n_blocks, W]`` outputs back to flat stream order
        (``slot``: each event's flat block-major slot)."""
        if self.mesh is not None:
            # the outputs are sharded over the block columns; replicate
            # them so the flat reshape and the slot gather stay local
            info = jax.device_put(info, NamedSharding(self.mesh, P()))
        flat = lambda x: jnp.reshape(x, (-1,) + x.shape[2:])[slot]
        return StepInfo(
            z=flat(info.z), p=flat(info.p), lam_hat=flat(info.lam_hat),
            features=flat(info.features),
            writes=jnp.sum(info.writes).astype(jnp.int32))

    def _shard_gather(self):
        """Row gather for the sink drivers: each shard gathers its block
        columns' rows from its own state (``gather_idx`` holds per-shard
        local rows, ``[G, W]``), so the gather needs no collective.
        ``None`` selects the core gather when there is no mesh (local
        rows are then the flat rows)."""
        if self.mesh is None:
            return None
        axes = self.data_axes

        def local(st, idx):
            scal, agg = core_stream.gather_rows(st, idx)
            G = idx.shape[0]
            return (scal.reshape(4, G, -1),
                    agg.reshape((G, -1) + agg.shape[1:]))

        def gather(state, idx):
            return jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), state),
                          P(None, axes)),
                out_specs=(P(None, None, axes), P(None, axes)),
                check_vma=False,
            )(state, idx)

        return gather

    def _residency_scatter(self):
        """Hydration scatter for ``residency_step_for``: per shard, local
        slot indices into the shard's own state rows (``None`` selects the
        core single-domain scatter when there is no mesh)."""
        if self.mesh is None:
            return None
        axes = self.data_axes

        def scat(state, slots, scal, agg):
            return jax.shard_map(
                core_stream.hydrate_scatter,
                mesh=self.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), state),
                          P(axes), P(None, axes), P(axes)),
                out_specs=jax.tree.map(lambda _: P(axes), state),
                check_vma=False,
            )(state, slots, scal, agg)

        return scat

    def _build_step(self):
        local_step = self._local_step
        if self.mesh is None:
            if self.layout == "virtual":
                def local1(st, e, r, gid):
                    # single local shard: rows are permuted, ids via gid
                    return local_step(st, e, r, rng_entity=gid[e.key])
                return local1
            def local0(st, e, r):
                return local_step(st, e, r)
            return local0

        axes, sizes, n = self.data_axes, self.axis_sizes, self.n_shards
        virtual = self.layout == "virtual"

        def local(st, e, r, *consts):
            if virtual:
                (gid,) = consts
                ent = gid[e.key]
            else:
                idx = jnp.zeros((), jnp.int32)
                for a, sz in zip(axes, sizes):
                    idx = idx * sz + jax.lax.axis_index(a)
                # local row l of shard s is global entity l * n + s
                ent = e.key * n + idx
            st2, info = local_step(st, e, r, rng_entity=ent)
            return st2, info._replace(writes=info.writes[None])

        const_specs = (P(axes),) if virtual else ()

        def sharded(state, ev, rng, *consts):
            st2, info = jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), state),
                          jax.tree.map(lambda _: P(axes), ev),
                          P()) + const_specs,
                out_specs=(jax.tree.map(lambda _: P(axes), state),
                           StepInfo(z=P(axes), p=P(axes), lam_hat=P(axes),
                                    features=P(axes), writes=P(axes))),
                check_vma=False,
            )(state, ev, rng, *consts)
            return st2, info._replace(writes=info.writes.sum())

        return sharded

    # ----------------------------------------------------------- stream
    def run_stream(self, state: ProfileState, keys, qs, ts, *,
                   batch_per_shard: int = 1024,
                   rng: Optional[jax.Array] = None,
                   collect_info: bool = True, donate: bool = True,
                   sink: Optional["persistence.WriteBehindSink"] = None,
                   sink_group: int = 4, residency=None,
                   pipeline_depth: int = 1
                   ) -> Tuple[ProfileState, Union[StepInfo, jax.Array]]:
        """Drive the sharded engine over a flat stream in one dispatch.

        The stream is routed shard-aligned on the host
        (``partition_stream``), then all blocks are scanned through the
        sharded step inside a single jitted, state-donating program — one
        dispatch per mesh for the whole stream, zero state copies between
        blocks (see the ``core.stream`` donation contract; ``state`` is dead
        after the call when ``donate=True``; layout tables ride as
        non-donated trailing consts and stay live).

        ``sink``: optional write-behind persistence sink (``make_sink``).
        The stream is then driven in flush groups of ``sink_group``
        blocks (one dispatch per group — the group-commit knob) and each
        group's thinned rows are flushed to the sink's per-partition
        stores — partitions aligned with this engine's layout routing —
        while the next group computes.  Caller flushes.

        ``residency``: per-shard slot budget (int) or a list of prebuilt
        per-shard ``streaming.residency.ResidencyMap``s, one per shard.
        The state then holds ``n_shards * S`` slots
        (``init_resident_state``) and both layouts run the same
        slot-based schedule: keys route to their owning shard as usual,
        each shard's ResidencyMap assigns local slots per flush group,
        misses hydrate from the sink's layout-aligned partition stores
        and victims recycle clock/second-chance.  Requires ``sink``.

        ``pipeline_depth``: same knob as ``core.stream.run_stream`` — 1
        is the serial flush-group loop; >= 2 runs the pipelined plane on
        both layouts (the prep thread then also owns the per-group h2d
        ``device_put`` staging and the sharded slot assignment's
        vectorized batch take), bit-identical outputs.

        Returns the final state plus either a StepInfo in *stream order*
        (``collect_info=True``) or per-block write counts.
        """
        if rng is None:
            rng = jax.random.PRNGKey(0)
        depth = int(pipeline_depth)
        if depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if depth > 1 and sink is None:
            raise ValueError(
                "pipeline_depth > 1 requires a sink: the pipelined plane "
                "overlaps host group prep with device compute across "
                "flush groups, which the single-dispatch path does not "
                "have")
        if residency is not None:
            return self._run_stream_residency(
                state, keys, qs, ts, batch_per_shard, rng, collect_info,
                donate, sink, sink_group, residency, depth)
        if sink is not None:
            return self._run_stream_sink(state, keys, qs, ts,
                                         batch_per_shard, rng, collect_info,
                                         donate, sink, sink_group, depth)
        events, slot = self.partition_stream(keys, qs, ts, batch_per_shard)
        key = (collect_info, donate)
        if key not in self._runners:
            self._runners[key] = core_stream.block_runner_for(
                self._raw_step(), collect_info, donate)
        state, info = self._runners[key](state, events, rng,
                                         *self._step_consts)
        if not collect_info:
            return state, info
        return state, self._stream_order(info, slot)

    def _run_stream_sink(self, state, keys, qs, ts, batch_per_shard, rng,
                         collect_info, donate, sink, sink_group,
                         pipeline_depth=1):
        """Write-behind block loop for the sharded path.

        Reuses ``core.stream._drive_with_sink``; the per-lane gather index
        is the lane's row within its own shard (``_shard_gather``), and the
        sink keys
        are *global* entity ids (arithmetic under the block layout, via the
        ``gid_of_row`` table under the virtual layout) so stored rows are
        keyed exactly like the per-event worker's.
        """
        key = np.asarray(keys, np.int32)
        q = np.asarray(qs, np.float32)
        t = np.asarray(ts, np.float32)
        n, B = self.n_shards, int(batch_per_shard)
        shard, local = self.route(key)
        out_key, out_q, out_t, out_valid, slot, n_blocks = \
            route_stream_blocks(shard, local, q, t, n, B)
        W = n * B
        shard_of_col = np.repeat(np.arange(n, dtype=np.int64), B)
        if self.layout == "virtual":
            flat_host = shard_of_col[None, :] * self.entities_per_shard \
                + out_key.reshape(n_blocks, W)
            gid_host = np.asarray(self.vlayout.gid_of_row)[flat_host]
        else:
            gid_host = out_key.reshape(n_blocks, W).astype(np.int64) * n \
                + shard_of_col[None, :]
        kb = out_key.reshape(n_blocks, W)
        qb = out_q.reshape(n_blocks, W)
        tb = out_t.reshape(n_blocks, W)
        vb = out_valid.reshape(n_blocks, W)
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(None, self.data_axes))
            put = lambda x: jax.device_put(jnp.asarray(x), sh)
        else:
            put = lambda x: x

        def group_of(lo, hi):
            ev = Event(key=put(kb[lo:hi]), q=put(qb[lo:hi]),
                       t=put(tb[lo:hi]), valid=put(vb[lo:hi]))
            return ev, kb[lo:hi]

        rkey = ("sink", collect_info, donate)
        if rkey not in self._runners:
            self._runners[rkey] = core_stream.sink_step_for(
                self._raw_step(), collect_info, donate,
                gather=self._shard_gather())
        state, info = core_stream._drive_with_sink(
            self._runners[rkey], state, n_blocks, max(1, int(sink_group)),
            group_of, rng, sink, sink_keys=gid_host, valid_host=vb,
            collect_info=collect_info, consts=self._step_consts,
            pipeline_depth=pipeline_depth)
        if not collect_info:
            return state, info
        return state, self._stream_order(info, slot)

    def _run_stream_residency(self, state, keys, qs, ts, batch_per_shard,
                              rng, collect_info, donate, sink, sink_group,
                              residency, pipeline_depth=1):
        """Slot-based resident-set loop for the sharded path.

        Reuses ``core.stream._drive_with_residency``; events are packed
        shard-aligned with *global* ids (slots cannot be assigned ahead of
        the flush-group schedule), each group translates its shard columns
        through that shard's ResidencyMap, and hydration reads route to
        the layout-aligned partition stores through the sink's ordered
        FIFO.  Per-shard miss lists are padded to one common power-of-two
        width so the ``shard_map`` scatter sees a uniform [n_shards * H]
        layout.
        """
        from repro.streaming.residency import (ResidencyMap,
                                               split_oversized_group)
        if sink is None:
            raise ValueError(
                "residency requires a write-behind sink: evicted slots "
                "rely on the durable store for rehydration")
        key = np.asarray(keys, np.int32)
        q = np.asarray(qs, np.float32)
        t = np.asarray(ts, np.float32)
        n, B = self.n_shards, int(batch_per_shard)
        if isinstance(residency, (int, np.integer)):
            rmaps = [ResidencyMap(self.num_entities, int(residency))
                     for _ in range(n)]
        else:
            rmaps = list(residency)
        if len(rmaps) != n:
            raise ValueError(f"need one ResidencyMap per shard "
                             f"({n}), got {len(rmaps)}")
        S = rmaps[0].n_slots
        if any(m.n_slots != S for m in rmaps):
            raise ValueError("per-shard slot budgets must be uniform")
        if state.num_entities != n * S:
            raise ValueError(
                f"state holds {state.num_entities} rows but the resident "
                f"set needs {n} shards x {S} slots; build it with "
                f"init_resident_state({S})")
        shard, _ = self.route(key)
        # pack *global* ids into the blocks: local slots are a per-group
        # decision, made by the ResidencyMaps inside plan_group below
        out_key, out_q, out_t, out_valid, slot_map, n_blocks = \
            route_stream_blocks(shard, key, q, t, n, B)
        W = n * B
        kb = out_key.reshape(n_blocks, W)
        qb = out_q.reshape(n_blocks, W)
        tb = out_t.reshape(n_blocks, W)
        vb = out_valid.reshape(n_blocks, W)
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(None, self.data_axes))
            put = lambda x: jax.device_put(jnp.asarray(x), sh)
        else:
            put = lambda x: x
        serde = sink.serde
        n_taus = len(self.cfg.taus)

        def plan_group(lo, hi):
            G = hi - lo
            kseg, vseg = kb[lo:hi], vb[lo:hi]
            # Per-shard oversized-group splitting: each shard's columns
            # split independently against its own slot budget, and
            # sub-group j dispatches the union of every shard's j-th
            # segment (shards that split less run empty-masked sub-groups
            # — a zero-miss assign_group is free).  Scan order per shard
            # is preserved, so per-key FIFO order is too.
            shard_segs = []
            for s in range(n):
                cols = slice(s * B, (s + 1) * B)
                segs = split_oversized_group(
                    kseg[:, cols], vseg[:, cols], S)
                if len(segs) > 1:
                    rmaps[s].stats.splits += len(segs) - 1
                shard_segs.append(segs)
            n_sub = max(len(segs) for segs in shard_segs)
            plans = []
            for j in range(n_sub):
                vm = np.zeros((G, W), bool)
                for s in range(n):
                    if j < len(shard_segs[s]):
                        cols = slice(s * B, (s + 1) * B)
                        vm[:, cols] = shard_segs[s][j].reshape(G, B)
                slots = np.zeros((G, W), np.int32)
                miss = []
                for s in range(n):
                    cols = slice(s * B, (s + 1) * B)
                    # pipelined plane: vectorized batch take on the prep
                    # thread (bit-identical slots — see residency.py)
                    asn = rmaps[s].assign_group(kseg[:, cols],
                                                vm[:, cols],
                                                batch_take=pipeline_depth
                                                > 1)
                    # plan-time demote: a recency refresh only, safe
                    # before any sub-group's flush (see core.stream)
                    sink.demote(asn.evicted)
                    slots[:, cols] = asn.slot.reshape(G, B)
                    miss.append(asn)
                mmax = max(a.miss_keys.size for a in miss)
                H = core_stream.hydration_width(mmax)
                fresh_keys = np.concatenate(
                    [a.miss_keys[a.miss_fresh] for a in miss])
                re_keys = np.concatenate(
                    [a.miss_keys[~a.miss_fresh] for a in miss])
                ev = Event(key=put(slots), q=put(qb[lo:hi]),
                           t=put(tb[lo:hi]), valid=put(vm))
                # rng entity ids: the raw key blocks (padding lanes are 0
                # from the packer; the engine masks invalid lanes itself)
                ent = put(kseg)

                def build(rows_fresh, rows_re, miss=miss, H=H):
                    # shared iterators: merge_miss_rows consumes each
                    # shard's slice of the two read lanes in per-shard
                    # miss order
                    it_f, it_r = iter(rows_fresh), iter(rows_re)
                    segs = [core_stream.pack_hydration(
                                core_stream.merge_miss_rows(
                                    a.miss_fresh, it_f, it_r),
                                a.miss_slots, serde, S, n_taus, width=H)
                            for a in miss]
                    return (np.concatenate([g[0] for g in segs]),
                            np.concatenate([g[1] for g in segs], axis=1),
                            np.concatenate([g[2] for g in segs], axis=0))

                plans.append(core_stream._GroupPlan(
                    (ev, ent), slots, kseg.reshape(-1),
                    vm.reshape(-1), fresh_keys, re_keys, build,
                    last=j == n_sub - 1))
            return plans

        rkey = ("residency", collect_info, donate)
        if rkey not in self._runners:
            self._runners[rkey] = core_stream.residency_step_for(
                self._residency_step(), collect_info, donate,
                scatter=self._residency_scatter(),
                gather=self._shard_gather())
        state, info = core_stream._drive_with_residency(
            self._runners[rkey], state, n_blocks, max(1, int(sink_group)),
            plan_group, rng, sink, collect_info=collect_info,
            pipeline_depth=pipeline_depth)
        if not collect_info:
            return state, info
        return state, self._stream_order(info, slot_map)

    # ------------------------------------------------------- persistence
    def make_sink(self, **kw) -> "persistence.WriteBehindSink":
        """A ``WriteBehindSink`` whose partitions mirror this engine's
        layout: key -> partition is exactly the layout's key -> shard map,
        so every durable row lands on the store owned by the shard that
        computed it (no cross-partition traffic — the §5.3 no-coordination
        property extends to storage).

        ``**kw`` passes through to the sink — in particular
        ``backend="durable", store_dir=...`` puts real WAL+compaction
        stores (``streaming/durable.py``) behind this engine, one
        partition directory per shard, and ``store_kw=`` forwards
        storage-plane knobs to those stores (``compaction="background"``,
        ``bloom_bits_per_key=``, ``compact_rate_bytes_per_s=``);
        ``hydrate_from_dir`` is the matching restart path.
        """
        return persistence.WriteBehindSink(
            self.cfg, n_partitions=self.n_shards,
            partition_fn=lambda ks: self.route(np.asarray(ks))[0], **kw)

    def reopen_stores(self, store_dir: str, **kw):
        """Recover this engine's per-shard ``DurableStore`` partitions from
        an on-disk directory (WAL replay + segment load, torn tails
        repaired — see ``streaming/durable.py``).  The returned list is
        layout-aligned, so it can be passed to ``hydrate_state``,
        ``materialize_cold``, or a fresh sink via ``make_sink(stores=...)``
        to resume writing."""
        from repro.streaming.durable import open_partition_stores
        return open_partition_stores(store_dir, self.n_shards, **kw)

    def hydrate_from_dir(self, store_dir: str, **kw) -> ProfileState:
        """Real crash recovery: reopen the durable partition directories
        under ``store_dir`` and rebuild the mesh-sharded state from what
        the disk actually holds.  Unlike ``hydrate_state(sink.stores)``
        (which reads the surviving *process* state), this path starts from
        bytes alone — it is what a restarted process would run."""
        return self.hydrate_state(self.reopen_stores(store_dir, **kw))

    def _row_of_key_host(self) -> np.ndarray:
        """Host map: global entity id -> flat state row, per the layout."""
        if self.layout == "virtual":
            return np.asarray(self.vlayout.row_of_key)
        k = np.arange(self.num_entities, dtype=np.int64)
        return (k % self.n_shards) * self.entities_per_shard \
            + k // self.n_shards

    def hydrate_state(self, stores) -> ProfileState:
        """Rebuild the mesh-sharded state from durable partition stores.

        The restart path: ``hydrate_state(sink.stores)`` after a (simulated)
        process loss yields a state whose persisted columns are bit-exact to
        the lost in-memory state (exact mode) — pinned by
        ``tests/test_persistence.py`` and the serving restart demo.
        """
        state = persistence.hydrate_state(
            stores, self.num_entities, len(self.cfg.taus),
            row_of_key=self._row_of_key_host())
        if self.mesh is None:
            return state
        spec = jax.tree.map(lambda _: P(self.data_axes), state)
        return jax.device_put(state, jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec))

    def materialize(self, state: ProfileState, keys: jax.Array,
                    t: jax.Array) -> jax.Array:
        """Read-only global feature materialization (scoring path).

        Block layout: key k lives at flat row
        (k % n_shards) * E_local + (k // n_shards).  Virtual layout: the
        inverse gather through ``row_of_key`` — user-visible entity ids are
        unchanged by rebalancing.
        """
        if self.layout == "virtual":
            flat = self._row_of_key[keys]
        else:
            flat = (keys % self.n_shards) * self.entities_per_shard \
                + keys // self.n_shards
        out = None if self.mesh is None else NamedSharding(self.mesh, P())
        return core_engine.materialize_features(state, flat, t,
                                                self.cfg.taus,
                                                out_sharding=out)

    def materialize_cold(self, stores, keys, t, l2_probe=None) -> jax.Array:
        """Score straight from durable bytes — restart as cold-start
        hydration, with no dense state table ever built.

        ``stores`` must be layout-partitioned like this engine's
        ``make_sink`` output (key -> partition is the layout's key ->
        shard map).  One batched ``multi_get`` per touched partition
        (metered on the store counters), vectorized unpack, then the same
        decay+materialize program as ``materialize`` — so for persisted
        profiles the scores are bit-identical to materializing a fully
        hydrated state; absent keys score as fresh profiles.  Device cost
        is O(len(keys)) rows, independent of ``num_entities``.

        ``l2_probe``: optional host-L2 lookup callable ``keys -> (rows,
        hit)`` — pass the owning sink's ``l2_probe`` so the probe runs
        under the same partition keying the rows were inserted with (the
        sink owns ``partition_fn``, which need not match this engine's
        ``route``).  Hits — rows and cached absences — skip the durable
        gets; the bytes are identical, so scores are unchanged.  Only
        coherent on a quiescent sink (``ScoringPipeline.score_cold``
        flushes first).
        """
        from repro.core import estimators
        from repro.streaming.kvstore import SerDe

        keys_np = np.asarray(keys, np.int64)
        n_taus = len(self.cfg.taus)
        serde = SerDe(n_taus)
        last_t = np.full(keys_np.size, -np.inf, np.float32)
        agg = np.zeros((keys_np.size, n_taus, 3), np.float32)
        if l2_probe is not None:
            rows, hit = l2_probe(keys_np)
            rows = list(rows)
        else:
            rows = [None] * int(keys_np.size)
            hit = np.zeros(keys_np.size, bool)
        part = self.route(keys_np)[0]
        for p in np.unique(part):
            sel = np.nonzero(part == p)[0]
            todo = sel[~hit[sel]]
            if todo.size:
                got = stores[int(p)].multi_get(keys_np[todo])
                for j, r in zip(todo, got):
                    rows[int(j)] = r
            present = sel[[rows[int(i)] is not None for i in sel]]
            if present.size:
                lt, _, ag, _, _ = serde.unpack_rows(
                    [rows[int(i)] for i in present],
                    keys=keys_np[present], partition=int(p))
                last_t[present] = lt.astype(np.float32)
                agg[present] = ag
        taus = jnp.asarray(self.cfg.taus, jnp.float32)
        agg_now = estimators.decay_to(jnp.asarray(agg),
                                      jnp.asarray(last_t), t, taus)
        return estimators.materialize(agg_now)
