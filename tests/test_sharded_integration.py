"""Mesh-level integration tests (run in subprocesses so the 8 fake devices
never leak into the main test process's jax)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "JAX_PLATFORMS": "cpu"}


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_sharded_engine_no_decision_path_collectives():
    """Paper §4 design goal, verified at the HLO level: the sharded feature
    engine's step emits NO collectives except the scalar metrics reduction.
    """
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, re
        from jax.sharding import Mesh
        from repro.features.engine import ShardedFeatureEngine
        from repro.features.spec import ProfileSpec
        from repro.core import Event

        mesh = jax.make_mesh((8,), ("data",))
        spec = ProfileSpec(windows=(60., 3600.))
        eng = ShardedFeatureEngine(spec.engine_config(), 64, mesh=mesh)
        state = eng.init_state()
        ev = Event(key=jnp.zeros(64, jnp.int32), q=jnp.ones(64),
                   t=jnp.ones(64), valid=jnp.ones(64, bool))
        lowered = jax.jit(eng.make_step()).lower(state, ev,
                                                 jax.random.PRNGKey(0))
        hlo = lowered.compile().as_text()
        colls = [l.strip()[:120] for l in hlo.splitlines()
                 if re.search(r" (all-gather|all-to-all|"
                              r"collective-permute)\\(", l)]
        big_ar = [l.strip()[:120] for l in hlo.splitlines()
                  if " all-reduce(" in l and "f32[]" not in l
                  and "s32[]" not in l]
        print("COLLS", len(colls), len(big_ar))
        for l in (colls + big_ar)[:5]:
            print("  ", l)
    """)
    n_coll, n_big_ar = map(int, out.split("COLLS")[1].split()[:2])
    assert n_coll == 0, out
    assert n_big_ar == 0, out


def test_sharded_engine_matches_unsharded_statistics():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.features.engine import ShardedFeatureEngine
        from repro.features.spec import ProfileSpec
        from repro.core import Event

        mesh = jax.make_mesh((8,), ("data",))
        spec = ProfileSpec(windows=(60., 3600.),
                           write_budget_per_min=0.02)
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 64, 1024).astype(np.int32)
        qs = rng.lognormal(3, 1, 1024).astype(np.float32)
        ts = np.sort(rng.uniform(0, 2e5, 1024)).astype(np.float32)

        def drive(mesh_or_none):
            eng = ShardedFeatureEngine(spec.engine_config(), 64,
                                       mesh=mesh_or_none)
            state = eng.init_state()
            step = jax.jit(eng.make_step())
            writes = 0
            for i in range(0, 1024, 64):
                if mesh_or_none is not None:
                    ev = eng.partition_events(keys[i:i+64], qs[i:i+64],
                                              ts[i:i+64], 8)
                else:
                    ev = Event(key=jnp.asarray(keys[i:i+64]),
                               q=jnp.asarray(qs[i:i+64]),
                               t=jnp.asarray(ts[i:i+64]),
                               valid=jnp.ones(64, bool))
                state, info = step(state, ev, jax.random.PRNGKey(0))
                writes += int(info.writes)
            total = float(jnp.sum(eng.materialize(
                state, jnp.arange(64), jnp.float32(2e5))[:, 1]))
            return writes, total

        w_sh, sum_sh = drive(mesh)
        w_un, sum_un = drive(None)
        print("RES", w_sh, w_un, sum_sh, sum_un)
    """)
    w_sh, w_un, sum_sh, sum_un = out.split("RES")[1].split()
    # different RNG folding across shards -> statistically similar, not equal
    assert abs(int(w_sh) - int(w_un)) < 0.5 * max(int(w_un), 1), out
    assert abs(float(sum_sh) - float(sum_un)) / max(float(sum_un), 1) < 0.5


def test_sharded_engine_bitwise_parity_with_local():
    """Global-entity RNG keying makes shard placement decision-invariant:
    the same routed micro-batches through the sharded engine and through
    core.engine (global keys) yield bit-identical StepInfo on valid lanes
    and bit-identical state, in both execution modes."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.features.engine import ShardedFeatureEngine
        from repro.core import EngineConfig, Event, init_state, make_step

        mesh = jax.make_mesh((8,), ("data",))
        cfg = EngineConfig(taus=(60., 3600.), h=600., budget=0.0005,
                           policy="pp", exact_rounds=16)
        rng = np.random.default_rng(0)
        N, E = 1024, 64
        keys = rng.integers(0, E, N).astype(np.int32)
        qs = rng.lognormal(3, 1, N).astype(np.float32)
        ts = np.sort(rng.uniform(0, 2e5, N)).astype(np.float32)
        root = jax.random.PRNGKey(5)
        k = np.arange(E)
        perm = (k % 8) * 8 + k // 8       # sharded row of global entity k

        for mode in ("exact", "fast"):
            eng = ShardedFeatureEngine(cfg, E, mesh=mesh, mode=mode)
            st_sh = eng.init_state()
            st_lo = init_state(eng.num_entities, 2)
            step_sh = jax.jit(eng.make_step())
            step_lo = jax.jit(make_step(cfg, mode))
            writes = 0
            for i in range(0, N, 64):
                ev = eng.partition_events(keys[i:i+64], qs[i:i+64],
                                          ts[i:i+64], 8)
                gkey = np.asarray(ev.key) * 8 + np.repeat(np.arange(8), 8)
                ev_g = Event(key=jnp.asarray(gkey), q=ev.q, t=ev.t,
                             valid=ev.valid)
                st_sh, i_sh = step_sh(st_sh, ev, root)
                st_lo, i_lo = step_lo(st_lo, ev_g, root)
                v = np.asarray(ev.valid)
                # z is valid-gated -> equal everywhere; p/features compare
                # on valid lanes (padding lanes gather different rows)
                assert np.array_equal(np.asarray(i_sh.z), np.asarray(i_lo.z))
                assert np.array_equal(np.asarray(i_sh.p)[v],
                                      np.asarray(i_lo.p)[v])
                assert np.allclose(np.asarray(i_sh.features)[v],
                                   np.asarray(i_lo.features)[v],
                                   rtol=1e-6, atol=1e-6)
                assert int(i_sh.writes) == int(i_lo.writes)
                writes += int(i_sh.writes)
            for a, b, name in zip(st_sh, st_lo, st_sh._fields):
                assert np.array_equal(np.asarray(a)[perm], np.asarray(b)), \\
                    (mode, name)
            assert 0 < writes < N            # thinning actually engaged
            print("PARITY", mode, writes)
    """)
    assert "PARITY exact" in out and "PARITY fast" in out


def test_sharded_run_stream_matches_local_stream():
    """The sharded donated-buffer stream driver: one dispatch for the whole
    partitioned stream, bit-identical (exact mode) to core.stream.run_stream
    on the same flat stream, with per-event info mapped back to stream
    order."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.features.engine import ShardedFeatureEngine
        from repro.core import EngineConfig, init_state
        from repro.core.stream import run_stream as local_run_stream

        mesh = jax.make_mesh((8,), ("data",))
        cfg = EngineConfig(taus=(60., 3600.), h=600., budget=0.0005,
                           policy="pp", exact_rounds=32)
        rng = np.random.default_rng(1)
        N, E = 1500, 64                      # non-block-multiple tail
        keys = rng.integers(0, E, N).astype(np.int32)
        qs = rng.lognormal(3, 1, N).astype(np.float32)
        ts = np.sort(rng.uniform(0, 2e5, N)).astype(np.float32)
        root = jax.random.PRNGKey(5)

        eng = ShardedFeatureEngine(cfg, E, mesh=mesh, mode="exact")
        st_sh, info_sh = eng.run_stream(eng.init_state(), keys, qs, ts,
                                        batch_per_shard=64, rng=root)
        st_lo, info_lo = local_run_stream(cfg, init_state(E, 2), keys, qs,
                                          ts, batch=64, mode="exact",
                                          rng=root)
        assert np.array_equal(np.asarray(info_sh.z), np.asarray(info_lo.z))
        assert np.array_equal(np.asarray(info_sh.p), np.asarray(info_lo.p))
        assert int(info_sh.writes) == int(info_lo.writes)
        k = np.arange(E)
        perm = (k % 8) * 8 + k // 8
        for a, b, name in zip(st_sh, st_lo, st_sh._fields):
            assert np.array_equal(np.asarray(a)[perm], np.asarray(b)), name
            # one E/8-row shard of every state column on every device
            shards = a.addressable_shards
            assert {s.device for s in shards} == set(mesh.devices.flat), name
            assert all(s.data.shape[0] == E // 8 for s in shards), name

        # cheapest path: per-block write counts only, donated state
        eng2 = ShardedFeatureEngine(cfg, E, mesh=mesh, mode="exact")
        st2, wr = eng2.run_stream(eng2.init_state(), keys, qs, ts,
                                  batch_per_shard=64, rng=root,
                                  collect_info=False)
        assert int(jnp.sum(wr)) == int(info_lo.writes)
        print("STREAM", int(info_sh.writes), N)
    """)
    writes, n = map(int, out.split("STREAM")[1].split()[:2])
    assert 0 < writes < n


def test_virtual_layout_bitwise_parity_extreme_skew():
    """The skew-rebalanced ``layout="virtual"`` path (power-of-two-choices
    over virtual shards + gather at materialize) changes *placement only*:
    on an extreme-skew stream (one key carrying ~85% of events) its thinning
    decisions, per-event info, final state and materialized features are all
    bit-identical to the local engine — the CI enforcement of the layout
    contract's RNG identity guarantee."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.features.engine import ShardedFeatureEngine
        from repro.core import EngineConfig, init_state
        from repro.core.engine import materialize_features
        from repro.core.stream import run_stream as local_run_stream

        mesh = jax.make_mesh((8,), ("data",))
        cfg = EngineConfig(taus=(60., 3600.), h=600., budget=0.0005,
                           policy="pp", exact_rounds=16)
        rng = np.random.default_rng(1)
        N, E, hot = 1600, 64, 37
        keys = np.where(rng.uniform(size=N) < 0.85, hot,
                        rng.integers(0, E, N)).astype(np.int32)
        qs = rng.lognormal(3, 1, N).astype(np.float32)
        ts = np.sort(rng.uniform(0, 2e5, N)).astype(np.float32)
        root = jax.random.PRNGKey(5)

        eng = ShardedFeatureEngine(
            cfg, E, mesh=mesh, mode="exact", layout="virtual",
            key_weights=np.bincount(keys, minlength=E))
        st_sh, info_sh = eng.run_stream(eng.init_state(), keys, qs, ts,
                                        batch_per_shard=16, rng=root)
        st_lo, info_lo = local_run_stream(cfg, init_state(E, 2), keys, qs,
                                          ts, batch=16, mode="exact",
                                          rng=root)
        assert np.array_equal(np.asarray(info_sh.z), np.asarray(info_lo.z))
        assert np.array_equal(np.asarray(info_sh.p), np.asarray(info_lo.p))
        assert int(info_sh.writes) == int(info_lo.writes)
        row = np.asarray(eng.vlayout.row_of_key)
        for a, b, name in zip(st_sh, st_lo, st_sh._fields):
            assert np.array_equal(np.asarray(a)[row], np.asarray(b)), name
        # gather-on-materialize: user-visible ids unchanged by rebalancing
        m_sh = eng.materialize(st_sh, jnp.arange(E), jnp.float32(2e5))
        m_lo = materialize_features(st_lo, jnp.arange(E), jnp.float32(2e5),
                                    cfg.taus)
        assert np.array_equal(np.asarray(m_sh), np.asarray(m_lo))
        print("VPARITY", int(info_sh.writes), N)
    """)
    writes, n = map(int, out.split("VPARITY")[1].split()[:2])
    assert 0 < writes < n


def test_virtual_layout_cuts_padding_under_mesh():
    """stream_layout_stats through a real 8-shard engine pair: the virtual
    layout needs materially fewer padded block slots than the block layout
    on a Zipf stream (the rebalancing win the skew bench records)."""
    out = _run("""
        import jax, numpy as np, json
        from repro.core import EngineConfig
        from repro.features.engine import ShardedFeatureEngine

        mesh = jax.make_mesh((8,), ("data",))
        cfg = EngineConfig(taus=(60.,), h=600.)
        rng = np.random.default_rng(0)
        E = 4096
        w = 1.0 / np.arange(1, E + 1) ** 1.0
        keys = rng.permutation(E)[rng.choice(E, 40_000, p=w / w.sum())]
        keys = keys.astype(np.int32)
        stats = {}
        for layout in ("block", "virtual"):
            eng = ShardedFeatureEngine(
                cfg, E, mesh=mesh, layout=layout,
                key_weights=np.bincount(keys, minlength=E))
            stats[layout] = eng.stream_layout_stats(keys, 512)
        print("PADS", json.dumps(stats))
    """)
    stats = json.loads(out.split("PADS", 1)[1])
    assert stats["block"]["events"] == stats["virtual"]["events"] == 40_000
    assert (stats["virtual"]["padded_fraction"] * 2
            <= stats["block"]["padded_fraction"]), stats


def test_dryrun_cell_small_mesh():
    """run_cell logic end to end on an 8-device mesh (fast smoke of the
    512-device dry-run path)."""
    out = _run("""
        import jax, dataclasses, json
        from repro.configs.base import load_smoke_config
        from repro.configs import shapes as shape_lib
        from repro.distributed import context as dctx, sharding as rules
        from repro.launch import hlo_analysis, shardings
        from repro.train.trainer import make_train_step

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        run = load_smoke_config("yi-9b")
        run = dataclasses.replace(run, train=dataclasses.replace(
            run.train, grad_accum=1))
        shape = shape_lib.ShapeSpec("t", 64, 8, "train")
        with dctx.mesh_context(mesh, rules.make_rules(fsdp=True)):
            fn = make_train_step(run)
            state = shardings.train_state_sds(run, mesh)
            batch = shardings.batch_sds(run, shape, mesh)
            rng = shardings.rng_sds(mesh)
            compiled = jax.jit(fn).lower(state, batch, rng).compile()
            mem = hlo_analysis.memory_analysis_dict(compiled)
            coll = hlo_analysis.collective_stats(compiled.as_text(), 8)
        print("OK", json.dumps({"args": mem.get("argument_size_in_bytes"),
                                "coll": coll.per_chip_bytes}))
    """)
    assert "OK" in out
    rec = json.loads(out.split("OK", 1)[1])
    assert rec["args"] > 0


def test_elastic_reshard_after_checkpoint():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint import CheckpointManager
        from repro.checkpoint import repartition_profile_state
        from repro.features.engine import ShardedFeatureEngine
        from repro.features.spec import ProfileSpec
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh8 = jax.make_mesh((8,), ("data",))
        spec = ProfileSpec(windows=(60.,), write_budget_per_min=60.0)
        eng = ShardedFeatureEngine(spec.engine_config(), 64, mesh=mesh8)
        state = eng.init_state()
        step = jax.jit(eng.make_step())
        ev = eng.partition_events(np.arange(64, dtype=np.int32),
                                  np.ones(64, np.float32),
                                  np.arange(64, dtype=np.float32) + 1, 8)
        state, _ = step(state, ev, jax.random.PRNGKey(0))

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_io=False)
            mgr.save(1, state)
            restored = mgr.restore(state)
        new = repartition_profile_state(restored, old_shards=8,
                                        new_shards=4, num_keys=64)
        # key k's row moved correctly
        ok = True
        agg_old = np.asarray(restored.agg)
        for k in range(64):
            src = (k % 8) * 8 + k // 8
            dst = (k % 4) * 16 + k // 4
            ok &= np.allclose(agg_old[src], np.asarray(new.agg)[dst])
        print("ELASTIC", ok)
    """)
    assert "ELASTIC True" in out


def test_mesh_sink_byte_parity_and_hydrate():
    """Durable write-behind on a real 8-device mesh: sink bytes equal the
    per-event worker's for both layouts, and hydrate_state rebuilds the
    mesh-sharded state exactly (the persistence contract survives
    sharding, routing and the group-commit driver)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import EngineConfig
        from repro.features.engine import ShardedFeatureEngine
        from repro.streaming.worker import FeatureWorker
        from repro.streaming.kvstore import KVStore

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(2)
        n_events, n_keys = 1200, 64
        keys = rng.integers(0, n_keys, n_events).astype(np.int32)
        ts = np.cumsum(rng.exponential(20.0, n_events)).astype(np.float32)
        qs = rng.lognormal(3.0, 1.0, n_events).astype(np.float32)
        root = jax.random.PRNGKey(3)
        cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=0.002,
                           policy="pp", exact_rounds=256)
        store = KVStore(seed=0)
        wkr = FeatureWorker(cfg, store, rng=root)
        for i in range(n_events):
            wkr.process(int(keys[i]), float(qs[i]), float(ts[i]))
        for layout in ("block", "virtual"):
            eng = ShardedFeatureEngine(
                cfg, n_keys, mesh=mesh, mode="exact", layout=layout,
                key_weights=(np.bincount(keys, minlength=n_keys)
                             if layout == "virtual" else None))
            sink = eng.make_sink()
            st, info = eng.run_stream(eng.init_state(), keys, qs, ts,
                                      batch_per_shard=32, rng=root,
                                      sink=sink, sink_group=3)
            sink.flush()
            data = {}
            for s in sink.stores:
                data.update(s.data)
            assert set(data) == set(store.data), layout
            bad = [k for k in data if data[k] != store.data[k]]
            assert not bad, (layout, len(bad))
            hyd = eng.hydrate_state(sink.stores)
            for f in ("last_t", "v_f", "agg"):
                a = np.asarray(getattr(hyd, f))
                b = np.asarray(getattr(st, f))
                assert np.array_equal(a, b), (layout, f)
            sink.close()
            print("LAYOUT_OK", layout, int(info.writes))
        print("ALL_OK")
    """)
    assert "ALL_OK" in out
    assert out.count("LAYOUT_OK") == 2
