"""Bring-up smoke test: the thinned feature engine's main path on a TPU.

    python chip_smoke.py              # one chip, phases 1-7 below
    python chip_smoke.py --chips 4    # four chips: the sharded path only

The deployment is the paper's IIoT regime (Table 2) at its published 800K
keys: ~0.7% of keys carry 80% of the events, 40% of events are anomalous,
near-symmetric (uniform) marks.  The engine runs in fast mode with policy
pp_vr (Eq. 4, alpha = 1), write budget Lambda * h = 0.1, the default six
profile windows (1 minute .. 120 days) and batches of 4096 events; its
device state is 800K x 22 float32 (~70 MB).  Every input is generated from
``--seed``.

One chip:

1. device     the first JAX device is a TPU
2. kernel     the compiled flush-group step holds the Pallas kernel
3. ingest     1M events through ``run_stream`` into a durable sink
4. oracle     exact mode on a 20K-event prefix against the per-event oracle
5. restart    ``hydrate_state`` from the store directory == device state
6. residency  the ingest again with a quarter of the keys resident
7. serve      open-loop requests through ``ScoringPipeline.serve``

Four chips (``--chips 4``): the sharded engine on a 4-device ``data`` mesh,
both layouts, with a layout-routed durable sink, against the one-device
engine on the same stream.

Each phase prints one line.  The last line is a JSON object with ``"ok":
true`` and the device; any failure exits non-zero before it.  All phases
run in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import thinning  # noqa: E402
from repro.core.reference import ReferenceEngine  # noqa: E402
from repro.core.stream import _sink_step, run_stream  # noqa: E402
from repro.core.types import Event, init_state  # noqa: E402
from repro.features.engine import ShardedFeatureEngine  # noqa: E402
from repro.features.spec import ProfileSpec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.frontend import poisson_arrivals  # noqa: E402
from repro.serving.pipeline import ScoringPipeline, init_scorer  # noqa: E402
from repro.streaming.durable import open_partition_stores  # noqa: E402
from repro.streaming.persistence import (WriteBehindSink,  # noqa: E402
                                         hydrate_state)
from repro.streaming.workload import REGIMES, generate  # noqa: E402

N_KEYS = 800_000            # Table 2 IIoT, published scale
N_EVENTS = 1 << 20          # 256 batches, 32 flush groups
BATCH = 4096
SINK_GROUP = 8
N_ORACLE = 20_480           # exact-mode prefix checked against the oracle
N_REQUESTS = 4096           # serve phase
SERVE_BATCH = 256
SERVE_LOAD = 20_000.0       # offered requests/s
SHARDED_EVENTS = 1 << 18    # four-chip phase

SPEC = ProfileSpec(write_budget_per_min=0.1 / 60.0, variance_alpha=1.0,
                   policy="pp_vr")

# tests/test_core_engine.py's oracle tolerances
P_RTOL, P_ATOL, LAM_RTOL = 2e-4, 1e-6, 2e-4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def deployment(n_keys: int, n_events: int, seed: int):
    spec = dataclasses.replace(REGIMES["iiot"], n_keys=n_keys,
                               n_events=n_events)
    return generate(spec, seed=seed)


def stored_rows(stores) -> dict:
    """Every (key, row bytes) the stores hold, memtable and segments."""
    out = {}
    for s in stores:
        ks = sorted(s.keys())
        out.update(zip(ks, s.multi_get(ks)))
    return out


def exact_rounds_for(keys: np.ndarray, batch: int) -> int:
    """Power of two >= the most events one key has in one batch."""
    most = max(int(np.bincount(keys[i:i + batch]).max())
               for i in range(0, len(keys), batch))
    return 1 << (most - 1).bit_length()


def peak_device_bytes() -> int:
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    check(bool(peak), f"{dev.device_kind} reports no peak_bytes_in_use")
    return int(peak)


# ---------------------------------------------------------------- phases
def phase_device(chips: int, cache_dir: str):
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"no TPU: first device is {dev.platform}")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), jax=jax.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache=cache_dir, cache_entries=entries)
    return dev


def phase_kernel(cfg, n_keys: int, batch: int, group: int):
    """Lower + compile the fast-mode flush-group step that ``run_stream``
    dispatches, and find the Pallas kernel in it."""
    check(ops._resolve("auto") == "pallas",
          "the engine's kernel calls do not resolve to the Pallas kernel")
    state = init_state(n_keys, len(cfg.taus))
    shp = lambda dt: jax.ShapeDtypeStruct((group, batch), dt)
    ev = Event(key=shp(jnp.int32), q=shp(jnp.float32), t=shp(jnp.float32),
               valid=shp(jnp.bool_))
    gidx = jax.ShapeDtypeStruct((group * batch,), jnp.int32)
    t0 = time.perf_counter()
    compiled = _sink_step(cfg, "fast", True, True, "compact").lower(
        state, ev, jax.random.PRNGKey(0), gidx).compile()
    secs = time.perf_counter() - t0
    kernels = [l for l in compiled.as_text().splitlines()
               if "custom-call(" in l and "tpu_custom_call" in l]
    check(bool(kernels), "the compiled group step holds no Pallas kernel")
    mem = compiled.memory_analysis()
    say("kernel", path=ops._resolve("auto"), tpu_custom_calls=len(kernels),
        compile_s=secs,
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        alias_bytes=mem.alias_size_in_bytes)


def ingest(cfg, stream, n_keys: int, batch: int, group: int, rng,
           store_dir: str, residency=None):
    """One durable ingest run; returns (state, info, sink stats, wall)."""
    rows = n_keys if residency is None else residency
    state = init_state(rows, len(cfg.taus))
    sink = WriteBehindSink(cfg, backend="durable", store_dir=store_dir)
    t0 = time.perf_counter()
    state, info = run_stream(cfg, state, stream.key, stream.q, stream.t,
                             batch=batch, mode="fast", rng=rng, sink=sink,
                             sink_group=group, residency=residency)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    stats = sink.flush()
    sink.close()
    return state, info, stats, wall


def phase_ingest(cfg, stream, n_keys: int, batch: int, group: int, rng,
                 store_dir: str, warm_dir: str):
    # warm-up on the first flush group: compiles the group program
    n_warm = batch * group
    warm = dataclasses.replace(stream, key=stream.key[:n_warm],
                               q=stream.q[:n_warm], t=stream.t[:n_warm])
    ingest(cfg, warm, n_keys, batch, group, rng, warm_dir)
    state, info, stats, wall = ingest(cfg, stream, n_keys, batch, group, rng,
                                      store_dir)
    n = len(stream)
    z = np.asarray(info.z)
    check(z.shape == (n,), f"z has shape {z.shape}, want ({n},)")
    check(bool(np.all(np.isfinite(np.asarray(info.p)))), "non-finite p")
    check(bool(np.all(np.isfinite(np.asarray(state.agg)))),
          "non-finite aggregates")
    check(int(info.writes) == int(z.sum()),
          f"writes {int(info.writes)} != selected events {int(z.sum())}")
    say("ingest", events=n, batch=batch, sink_group=group, wall_s=wall,
        events_per_s=n / wall, writes=int(info.writes),
        puts_per_event=stats["puts"] / n,
        peak_bytes_in_use=peak_device_bytes())
    return state, info


def zs_f32_error(cfg, ent, taus, q: float, t: float) -> float:
    """Bound on the float32 error of Eq. 4's standardised mark ``zs`` for
    one event, from the oracle's (float64) state of its key.

    The engine derives sigma_w from decayed count / sum / sumsq as
    ``sumsq/cnt - mean^2``.  Where the marks' spread is small next to their
    mean (IIoT's near-symmetric marks), that difference cancels and float32
    keeps the variance only to about ``eps32 * sumsq/cnt``; the bound allows
    16 such ulps and is 16 (zs anywhere in its clip range) where the
    variance is no larger than that noise."""
    if cfg.policy != "pp_vr" or not math.isfinite(ent.last_t):
        return 0.0
    j = cfg.mu_tau_index
    cnt, sm, sq = ent.agg[j] * math.exp(-max(t - ent.last_t, 0.0) / taus[j])
    if cnt < 1.0:               # cold: sigma is the constant 1e8
        return 0.0
    mean, s2 = sm / cnt, sq / cnt
    var, dvar = s2 - mean * mean, 16 * 2.0 ** -24 * s2
    if var <= dvar:
        return 16.0
    return min(16.0, abs(q - mean) / math.sqrt(var) * dvar / (2 * var))


def phase_oracle(cfg, stream, n_keys: int, n_prefix: int, batch: int, rng):
    """Exact mode against ``core.reference.ReferenceEngine`` event by
    event.  A key leaves the comparison (its later events are skipped, and
    counted) at its first event whose decision sits inside the arithmetic's
    reach: ``u`` within p's tolerance (a z flip), or p off by more than the
    tolerance but within the float32 cancellation bound of ``zs``."""
    keys = stream.key[:n_prefix]
    qs, ts = stream.q[:n_prefix], stream.t[:n_prefix]
    ecfg = dataclasses.replace(cfg, exact_rounds=exact_rounds_for(keys,
                                                                  batch))
    t0 = time.perf_counter()
    _, info = run_stream(ecfg, init_state(n_keys, len(cfg.taus)), keys, qs,
                         ts, batch=batch, mode="exact", rng=rng)
    z, p = np.asarray(info.z), np.asarray(info.p)
    lam = np.asarray(info.lam_hat)
    wall = time.perf_counter() - t0
    sigmoid = lambda x: 1.0 / (1.0 + math.exp(-x))

    # the oracle runs on the host CPU backend, independent of the chip
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        rng_cpu = jax.device_put(rng, cpu)
        u = np.asarray(thinning.uniform_for_events(
            rng_cpu, jnp.asarray(keys), thinning.time_bits(jnp.asarray(ts))))
        ref = ReferenceEngine(ecfg, n_keys, rng_cpu)
        boundary = ill = skipped = 0
        bad, diverged = [], set()
        p_use = lam_use = 0.0       # largest error / tolerance
        for i in range(n_prefix):
            k, q, t = int(keys[i]), float(qs[i]), float(ts[i])
            zs_err = zs_f32_error(ecfg, ref.ents[k], ref.taus, q, t)
            p_r, z_r, lam_r = ref.process(k, q, t)
            if k in diverged:
                skipped += 1
                continue
            tol = P_RTOL * p_r + P_ATOL
            lo, hi = p_r - tol, p_r + tol
            if zs_err and 0.0 < p_r < 1.0 - 1e-6:
                logit = math.log(p_r) - math.log1p(-p_r)
                lo = sigmoid(logit - ecfg.alpha * zs_err) - tol
                hi = sigmoid(logit + ecfg.alpha * zs_err) + tol
            if bool(z[i]) != bool(z_r):
                if lo <= u[i] <= hi:
                    boundary += 1
                    diverged.add(k)     # later events see another state
                    continue
                bad.append((i, "z", bool(z[i]), bool(z_r)))
            p_err = abs(p[i] - p_r)
            if p_err > tol:
                if lo <= p[i] <= hi:
                    ill += 1
                    diverged.add(k)
                    continue
                bad.append((i, "p", float(p[i]), p_r))
            lam_err = abs(lam[i] - lam_r)
            if lam_err > LAM_RTOL * lam_r:
                bad.append((i, "lam", float(lam[i]), lam_r))
            p_use = max(p_use, p_err / tol)
            lam_use = max(lam_use, lam_err / (LAM_RTOL * lam_r))
    check(not bad, f"{len(bad)} oracle mismatches, first {bad[:5]}")
    say("oracle", events=n_prefix, exact_rounds=ecfg.exact_rounds,
        engine_wall_s=wall, writes=int(z.sum()), boundary_lanes=boundary,
        ill_conditioned_lanes=ill, events_skipped=skipped,
        max_p_err_over_tol=p_use, max_lam_err_over_tol=lam_use)


def phase_restart(state, store_dir: str, n_keys: int, n_taus: int):
    stores = open_partition_stores(store_dir, 1)
    try:
        hyd = hydrate_state(stores, n_keys, n_taus)
        n_rows = sum(len(s.keys()) for s in stores)
    finally:
        for s in stores:
            s.close()
    for f in ("last_t", "v_f", "agg"):
        check(np.array_equal(np.asarray(getattr(hyd, f)),
                             np.asarray(getattr(state, f))),
              f"hydrated {f} differs from the device state")
    say("restart", stored_keys=n_rows, columns="last_t,v_f,agg",
        bit_exact=True)


def phase_residency(cfg, stream, n_keys: int, batch: int, group: int, rng,
                    dense_info, dense_dir: str, store_dir: str):
    slots = n_keys // 4
    _, info, stats, wall = ingest(cfg, stream, n_keys, batch, group, rng,
                                  store_dir, residency=slots)
    for f in ("z", "p"):
        check(np.array_equal(np.asarray(getattr(info, f)),
                             np.asarray(getattr(dense_info, f))),
              f"residency {f} differs from the dense run")
    stores = [open_partition_stores(d, 1) for d in (dense_dir, store_dir)]
    try:
        dense_rows, res_rows = (stored_rows(s) for s in stores)
    finally:
        for group_stores in stores:
            for s in group_stores:
                s.close()
    check(dense_rows == res_rows,
          f"stored bytes differ: {len(dense_rows)} dense rows vs "
          f"{len(res_rows)} resident-run rows")
    # no warm-up here: the wall time includes compiling the residency step
    say("residency", slots=slots, events=len(stream), wall_s=wall,
        stored_rows=len(res_rows), z_p_bytes_equal=True)


def phase_serve(spec, stream, n_keys: int, n_requests: int, batch: int,
                load: float, seed: int, store_dir: str, warm_dir: str):
    pipe = ScoringPipeline.build(spec, n_keys, mode="fast")
    pipe.scorer = init_scorer(jax.random.PRNGKey(seed), spec.feature_dim)
    keys, qs = stream.key[:n_requests], stream.q[:n_requests]
    ts = stream.t[:n_requests]
    rng = jax.random.PRNGKey(seed)
    # warm-up burst: compiles the dispatch programs
    w = min(4 * batch, n_requests)
    wsink = pipe.make_sink(backend="durable", store_dir=warm_dir)
    pipe.serve(keys[:w], qs[:w], ts[:w], arrival_s=np.zeros(w), batch=batch,
               rng=rng, sink=wsink)
    wsink.close()
    sink = pipe.make_sink(backend="durable", store_dir=store_dir)
    res = pipe.serve(keys, qs, ts,
                     arrival_s=poisson_arrivals(n_requests, load, seed=seed),
                     batch=batch, max_wait_s=0.002, rng=rng, sink=sink)
    stats = sink.flush()
    sink.close()

    feats = np.asarray(res.features, np.float64)
    scores = np.asarray(res.scores, np.float64)
    check(feats.shape == (n_requests, spec.feature_dim)
          and scores.shape == (n_requests,), "serve outputs have bad shapes")
    check(bool(np.all(np.isfinite(feats)) and np.all(np.isfinite(scores))),
          "non-finite serve outputs")
    # float64 reference of the scorer.  The chip multiplies f32 matrices
    # in one bfloat16 pass (8-bit significands), so each product carries a
    # relative error of at most 2^-8; the bound below is twice that,
    # propagated through both layers.
    prm = jax.tree.map(lambda a: np.asarray(a, np.float64), pipe.scorer)
    x = (np.log1p(np.abs(feats)) * np.sign(feats) - prm.mu) / prm.sd
    h = np.maximum(x @ prm.w1 + prm.b1, 0.0)
    want = (h @ prm.w2 + prm.b2)[:, 0]
    bound = 2.0 ** -7 * (np.abs(x) @ np.abs(prm.w1) @ np.abs(prm.w2)
                         + np.abs(h) @ np.abs(prm.w2))[:, 0] + 1e-5
    err = np.abs(scores - want)
    check(bool(np.all(err <= bound)),
          f"{int((err > bound).sum())} scores outside the bfloat16 bound")
    q = res.latency_quantiles()
    st = res.stats
    say("serve", requests=n_requests, batch=batch, offered_per_s=load,
        p50_ms=q["p50"] * 1e3, p99_ms=q["p99"] * 1e3,
        dispatches=st.dispatches, full=st.full_batches,
        deadline=st.deadline_batches, puts=stats["puts"],
        max_score_err=float(err.max()), max_err_over_bound=float(
            (err / bound).max()))


def phase_sharded(cfg, stream, n_keys: int, n_dev: int, batch: int, rng,
                  tmp: str):
    """Sharded engine on an ``n_dev`` data mesh vs the one-device engine:
    exact mode, where per-key sequential semantics make the decisions
    independent of how events are batched and routed."""
    mesh = jax.make_mesh((n_dev,), ("data",))
    keys, qs, ts = stream.key, stream.q, stream.t
    bps = batch // n_dev
    # a shard block of bps lanes holds at most bps events of one key
    ecfg = dataclasses.replace(cfg, exact_rounds=max(
        exact_rounds_for(keys, batch), 1 << (bps - 1).bit_length()))

    sink = WriteBehindSink(ecfg, backend="durable",
                           store_dir=os.path.join(tmp, "one"))
    t0 = time.perf_counter()
    st_lo, info_lo = run_stream(ecfg, init_state(n_keys, len(cfg.taus)),
                                keys, qs, ts, batch=batch, mode="exact",
                                rng=rng, sink=sink, sink_group=SINK_GROUP)
    jax.block_until_ready(st_lo)
    wall_lo = time.perf_counter() - t0
    sink.close()
    stores = open_partition_stores(os.path.join(tmp, "one"), 1)
    rows_lo = stored_rows(stores)
    for s in stores:
        s.close()
    z_lo, p_lo = np.asarray(info_lo.z), np.asarray(info_lo.p)
    say("one-device", events=len(keys), exact_rounds=ecfg.exact_rounds,
        wall_s=wall_lo, writes=int(z_lo.sum()), stored_rows=len(rows_lo))

    for layout in ("block", "virtual"):
        eng = ShardedFeatureEngine(
            ecfg, n_keys, mesh=mesh, mode="exact", layout=layout,
            key_weights=(np.bincount(keys, minlength=n_keys)
                         if layout == "virtual" else None))
        state = eng.init_state()
        devs = {s.device for s in state.agg.addressable_shards}
        check(len(devs) == n_dev,
              f"{layout}: state spans {len(devs)} devices, not {n_dev}")
        store_dir = os.path.join(tmp, layout)
        sink = eng.make_sink(backend="durable", store_dir=store_dir)
        t0 = time.perf_counter()
        st, info = eng.run_stream(state, keys, qs, ts, batch_per_shard=bps,
                                  rng=rng, sink=sink, sink_group=SINK_GROUP)
        jax.block_until_ready(st)
        wall = time.perf_counter() - t0
        sink.close()
        stores = eng.reopen_stores(store_dir)
        rows = stored_rows(stores)
        for s in stores:
            s.close()
        check(np.array_equal(np.asarray(info.z), z_lo),
              f"{layout}: z differs from the one-device engine")
        check(np.array_equal(np.asarray(info.p), p_lo),
              f"{layout}: p differs from the one-device engine")
        check(rows == rows_lo,
              f"{layout}: stored bytes differ from the one-device engine")
        say("sharded", layout=layout, devices=n_dev, batch_per_shard=bps,
            wall_s=wall, writes=int(np.asarray(info.z).sum()),
            z_p_bytes_equal=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    dev = phase_device(args.chips, cache_dir)
    cfg = SPEC.engine_config()
    rng = jax.random.PRNGKey(args.seed)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            stream = deployment(N_KEYS, SHARDED_EVENTS, args.seed)
            phase_sharded(cfg, stream, N_KEYS, 4, BATCH, rng, tmp)
        else:
            stream = deployment(N_KEYS, N_EVENTS, args.seed)
            phase_kernel(cfg, N_KEYS, BATCH, SINK_GROUP)
            dense_dir = os.path.join(tmp, "dense")
            state, info = phase_ingest(cfg, stream, N_KEYS, BATCH,
                                       SINK_GROUP, rng, dense_dir,
                                       os.path.join(tmp, "warm"))
            phase_oracle(cfg, stream, N_KEYS, N_ORACLE, BATCH, rng)
            phase_restart(state, dense_dir, N_KEYS, len(cfg.taus))
            del state
            phase_residency(cfg, stream, N_KEYS, BATCH, SINK_GROUP, rng,
                            info, dense_dir, os.path.join(tmp, "resident"))
            phase_serve(SPEC, stream, N_KEYS, N_REQUESTS, SERVE_BATCH,
                        SERVE_LOAD, args.seed, os.path.join(tmp, "serve"),
                        os.path.join(tmp, "serve_warm"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
