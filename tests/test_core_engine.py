"""Engine correctness: JAX vectorized modes vs the per-event Python oracle."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, Event, init_state, make_step
from repro.core.reference import ReferenceEngine

jax.config.update("jax_enable_x64", False)


def _make_stream(rng, n_events, n_entities, skew=1.5, t_scale=50.0):
    """Zipf-skewed keys, exponential inter-arrivals, lognormal marks."""
    probs = (1.0 / np.arange(1, n_entities + 1) ** skew)
    probs /= probs.sum()
    keys = rng.choice(n_entities, size=n_events, p=probs)
    ts = np.cumsum(rng.exponential(t_scale, size=n_events))
    # strictly increasing distinct timestamps per key (paper assumes ordered
    # streams; equality would make the RNG counter collide)
    qs = rng.lognormal(3.0, 1.0, size=n_events)
    return keys.astype(np.int32), qs.astype(np.float32), ts.astype(np.float32)


POLICIES = ["pp", "pp_vr", "full", "fixed", "unfiltered"]


@pytest.mark.parametrize("policy", POLICIES)
def test_exact_engine_matches_oracle(policy):
    rng = np.random.default_rng(0)
    n_events, n_entities, batch = 256, 12, 32
    keys, qs, ts = _make_stream(rng, n_events, n_entities)
    cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=600.0, budget=0.01,
                       alpha=1.0, policy=policy, fixed_rate=0.3,
                       mu_tau_index=1, exact_rounds=batch)
    root = jax.random.PRNGKey(7)
    ref = ReferenceEngine(cfg, n_entities, root)
    for k, q, t in zip(keys, qs, ts):
        ref.process(int(k), float(q), float(t))

    step = jax.jit(make_step(cfg, "exact"))
    state = init_state(n_entities, len(cfg.taus))
    zs, ps = [], []
    for i in range(0, n_events, batch):
        ev = Event(key=jnp.asarray(keys[i:i + batch]),
                   q=jnp.asarray(qs[i:i + batch]),
                   t=jnp.asarray(ts[i:i + batch]),
                   valid=jnp.ones(batch, bool))
        state, info = step(state, ev, root)
        zs.append(np.asarray(info.z))
        ps.append(np.asarray(info.p))

    ref_agg = np.stack([e.agg for e in ref.ents])
    ref_vf = np.array([e.v_f for e in ref.ents])
    ref_lt = np.array([e.last_t for e in ref.ents])
    np.testing.assert_allclose(np.asarray(state.agg), ref_agg, rtol=2e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(state.v_f), ref_vf, rtol=2e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.last_t), ref_lt, rtol=1e-6)
    assert int(np.concatenate(zs).sum()) == ref.writes


def test_exact_engine_padding_mask():
    cfg = EngineConfig(taus=(60.0,), policy="unfiltered", exact_rounds=4)
    state = init_state(4, 1)
    step = jax.jit(make_step(cfg, "exact"))
    ev = Event(key=jnp.array([1, 1, 2, 3], jnp.int32),
               q=jnp.array([1.0, 2.0, 3.0, 4.0]),
               t=jnp.array([1.0, 2.0, 3.0, 4.0]),
               valid=jnp.array([True, True, True, False]))
    state, info = step(state, ev, jax.random.PRNGKey(0))
    assert int(info.writes) == 3
    assert not bool(info.z[3])
    assert np.asarray(state.agg)[3].sum() == 0.0


def test_fast_mode_matches_exact_across_batches():
    """With one event per key per batch, fast == exact exactly."""
    rng = np.random.default_rng(1)
    n_entities, batch, n_batches = 64, 32, 6
    cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=0.02,
                       policy="pp", exact_rounds=4)
    root = jax.random.PRNGKey(3)
    step_e = jax.jit(make_step(cfg, "exact"))
    step_f = jax.jit(make_step(cfg, "fast"))
    se = init_state(n_entities, 2)
    sf = init_state(n_entities, 2)
    t0 = 0.0
    for b in range(n_batches):
        keys = rng.choice(n_entities, size=batch, replace=False).astype(np.int32)
        ts = (t0 + np.sort(rng.uniform(1, 500, size=batch))).astype(np.float32)
        t0 = float(ts.max()) + 1.0
        ev = Event(key=jnp.asarray(keys),
                   q=jnp.asarray(rng.lognormal(0, 1, batch).astype(np.float32)),
                   t=jnp.asarray(ts), valid=jnp.ones(batch, bool))
        se, ie = step_e(se, ev, root)
        sf, if_ = step_f(sf, ev, root)
        np.testing.assert_array_equal(np.asarray(ie.z), np.asarray(if_.z))
        np.testing.assert_allclose(np.asarray(ie.p), np.asarray(if_.p),
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(se.agg), np.asarray(sf.agg),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(se.v_f), np.asarray(sf.v_f),
                               rtol=1e-4, atol=1e-6)


def test_fast_mode_folds_multiple_events_per_key():
    """Duplicate keys in one batch: final state must equal sequential folding
    of the same decisions (fast mode's decisions are batch-start; given those
    p/z, the fold must be exact)."""
    cfg = EngineConfig(taus=(100.0,), h=50.0, policy="unfiltered")
    state = init_state(2, 1)
    step = jax.jit(make_step(cfg, "fast"))
    ev = Event(key=jnp.array([0, 0, 0, 1], jnp.int32),
               q=jnp.array([1.0, 2.0, 3.0, 5.0]),
               t=jnp.array([10.0, 20.0, 30.0, 15.0]),
               valid=jnp.ones(4, bool))
    state, info = step(state, ev, jax.random.PRNGKey(0))
    # entity 0 decayed sum at t=30: 1*e^-20/100*... contributions at final t:
    expect_sum = 1.0 * np.exp(-20 / 100) + 2.0 * np.exp(-10 / 100) + 3.0
    np.testing.assert_allclose(float(state.agg[0, 0, 1]), expect_sum, rtol=1e-5)
    np.testing.assert_allclose(float(state.agg[1, 0, 1]), 5.0, rtol=1e-6)
    assert float(state.last_t[0]) == 30.0
    # v_f fold with h: 3 persisted events
    expect_v = (np.exp(-20 / 50) + np.exp(-10 / 50) + 1.0)
    np.testing.assert_allclose(float(state.v_f[0]), expect_v, rtol=1e-5)


@pytest.mark.parametrize("mode", ["exact", "fast"])
def test_step_info_matches_oracle_per_event(mode):
    """The fused-kernel routing must leave make_step's *outputs* unchanged:
    per-event p / z / lam / decision-time features pinned to the per-event
    oracle (exact mode; fast mode is pinned on a conflict-free stream where
    batch-start decisions coincide with sequential ones)."""
    rng = np.random.default_rng(42)
    n_entities, batch, n_batches = 24, 16, 6
    cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=0.02, alpha=1.0,
                       policy="pp_vr", mu_tau_index=1, exact_rounds=batch)
    root = jax.random.PRNGKey(13)
    ref = ReferenceEngine(cfg, n_entities, root)
    step = jax.jit(make_step(cfg, mode))
    state = init_state(n_entities, len(cfg.taus))

    t0 = 0.0
    for b in range(n_batches):
        if mode == "fast":  # conflict-free batches: fast == exact == oracle
            keys = rng.choice(n_entities, size=batch,
                              replace=False).astype(np.int32)
        else:
            keys = rng.choice(n_entities, size=batch).astype(np.int32)
        ts = (t0 + np.sort(rng.uniform(1, 400, size=batch))).astype(np.float32)
        t0 = float(ts.max()) + 1.0
        qs = rng.lognormal(3, 1, batch).astype(np.float32)

        # oracle decision-time features (pre-update, full [cnt,sum,mean,std])
        want_feats = []
        order = np.lexsort((ts, keys)) if mode == "exact" else np.arange(batch)
        ps, zs, lams = np.zeros(batch), np.zeros(batch, bool), np.zeros(batch)
        for i in order:
            e = ref.ents[keys[i]]
            agg_now = (e.agg * np.exp(-np.clip(ts[i] - e.last_t, 0, None)
                                      / ref.taus)[:, None]
                       if math.isfinite(e.last_t) else np.zeros_like(e.agg))
            cnt = np.maximum(agg_now[:, 0], 1e-12)
            mean = agg_now[:, 1] / cnt
            var = np.maximum(agg_now[:, 2] / cnt - mean ** 2, 0.0)
            want_feats.append((i, np.concatenate(
                [agg_now[:, 0], agg_now[:, 1], mean, np.sqrt(var)])))
            ps[i], zs[i], lams[i] = ref.process(int(keys[i]), float(qs[i]),
                                                float(ts[i]))

        ev = Event(key=jnp.asarray(keys), q=jnp.asarray(qs),
                   t=jnp.asarray(ts), valid=jnp.ones(batch, bool))
        state, info = step(state, ev, root)
        np.testing.assert_array_equal(np.asarray(info.z), zs)
        np.testing.assert_allclose(np.asarray(info.p), ps, rtol=2e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(info.lam_hat), lams, rtol=2e-4)
        T = len(cfg.taus)
        for i, feats in want_feats:
            got = np.asarray(info.features[i])
            np.testing.assert_allclose(got[:3 * T], feats[:3 * T],
                                       rtol=2e-3, atol=1e-3)
            # std suffers fp32 cancellation in sq/cnt - mean^2: error scales
            # with the mean magnitude, not the (possibly ~0) std itself.
            scale = 1.0 + np.abs(feats[2 * T:3 * T])
            err = np.abs(got[3 * T:] - feats[3 * T:])
            assert np.all(err <= 5e-3 * scale + 2e-2 * np.abs(feats[3 * T:])), \
                (got[3 * T:], feats[3 * T:])


@pytest.mark.parametrize("mode", ["exact", "fast"])
def test_run_stream_matches_per_batch_loop(mode):
    """The donated-buffer block driver must be a pure driver change: same
    final state and same per-event info as the per-batch dispatch loop,
    including the padded (non-block-multiple) tail."""
    from repro.core import run_stream
    rng = np.random.default_rng(3)
    n_events, n_entities, batch = 200, 16, 64   # 200 % 64 != 0 -> padded tail
    keys, qs, ts = _make_stream(rng, n_events, n_entities)
    cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=0.05,
                       policy="pp", exact_rounds=32)
    root = jax.random.PRNGKey(5)

    step = jax.jit(make_step(cfg, mode))
    state_l = init_state(n_entities, len(cfg.taus))
    zs, ps = [], []
    for i in range(0, n_events, batch):
        j = min(i + batch, n_events)
        pad = batch - (j - i)
        ev = Event(key=jnp.asarray(np.pad(keys[i:j], (0, pad))),
                   q=jnp.asarray(np.pad(qs[i:j], (0, pad))),
                   t=jnp.asarray(np.pad(ts[i:j], (0, pad))),
                   valid=jnp.asarray(np.pad(np.ones(j - i, bool), (0, pad))))
        state_l, info = step(state_l, ev, root)
        zs.append(np.asarray(info.z[:j - i]))
        ps.append(np.asarray(info.p[:j - i]))

    state_s, info_s = run_stream(cfg, init_state(n_entities, len(cfg.taus)),
                                 keys, qs, ts, batch=batch, mode=mode,
                                 rng=root)
    np.testing.assert_array_equal(np.asarray(info_s.z), np.concatenate(zs))
    np.testing.assert_allclose(np.asarray(info_s.p), np.concatenate(ps),
                               rtol=1e-6)
    assert int(info_s.writes) == int(np.concatenate(zs).sum())
    for a, b, name in zip(state_l, state_s, state_l._fields):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   err_msg=name)


def test_exact_padding_does_not_consume_round_slots():
    """key=0/t=0 padding in a partial block must not occupy entity 0's early
    rounds: its real events would silently overflow exact_rounds and drop."""
    from repro.core import run_stream
    n = 5
    keys = np.zeros(n, np.int32)                       # all events on key 0
    ts = np.arange(1, n + 1, dtype=np.float32)
    qs = np.ones(n, np.float32)
    cfg = EngineConfig(taus=(60.0,), policy="unfiltered", exact_rounds=8)
    # batch=16 -> 11 padding lanes with key 0, t 0 that sort ahead of the
    # real events unless padding is segregated.
    state, info = run_stream(cfg, init_state(2, 1), keys, qs, ts,
                             batch=16, mode="exact",
                             rng=jax.random.PRNGKey(0))
    assert int(info.writes) == n
    assert np.asarray(info.z).all()
    np.testing.assert_allclose(float(state.last_t[0]), float(ts[-1]))


@pytest.mark.parametrize("chunk", [8, 256])
def test_exact_compaction_matches_masked_schedule(chunk):
    """The segment-compacted round schedule is a pure re-packing of the same
    per-lane kernel work: decisions and state must be *bit-identical* to the
    O(rounds x B) masked reference, including padded lanes and key skew.
    (The derived std feature may differ by 1 ulp: XLA reassociates the
    sqrt(var) tail differently across the two compiled programs.)"""
    rng = np.random.default_rng(9)
    n_events, n_entities, batch = 384, 16, 128
    keys, qs, ts = _make_stream(rng, n_events, n_entities)
    cfg = EngineConfig(taus=(60.0, 3600.0), h=600.0, budget=0.01, alpha=1.0,
                       policy="pp_vr", mu_tau_index=1, exact_rounds=48)
    root = jax.random.PRNGKey(21)
    step_c = jax.jit(make_step(cfg, "exact", exact_chunk=chunk))
    step_m = jax.jit(make_step(cfg, "exact", exact_impl="masked"))
    st_c = init_state(n_entities, len(cfg.taus))
    st_m = init_state(n_entities, len(cfg.taus))
    for i in range(0, n_events, batch):
        nv = batch - (8 if i == 0 else 0)       # first batch has padded tail
        ev = Event(key=jnp.asarray(keys[i:i + batch]),
                   q=jnp.asarray(qs[i:i + batch]),
                   t=jnp.asarray(ts[i:i + batch]),
                   valid=jnp.arange(batch) < nv)
        st_c, ic = step_c(st_c, ev, root)
        st_m, im = step_m(st_m, ev, root)
        np.testing.assert_array_equal(np.asarray(ic.z), np.asarray(im.z))
        np.testing.assert_array_equal(np.asarray(ic.p), np.asarray(im.p))
        np.testing.assert_array_equal(np.asarray(ic.lam_hat),
                                      np.asarray(im.lam_hat))
        np.testing.assert_allclose(np.asarray(ic.features),
                                   np.asarray(im.features),
                                   rtol=1e-6, atol=1e-6)
        assert int(ic.writes) == int(im.writes)
    for a, b, name in zip(st_c, st_m, st_c._fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_decision_reproducibility_across_batching():
    """Same events, different batch splits -> identical thinning decisions."""
    rng = np.random.default_rng(2)
    keys, qs, ts = _make_stream(rng, 128, 8)
    cfg = EngineConfig(taus=(60.0,), h=600.0, budget=0.01, policy="pp",
                       exact_rounds=64)
    root = jax.random.PRNGKey(11)

    def run(batch):
        step = jax.jit(make_step(cfg, "exact"))
        state = init_state(8, 1)
        allz = []
        for i in range(0, 128, batch):
            ev = Event(key=jnp.asarray(keys[i:i + batch]),
                       q=jnp.asarray(qs[i:i + batch]),
                       t=jnp.asarray(ts[i:i + batch]),
                       valid=jnp.ones(batch, bool))
            state, info = step(state, ev, root)
            allz.append(np.asarray(info.z))
        return np.concatenate(allz)

    np.testing.assert_array_equal(run(16), run(64))


def _dense_fast_step(cfg, state, ev, rng):
    """Fast mode with the table-wide fold it had before the segment fold:
    [num_e + 1] accumulators and a ``where`` over every row.  The oracle of
    ``test_fast_fold_matches_dense_fold``."""
    from repro.core import estimators, intensity, thinning
    from repro.core import engine as core_engine
    from repro.core.types import ProfileState, StepInfo
    taus = jnp.asarray(cfg.taus, jnp.float32)
    num_e = state.num_entities
    safe_key = jnp.where(ev.valid, ev.key, 0)
    u = thinning.uniform_for_events(rng, safe_key,
                                    core_engine._seq_bits(ev.t))
    (_, _, _, z, p, feats, lam, _, _) = core_engine._fused_rmw(
        cfg, taus, state, safe_key, ev.q, ev.t, u, ev.valid)

    t_star = jnp.full((num_e + 1,), -jnp.inf).at[
        jnp.where(z, ev.key, num_e)].max(ev.t)[:num_e]
    wrote = jnp.isfinite(t_star)
    t_ref = jnp.where(wrote, t_star, 0.0)
    inv_p = jnp.where(z, 1.0 / p, 0.0)
    w_v = inv_p * intensity.decay(t_ref[safe_key] - ev.t, cfg.h)
    v_add = jnp.zeros((num_e + 1,)).at[
        jnp.where(z, ev.key, num_e)].add(w_v)[:num_e]
    v_f_new = jnp.where(
        wrote, v_add + intensity.decay(t_star - state.last_t, cfg.h)
        * state.v_f, state.v_f)
    beta_ev = intensity.decay((t_ref[safe_key] - ev.t)[:, None], taus)
    contrib = (inv_p[:, None, None] * beta_ev[:, :, None] *
               jnp.stack([jnp.ones_like(ev.q), ev.q, ev.q * ev.q],
                         -1)[:, None, :])
    agg_add = jnp.zeros((num_e + 1,) + state.agg.shape[1:]).at[
        jnp.where(z, ev.key, num_e)].add(contrib)[:num_e]
    agg_new = jnp.where(
        wrote[:, None, None],
        agg_add + estimators.decay_to(state.agg, state.last_t, t_star, taus),
        state.agg)
    last_t_new = jnp.where(wrote, t_star, state.last_t)

    tf_star = jnp.full((num_e + 1,), -jnp.inf).at[
        jnp.where(ev.valid, ev.key, num_e)].max(ev.t)[:num_e]
    saw = jnp.isfinite(tf_star)
    tf_ref = jnp.where(saw, tf_star, 0.0)
    w_full = jnp.where(ev.valid, 1.0, 0.0) * intensity.decay(
        tf_ref[safe_key] - ev.t, cfg.h)
    vfull_add = jnp.zeros((num_e + 1,)).at[
        jnp.where(ev.valid, ev.key, num_e)].add(w_full)[:num_e]
    v_full_new = jnp.where(
        saw, vfull_add + intensity.decay(tf_star - state.last_t_full, cfg.h)
        * state.v_full, state.v_full)
    last_t_full_new = jnp.where(saw, tf_star, state.last_t_full)

    state = ProfileState(last_t=last_t_new, v_f=v_f_new, agg=agg_new,
                         v_full=v_full_new, last_t_full=last_t_full_new)
    return state, StepInfo(z=z, p=p, lam_hat=lam, features=feats,
                           writes=jnp.sum(z).astype(jnp.int32))


@pytest.mark.parametrize("n_entities", [12, 5000])
@pytest.mark.parametrize("policy", POLICIES)
def test_fast_fold_matches_dense_fold(policy, n_entities):
    """The fast fold touches only the batch's keys, yet gives what the
    table-wide fold gives: the same decisions, the same state within float32
    reordering, and untouched rows bit for bit.  Keys repeat within a batch
    (Zipf), some lanes are invalid and later batches end in padding, with
    fewer keys than lanes and with many more."""
    rng = np.random.default_rng(17)
    batch, n_batches = 64, 24
    keys, qs, ts = _make_stream(rng, batch * n_batches, n_entities,
                                skew=1.1)
    cfg = EngineConfig(taus=(60.0, 3600.0, 86400.0), h=600.0, budget=2e-4,
                       alpha=1.0, policy=policy, fixed_rate=0.3,
                       mu_tau_index=1)
    root = jax.random.PRNGKey(29)
    step = jax.jit(make_step(cfg, "fast"))
    dense = jax.jit(functools.partial(_dense_fast_step, cfg))
    state = init_state(n_entities, len(cfg.taus))
    thinned = 0
    for b in range(n_batches):
        sl = slice(b * batch, (b + 1) * batch)
        valid = rng.random(batch) > 0.15
        if b >= n_batches // 2:
            valid[batch - 9:] = False           # a padded tail
        k = np.where(valid, keys[sl], 0).astype(np.int32)
        ev = Event(key=jnp.asarray(k), q=jnp.asarray(qs[sl]),
                   t=jnp.asarray(np.where(valid, ts[sl], 0.0)
                                 .astype(np.float32)),
                   valid=jnp.asarray(valid))
        got, info = step(state, ev, root)
        want, winfo = dense(state, ev, root)
        for f in ("z", "p", "lam_hat", "features", "writes"):
            np.testing.assert_array_equal(np.asarray(getattr(info, f)),
                                          np.asarray(getattr(winfo, f)),
                                          err_msg=f)
        z = np.asarray(info.z)
        persisted = np.isin(np.arange(n_entities), k[z])
        seen = np.isin(np.arange(n_entities), k[valid])
        for name, a, w, before in zip(got._fields, got, want, state):
            a, w, before = map(np.asarray, (a, w, before))
            np.testing.assert_allclose(a, w, rtol=1e-6, atol=0,
                                       err_msg=name)
            kept = ~(seen if name.endswith("_full") else persisted)
            np.testing.assert_array_equal(a[kept], before[kept],
                                          err_msg=name)
        thinned += int((valid & ~z).sum())
        state = got
    assert thinned > 0 or policy in ("full", "unfiltered")
