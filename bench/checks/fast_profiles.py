"""The check of a deployment whose events each update one key's profile,
in the fast mode, into one dense table and one durable partition
(``bench/compare.py`` says what each number measures).

``follow`` chooses the keys the comparison follows; ``check`` holds what
the timed path produced against the configuration's reference, run over
those keys; ``control`` puts that reference, computed in its control
precision, in the program's place, and returns the same numbers.
"""
from __future__ import annotations

import numpy as np

from bench import compare


def follow(config: dict, traffic: dict, stream, seed: int) -> np.ndarray:
    """Sorted ids of the keys followed: the traffic's ``key_share`` of all
    keys, drawn from the seed, and the heaviest keys of the stream."""
    return compare.sample_keys(seed, int(config["stream"]["n_keys"]),
                               float(traffic["key_share"]), stream.key)


def check(reference, config: dict, traffic: dict, win, stream, seed32: int,
          keys_followed, limits: dict) -> tuple:
    """The comparison's numbers, and what the diagnostics line shows.
    Where the window made several passes over the stream, each pass's
    outputs and store are held to the reference, and each number is the
    worst pass's; the final device state is the last pass's."""
    eng = reference.EngineParams.from_config(config["engine"])
    pos = win.sample_pos
    keys, q, t = stream.key[pos], stream.q[pos], stream.t[pos]
    u = reference.uniforms(seed32, keys, t)
    ref = reference.FastReference(eng, keys_followed)
    dec = ref.run(keys, q, t, u, win.batch_id)
    rows = ref.rows(keys)
    n_keys = int(config["stream"]["n_keys"])
    n_taus = len(eng.taus)
    p_limit = limits["p_rel_err"]

    def held(out: dict) -> tuple:
        fol = compare.follow(dec, u, rows, len(keys_followed), out["z"],
                             p_limit)
        numbers = compare.decisions(dec, fol, out["p"], out["z"],
                                    out["lam"], u, p_limit)
        hyd = durable_rows(out["store_dir"], n_keys, n_taus)
        at = {f: v[keys_followed] for f, v in hyd.items()}
        numbers["store_rel_err"] = compare.rows_gap(
            ref, fol, at, names=("last_t", "v_f", "agg"))
        return numbers, fol, hyd

    numbers, fol, hyd = held(dict(p=win.p, z=win.z, lam=win.lam,
                                  store_dir=win.store_dir))
    for out in win.passes:
        for k, v in held(out)[0].items():
            numbers[k] = max(numbers[k], v)
    whole = compare.follow(dec, u, rows, len(keys_followed), win.z,
                           p_limit, writes=np.inf)
    whole = compare.decisions(dec, whole, win.p, win.z, win.lam, u, p_limit)
    if win.state is not None:
        final = {f: v[keys_followed] for f, v in win.state.items()}
        numbers["state_rel_err"] = compare.rows_gap(ref, fol, final)
        numbers["full_rel_err"] = compare.full_gap(ref, final)
        off = np.zeros(n_keys, bool)
        for f in ("last_t", "v_f", "agg"):
            a, b = hyd[f], win.state[f]
            diff = a.view(np.uint32) != b.view(np.uint32)
            off |= diff.reshape(n_keys, -1).any(axis=1)
        numbers["store_rows_off"] = int(off.sum())
    if win.scores is not None:
        scorer = reference.Scorer(**win.scorer)
        numbers["score_err"] = compare.scores(dec, fol, scorer, win.scores)
        numbers["order_off"] = int(win.order_off)
    info = {"compared_events": int(fol.event_in.sum()),
            "sampled_events": int(len(pos)),
            "followed_keys": int(len(keys_followed)),
            "keys_left": fol.keys_left, "flips_forgiven": fol.forgiven,
            "passes": 1 + len(win.passes), "whole_history": whole}
    return numbers, info


def durable_rows(store_dir: str, n_keys: int, n_taus: int) -> dict:
    """Every key's row as the durable store in ``store_dir`` holds it."""
    import jax
    from repro.streaming.durable import open_partition_stores
    from repro.streaming.persistence import hydrate_state

    stores = open_partition_stores(store_dir, 1)
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            hyd = hydrate_state(stores, n_keys, n_taus)
            return {f: np.asarray(getattr(hyd, f)) for f in hyd._fields}
    finally:
        for s in stores:
            s.close()


def control(reference, config: dict, traffic: dict, stream, seed: int,
            followed, limits: dict, dtype=None, exp=np.exp,
            writes: float = compare.WRITES) -> dict:
    """The comparison's numbers with ``FastReference(dtype, exp)``
    standing in for the program (``dtype`` by default the reference's
    ``CONTROL`` precision), each key followed through ``writes`` of its
    writes, with full batches as the serve cell's dispatch log."""
    dtype = reference.CONTROL if dtype is None else dtype
    eng_cfg = config["engine"]
    p_limit = limits["p_rel_err"]
    sampled = np.zeros(int(config["stream"]["n_keys"]), bool)
    sampled[followed] = True
    pos = np.flatnonzero(sampled[stream.key])
    keys, q, t = stream.key[pos], stream.q[pos], stream.t[pos]
    batch = int(traffic.get("batch", eng_cfg["batch"]))
    batch_id = pos // batch
    seed32 = reference.engine_key_data(seed)
    u = reference.uniforms(seed32, keys, t)
    eng = reference.EngineParams.from_config(eng_cfg)
    ref = reference.FastReference(eng, followed)
    dec = ref.run(keys, q, t, u, batch_id)
    low = reference.FastReference(eng, followed, dtype, exp)
    got = low.run(keys, q, t, u, batch_id)
    fol = compare.follow(dec, u, ref.rows(keys), len(followed), got.z,
                         p_limit, writes)
    numbers = compare.decisions(dec, fol, got.p, got.z, got.lam, u, p_limit)
    cols = {c: getattr(low, c) for c in ("last_t", "v_f", "agg", "v_full",
                                         "last_t_full")}
    numbers["store_rel_err"] = compare.rows_gap(
        ref, fol, cols, names=("last_t", "v_f", "agg"))
    if traffic["driver"] == "ingest":
        numbers["state_rel_err"] = compare.rows_gap(ref, fol, cols)
        numbers["full_rel_err"] = compare.full_gap(ref, cols)
        numbers["store_rows_off"] = 0
    else:
        rng = np.random.default_rng([seed, 0x5C0E])
        F, H = 4 * len(eng.taus), int(traffic["scorer_hidden"])
        scorer = reference.Scorer(
            w1=rng.standard_normal((F, H)) / F ** 0.5, b1=np.zeros(H),
            w2=rng.standard_normal((H, 1)) / H ** 0.5, b2=np.zeros(1),
            mu=np.zeros(F), sd=np.ones(F))
        low_scores, _ = reference.score(scorer, got.features.astype(
            dtype).astype(np.float64))
        numbers["score_err"] = compare.scores(dec, fol, scorer, low_scores)
        numbers["order_off"] = 0
    return numbers
