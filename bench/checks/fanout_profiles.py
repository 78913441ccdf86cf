"""The check of a deployment whose events each update K profiles, rows of
one dense table, in the fast mode, into one durable partition
(``bench/compare.py`` says what each number measures).

The comparison runs on lanes, one per (event, profile) pair: a lane is
followed where its row is, and each lane draws its uniform on (row, event
id) in the reference (``bench/nexmark/reference.py``).  ``follow`` chooses
the rows; ``check`` holds what the timed path produced against the
reference over those rows; ``control`` puts the reference, computed in its
control precision, in the program's place and returns the same numbers.
"""
from __future__ import annotations

import numpy as np

from bench import compare
from bench.checks.fast_profiles import durable_rows


def follow(config: dict, traffic: dict, stream, seed: int) -> np.ndarray:
    """Sorted rows followed: the traffic's ``key_share`` of each family's
    rows (bidders, then auctions), drawn from the seed, and the
    ``compare.HEAVY`` rows with the most lanes in the stream."""
    rng = np.random.default_rng([seed, 0x5EED])
    share = float(traffic["key_share"])
    cut = stream.n_person_rows
    drawn = [np.flatnonzero(rng.random(cut) < share),
             cut + np.flatnonzero(rng.random(stream.n_rows - cut) < share)]
    counts = np.bincount(stream.key.reshape(-1), minlength=stream.n_rows)
    top = np.argsort(counts, kind="stable")[::-1][:compare.HEAVY]
    return np.union1d(np.concatenate(drawn), top)


def lanes(reference, stream, seed32: int, pos: np.ndarray) -> tuple:
    """(rows, q, t, u) of the lanes at ``pos``."""
    ev = pos // stream.key.shape[1]
    rows = stream.key.reshape(-1)[pos]
    return (rows, stream.q[ev], stream.t[ev],
            reference.uniforms(seed32, rows, stream.ctr[ev]))


def check(reference, config: dict, traffic: dict, win, stream, seed32: int,
          rows_followed, limits: dict) -> tuple:
    """The comparison's numbers, and what the diagnostics line shows; each
    pass is held to the reference and each number is the worst pass's, as
    in ``fast_profiles.check``."""
    eng = reference.EngineParams.from_config(config["engine"])
    rows, q, t, u = lanes(reference, stream, seed32, win.sample_pos)
    ref = reference.FastReference(eng, rows_followed)
    dec = ref.run(rows, q, t, u, win.batch_id)
    at = ref.rows(rows)
    n_taus = len(eng.taus)
    p_limit = limits["p_rel_err"]

    def held(out: dict) -> tuple:
        fol = compare.follow(dec, u, at, len(rows_followed), out["z"],
                             p_limit)
        numbers = compare.decisions(dec, fol, out["p"], out["z"],
                                    out["lam"], u, p_limit)
        hyd = durable_rows(out["store_dir"], stream.n_rows, n_taus)
        mine = {f: v[rows_followed] for f, v in hyd.items()}
        numbers["store_rel_err"] = compare.rows_gap(
            ref, fol, mine, names=("last_t", "v_f", "agg"))
        return numbers, fol, hyd

    numbers, fol, hyd = held(dict(p=win.p, z=win.z, lam=win.lam,
                                  store_dir=win.store_dir))
    for out in win.passes:
        for k, v in held(out)[0].items():
            numbers[k] = max(numbers[k], v)
    whole = compare.follow(dec, u, at, len(rows_followed), win.z, p_limit,
                           writes=np.inf)
    whole = compare.decisions(dec, whole, win.p, win.z, win.lam, u, p_limit)
    final = {f: v[rows_followed] for f, v in win.state.items()}
    numbers["state_rel_err"] = compare.rows_gap(ref, fol, final)
    numbers["full_rel_err"] = compare.full_gap(ref, final)
    off = np.zeros(stream.n_rows, bool)
    for f in ("last_t", "v_f", "agg"):
        diff = hyd[f].view(np.uint32) != win.state[f].view(np.uint32)
        off |= diff.reshape(stream.n_rows, -1).any(axis=1)
    numbers["store_rows_off"] = int(off.sum())
    st = win.sink_stats
    # the comparison's events are lanes and its keys rows
    info = {"compared_events": int(fol.event_in.sum()),
            "sampled_events": int(len(win.sample_pos)),
            "followed_keys": int(len(rows_followed)),
            "table_rows": int(stream.n_rows),
            "keys_left": fol.keys_left, "flips_forgiven": fol.forgiven,
            "passes": 1 + len(win.passes), "whole_history": whole,
            "fanout_lanes_per_event": (st["fanout_lanes"] / win.events
                                       if "fanout_lanes" in st else None)}
    done = win.events // (1 + len(win.passes))
    info.update(lane_counts(stream.key[:done], stream.t[:done],
                            int(config["engine"]["batch"])))
    return numbers, info


def lane_counts(key: np.ndarray, t: np.ndarray, batch: int) -> dict:
    """What the batch layout does with a pass's ``[N, K]`` keys:
    ``tied_events``, the lanes whose (row, t) equals an earlier lane's in
    the same micro-batch of ``batch`` events (lanes that would draw one
    uniform if draws were keyed on time bits), and ``max_key_lanes``, the
    most lanes one row holds in a micro-batch."""
    n, k = key.shape
    code = (key.astype(np.int64) << 32) | np.repeat(
        np.asarray(t, np.float32).view(np.uint32)[:, None], k, axis=1)
    code = np.pad(code, [(0, -n % batch), (0, 0)], constant_values=-1)
    code = np.sort(code.reshape(-1, batch * k), axis=1)
    real = code >= 0
    tied = int((real[:, 1:] & (code[:, 1:] == code[:, :-1])).sum())
    row = code >> 32
    start = np.ones(row.shape, bool)
    start[:, 1:] = row[:, 1:] != row[:, :-1]
    at = np.flatnonzero(start)
    runs = np.diff(np.append(at, row.size))[real.reshape(-1)[at]]
    return {"tied_events": tied, "max_key_lanes": int(runs.max(initial=0))}


def control(reference, config: dict, traffic: dict, stream, seed: int,
            followed, limits: dict, dtype=None, exp=np.exp,
            writes: float = compare.WRITES) -> dict:
    """The comparison's numbers with ``FastReference(dtype, exp)``
    standing in for the program (``dtype`` by default the reference's
    ``CONTROL`` precision), each row followed through ``writes`` of its
    writes."""
    dtype = reference.CONTROL if dtype is None else dtype
    eng_cfg = config["engine"]
    sampled = np.zeros(stream.n_rows, bool)
    sampled[followed] = True
    pos = np.flatnonzero(sampled[stream.key.reshape(-1)])
    rows, q, t, u = lanes(reference, stream,
                          reference.engine_key_data(seed), pos)
    batch_id = pos // stream.key.shape[1] // int(eng_cfg["batch"])
    eng = reference.EngineParams.from_config(eng_cfg)
    ref = reference.FastReference(eng, followed)
    dec = ref.run(rows, q, t, u, batch_id)
    low = reference.FastReference(eng, followed, dtype, exp)
    got = low.run(rows, q, t, u, batch_id)
    fol = compare.follow(dec, u, ref.rows(rows), len(followed), got.z,
                         limits["p_rel_err"], writes)
    numbers = compare.decisions(dec, fol, got.p, got.z, got.lam, u,
                                limits["p_rel_err"])
    cols = {c: getattr(low, c) for c in ("last_t", "v_f", "agg", "v_full",
                                         "last_t_full")}
    numbers["store_rel_err"] = compare.rows_gap(
        ref, fol, cols, names=("last_t", "v_f", "agg"))
    numbers["state_rel_err"] = compare.rows_gap(ref, fol, cols)
    numbers["full_rel_err"] = compare.full_gap(ref, cols)
    numbers["store_rows_off"] = 0
    return numbers
