"""The comparison that decides a run's ``correct``.

What the timed path produced is held against ``reference.FastReference``
(float64) on a sample of keys: a share drawn from the seed, and the keys
with the most events in the stream.  The events of a sampled key that the
window processed are compared in stream order, through its first
``WRITES`` writes.

A key leaves the comparison after an event whose decision lies within
float32's reach of either outcome.  Two rules say where that is.  Where
float32's cancellation error in the variance of Eq. 4, read off the
reference's own state (``p_lo``, ``p_hi``), could move p by more than
``ILL_RTOL``, the key leaves before that event.  Where the program persisted
an event the reference did not, or the other way round, and the uniform lay
within the p limit of the reference's p, the flip is float32's and the key
leaves after that event; a flip farther out counts in ``z_flips``.

Float32 carries relative errors of some 1e-2 on the longest histories: the
thinning probability feeds back into the intensity state through 1/p on
every write, and that loop carries a rounding of one ulp forward instead of
forgetting it.  Two float32 copies of the reference that differ only in the
last ulp of ``exp`` read such gaps, of different sizes, on the same stream
(``bench/witness.py``).

Numbers, each held to its own limit (``bench/limits/<cell>.json``):

p_rel_err      largest |p - p_ref| / p_ref over compared events
lam_rel_err    largest |lam - lam_ref| / lam_ref
z_flips        compared events whose persist decision differs
state_rel_err  largest relative gap of a compared key's final device row
full_rel_err   largest relative gap of the full-stream columns (``v_full``,
               ``last_t_full``) of every sampled key's final device row
store_rel_err  largest relative gap of a compared key's durable row
store_rows_off rows where the durable store and the device state differ
               bit for bit (every key)
score_err      largest |score - score_ref| / its bfloat16 bound
order_off      requests answered out of arrival order or not at all
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import numpy as np

from bench import reference as ref

ILL_RTOL = 1e-4
# the keys with the most events are always followed: the hottest carries
# some 9% of an iiot800k stream, which a 5% sample would mostly miss
HEAVY = 16
# writes of one key the comparison follows: float32's gap to float64 grows
# geometrically with a key's writes (see the module's docstring)
WRITES = 32
# relative gaps are taken against max(|ref|, FLOOR): decayed aggregates may
# sit near float32's smallest normal, which the chip flushes to zero
FLOOR = 1e-30


def sample_keys(seed: int, n_keys: int, share: float, stream_keys
                ) -> np.ndarray:
    """Sorted ids of the keys the comparison follows: a share of all keys
    drawn from the seed, and the ``HEAVY`` keys with the most events in
    ``stream_keys``."""
    rng = np.random.default_rng([seed, 0x5EED])
    drawn = np.flatnonzero(rng.random(n_keys) < share)
    counts = np.bincount(stream_keys, minlength=n_keys)
    top = np.argsort(counts, kind="stable")[::-1][:HEAVY]
    return np.union1d(drawn, top)


def rel_gap(a, r) -> float:
    a = np.asarray(a, np.float64)
    r = np.asarray(r, np.float64)
    same = (a == r)                    # equal infinities included
    with np.errstate(invalid="ignore"):
        gap = np.abs(a - r) / np.maximum(np.abs(r), FLOOR)
    gap = np.where(same, 0.0, gap)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap.max()) if gap.size else 0.0


@dataclasses.dataclass
class Followed:
    """Which sampled events and keys stay in the comparison."""
    event_in: np.ndarray     # [n] bool, per sampled event
    key_in: np.ndarray       # [K] bool, per sampled key (to the end)
    keys_left: int
    forgiven: int            # flips within the p limit of the boundary


def writes_before(rows: np.ndarray, z: np.ndarray) -> np.ndarray:
    """Per event, the writes of its key earlier in the sequence."""
    order = np.argsort(rows, kind="stable")
    w = np.asarray(z, np.int64)[order]
    done = np.cumsum(w) - w
    starts = np.flatnonzero(np.r_[True, np.diff(rows[order]) != 0])
    out = np.empty(len(rows), np.int64)
    out[order] = done - np.repeat(done[starts], np.diff(np.r_[starts,
                                                               len(rows)]))
    return out


def follow(dec: ref.Decisions, u: np.ndarray, rows: np.ndarray,
           n_rows: int, prog_z, p_limit: float,
           writes: float = WRITES) -> Followed:
    """``writes``: how many of its writes a key is followed through
    (``np.inf`` for whole histories)."""
    p = dec.p
    n = len(p)
    ill = np.maximum(dec.p_hi - p, p - dec.p_lo)
    first_ill = np.full(n_rows, n, np.int64)
    idx = np.flatnonzero(ill > ILL_RTOL * p)
    np.minimum.at(first_ill, rows[idx], idx)
    at = np.arange(n)
    capped = np.bincount(rows, weights=dec.z, minlength=n_rows) >= writes
    before = (at < first_ill[rows]) & (writes_before(rows, dec.z) < writes)
    flip = before & (np.asarray(prog_z, bool) != dec.z)
    near = np.abs(u - p) <= p_limit * p + ill
    last = np.full(n_rows, n, np.int64)
    idx = np.flatnonzero(flip)
    np.minimum.at(last, rows[idx], idx)
    event_in = before & (at <= last[rows])
    key_in = (first_ill == n) & (last == n) & ~capped
    return Followed(event_in, key_in, int((~key_in).sum()),
                    int((flip & near & event_in).sum()))


def decisions(dec: ref.Decisions, f: Followed, p, z, lam, u,
              p_limit: float) -> dict:
    m = f.event_in
    if not m.any():
        return {"p_rel_err": 0.0, "lam_rel_err": 0.0, "z_flips": 0}
    flip = m & (np.asarray(z, bool) != dec.z)
    near = np.abs(u - dec.p) <= p_limit * dec.p + np.maximum(
        dec.p_hi - dec.p, dec.p - dec.p_lo)
    return {"p_rel_err": rel_gap(np.asarray(p)[m], dec.p[m]),
            "lam_rel_err": rel_gap(np.asarray(lam)[m], dec.lam[m]),
            "z_flips": int((flip & ~near).sum())}


def rows_gap(reference: ref.FastReference, f: Followed, cols: dict,
             names=("last_t", "v_f", "agg", "v_full", "last_t_full")
             ) -> float:
    """Largest relative gap of the followed keys' rows ``cols[name]``
    (indexed like ``reference.keys``) against the reference's state."""
    k = f.key_in
    return max(rel_gap(np.asarray(cols[c])[k], getattr(reference, c)[k])
               for c in names)


def full_gap(reference: ref.FastReference, cols: dict) -> float:
    """Largest relative gap of every followed key's full-stream columns,
    which fold each event with weight 1 and so carry no feedback."""
    return max(rel_gap(cols[c], getattr(reference, c))
               for c in ("v_full", "last_t_full"))


def scores(dec: ref.Decisions, f: Followed, scorer: ref.Scorer,
           prog_scores) -> float:
    m = f.event_in
    if not m.any():
        return 0.0
    want, bound = ref.score(scorer, dec.features[m])
    return float((np.abs(np.asarray(prog_scores, np.float64)[m] - want)
                  / bound).max())


def load_limits(bench_dir: str, cell: str) -> dict:
    with open(os.path.join(bench_dir, "limits", f"{cell}.json")) as fh:
        return json.load(fh)["limits"]


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a number without a limit, or
    a limit without its number, is not correct."""
    out, ok = {}, set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(name), limits.get(name)
        out[name] = {"value": v, "limit": lim}
        if v is None or lim is None or not v <= lim:
            ok = False
    return ok, out


def print_lines(table: dict, file=sys.stderr) -> None:
    for name, e in table.items():
        v, lim = e["value"], e["limit"]
        verdict = "ok" if (v is not None and lim is not None
                           and v <= lim) else "FAIL"
        print(f"check {name} {v!r} limit {lim!r} {verdict}", file=file,
              flush=True)
