"""Plain reference of the feature engine's fast mode and of the scorer.

Written from the paper's equations (arXiv 2606.16981: Eq. 2/4 inclusion,
Eq. 5 intensity, the Horvitz-Thompson decayed aggregates of §3.3) and the
fast mode's documented semantics, in straightforward NumPy; it imports
nothing of the program under test.

Fast mode: the events of one micro-batch (``batch`` consecutive events of
the stream) all decide against the profile state as it stood at the start
of the batch; afterwards each key's persisted events fold into its row,
decayed to the key's last persisted event time ``t*``:

    agg(t*) = sum_i z_i/p_i * exp(-(t* - t_i)/tau) [1, q_i, q_i^2]
              + exp(-(t* - last_t)/tau) * agg(last_t)

and likewise ``v_f`` with bandwidth ``h``; the full-stream control column
(``v_full``, ``last_t_full``) folds every event with weight 1.  Keys are
independent, so the reference can follow any subset of keys exactly, given
each event's batch number.

``dtype`` is the arithmetic precision: float64 for the reference; the
control of the correctness check runs the same code in bfloat16.  Event
times are float32 in the stream, and stay float32 in the bfloat16 control
(float64 in the reference): a store of its profile columns in bfloat16 is
the step that would tempt a change to the program, not a coarser clock.
"""
from __future__ import annotations

import dataclasses

import jax
import ml_dtypes
import numpy as np

BFLOAT16 = np.dtype(ml_dtypes.bfloat16)


@dataclasses.dataclass(frozen=True)
class EngineParams:
    taus: tuple
    h: float
    budget: float               # write budget Lambda, writes / s / key
    alpha: float
    policy: str                 # pp (Eq. 2) or pp_vr (Eq. 4)
    mu_tau_index: int
    min_p: float

    @classmethod
    def from_config(cls, eng: dict) -> "EngineParams":
        return cls(taus=tuple(float(x) for x in eng["windows_s"]),
                   h=float(eng["kde_bandwidth_s"]),
                   budget=float(eng["write_budget_per_s"]),
                   alpha=float(eng["variance_alpha"]),
                   policy=eng["policy"],
                   mu_tau_index=int(eng["mu_tau_index"]),
                   min_p=float(eng["min_p"]))


def engine_key_data(seed: int) -> int:
    """32-bit thinning-RNG seed derived from the run's seed (the full seed
    is wider than the 32 bits a PRNG key takes)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def uniforms(seed32: int, keys: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Each event's thinning uniform: threefry keyed on (entity id, bit
    pattern of its float32 time), as the paper's counter-based draw.
    Computed on the host CPU backend."""
    cpu = jax.devices("cpu")[0]
    bits = np.ascontiguousarray(t, np.float32).view(np.uint32)
    with jax.default_device(cpu):
        root = jax.random.PRNGKey(seed32)

        def one(k, s):
            return jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(root, k), s), ())
        u = jax.jit(jax.vmap(one))(np.asarray(keys, np.uint32), bits)
        return np.asarray(u, np.float64)


@dataclasses.dataclass
class Decisions:
    """Per-event outputs, aligned with the events given to ``run``."""
    p: np.ndarray
    z: np.ndarray
    lam: np.ndarray
    features: np.ndarray        # [n, 4T]: count, sum, mean, std per tau
    # the p that float32 arithmetic could reach from this same state: the
    # range of Eq. 4 over the variance's float32 cancellation error
    p_lo: np.ndarray
    p_hi: np.ndarray


class FastReference:
    """Fast-mode engine over the keys ``keys`` (global entity ids)."""

    def __init__(self, eng: EngineParams, keys: np.ndarray,
                 dtype=np.float64, exp=np.exp):
        self.eng = eng
        self.exp = exp              # the state update's exponential
        self.dt = np.dtype(dtype)
        self.tdt = np.dtype(np.float64 if self.dt == np.float64
                            else np.float32)
        self.keys = np.asarray(keys, np.int64)
        n, T = len(self.keys), len(eng.taus)
        self.row_of = {int(k): i for i, k in enumerate(self.keys)}
        self.last_t = np.full(n, -np.inf, self.tdt)
        self.v_f = np.zeros(n, self.dt)
        self.agg = np.zeros((n, T, 3), self.dt)
        self.v_full = np.zeros(n, self.dt)
        self.last_t_full = np.full(n, -np.inf, self.tdt)
        self.taus = np.asarray(eng.taus, self.dt)

    def rows(self, keys: np.ndarray) -> np.ndarray:
        return np.fromiter((self.row_of[int(k)] for k in keys), np.int64,
                           len(keys))

    def run(self, keys, q, t, u, batch_id) -> Decisions:
        """Events in stream order; ``batch_id`` is each event's micro-batch
        (non-decreasing).  Returns their decisions and advances the state."""
        r_all = self.rows(keys)
        q = np.asarray(q, np.float32).astype(self.dt)
        t = np.asarray(t, np.float32).astype(self.tdt)
        u = np.asarray(u, np.float64)
        batch_id = np.asarray(batch_id)
        n, T = len(r_all), len(self.taus)
        out = Decisions(p=np.zeros(n), z=np.zeros(n, bool), lam=np.zeros(n),
                        features=np.zeros((n, 4 * T)), p_lo=np.zeros(n),
                        p_hi=np.zeros(n))
        cuts = np.flatnonzero(np.diff(batch_id)) + 1
        for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, n]):
            self._batch(r_all[lo:hi], q[lo:hi], t[lo:hi], u[lo:hi],
                        out, slice(lo, hi))
        return out

    def _batch(self, r, q, t, u, out: Decisions, sl: slice) -> None:
        e, dt_ = self.eng, self.dt
        one = dt_.type(1.0)
        lt, vf = self.last_t[r], self.v_f[r]
        fresh = ~np.isfinite(lt)
        dt = np.where(fresh, 0, np.maximum(t - lt, 0)).astype(dt_)
        beta = np.where(fresh[:, None], dt_.type(0.0),
                        self.exp(-dt[:, None] / self.taus[None, :]))
        agg_now = self.agg[r] * beta[:, :, None]
        cnt, sm, sq = agg_now[..., 0], agg_now[..., 1], agg_now[..., 2]
        safe = np.maximum(cnt, dt_.type(1e-12))
        mean = sm / safe
        var = np.maximum(sq / safe - mean * mean, 0)
        feats = np.concatenate([cnt, sm, mean, np.sqrt(var)], axis=1)

        beta_h = np.where(fresh, dt_.type(0.0),
                          self.exp(-dt / dt_.type(e.h)))
        lam = (one + beta_h * vf) / dt_.type(e.h)
        base = np.minimum(one, dt_.type(e.budget) / np.maximum(
            lam, dt_.type(1e-30)))
        p_lo = p_hi = None
        if e.policy == "pp_vr":
            j = e.mu_tau_index
            cold = cnt[:, j] < 1
            mu = np.where(cold, dt_.type(0.0), mean[:, j])
            sg = np.where(cold, dt_.type(1e8),
                          np.sqrt(var[:, j]) + dt_.type(1e-8))
            zs = np.clip((q - mu) / np.maximum(sg, dt_.type(1e-8)), -8, 8)
            b = np.clip(base, 1e-6, 1 - 1e-6)
            p = np.where(base >= 1 - 1e-6, one,
                         one / (one + ((one - b) / b) * self.exp(
                             -dt_.type(e.alpha) * zs)))
            p_lo, p_hi = self._p_reach(cnt[:, j], sm[:, j], sq[:, j], q,
                                       b, base)
        elif e.policy == "pp":
            p = base
        else:
            raise ValueError(f"reference covers pp and pp_vr, not "
                             f"{e.policy!r}")
        p = np.clip(p, dt_.type(e.min_p), one)
        z = u < p.astype(np.float64)
        out.p[sl], out.z[sl], out.lam[sl] = p, z, lam
        out.features[sl] = feats
        out.p_lo[sl] = p if p_lo is None else np.minimum(p_lo, p)
        out.p_hi[sl] = p if p_hi is None else np.maximum(p_hi, p)
        self._fold(r, q, t, p, z)

    def _p_reach(self, cnt, sm, sq, q, b, base):
        """Range of Eq. 4's p over float32's reach on this state.

        sigma_w comes from ``sumsq/cnt - mean^2``; where the marks' spread
        is small next to their mean that difference cancels, and float32
        keeps the variance only to some ulps of ``sumsq/cnt``.  Allowing
        16 of them (and 16 ulps of the mean), the z-score ranges over an
        interval, clipped to [-8, 8] as Eq. 4's is; a count within 16 ulps
        of the cold threshold may fall either side of it."""
        e = self.eng
        ulp16 = 16 * 2.0 ** -24
        c = np.maximum(cnt, 1e-12)
        mean, s2 = sm / c, sq / c
        var = s2 - mean * mean
        dv = ulp16 * s2
        sg_lo = np.sqrt(np.maximum(var - dv, 0)) + 1e-8
        sg_hi = np.sqrt(np.maximum(var + dv, 0)) + 1e-8
        d = q - mean
        dm = ulp16 * np.abs(mean)
        cands = [(d + sd) / sg for sd in (-dm, dm) for sg in (sg_lo, sg_hi)]
        z_lo = np.clip(np.minimum.reduce(cands), -8, 8)
        z_hi = np.clip(np.maximum.reduce(cands), -8, 8)
        near_cold = np.abs(cnt - 1) <= ulp16
        z_lo = np.where(near_cold, -8.0, np.where(cnt < 1, 0.0, z_lo))
        z_hi = np.where(near_cold, 8.0, np.where(cnt < 1, 0.0, z_hi))
        lg = np.log(b) - np.log1p(-b)
        mandatory = base >= 1 - 1e-6
        lo = np.where(mandatory, 1.0, sigmoid(lg + e.alpha * z_lo))
        hi = np.where(mandatory, 1.0, sigmoid(lg + e.alpha * z_hi))
        clip = lambda x: np.clip(x, e.min_p, 1.0)
        return (clip(lo).astype(np.float64), clip(hi).astype(np.float64))

    def _fold(self, r, q, t, p, z) -> None:
        e, dt_ = self.eng, self.dt
        ur, inv = np.unique(r, return_inverse=True)
        m = len(ur)
        # persisted columns: events with z, decayed to each key's t*
        tstar = np.full(m, -np.inf, self.tdt)
        np.maximum.at(tstar, inv[z], t[z])
        wrote = np.isfinite(tstar)
        inv_p = np.where(z, dt_.type(1.0) / p, dt_.type(0.0))
        gap = np.where(z, tstar[inv] - t, 0).astype(dt_)
        v_add = np.zeros(m, dt_)
        np.add.at(v_add, inv, inv_p * self.exp(-gap / dt_.type(e.h)))
        w = (inv_p[:, None, None]
             * self.exp(-gap[:, None] / self.taus[None, :])[:, :, None]
             * np.stack([np.ones_like(q), q, q * q], -1)[:, None, :])
        agg_add = np.zeros((m,) + self.agg.shape[1:], dt_)
        np.add.at(agg_add, inv, w)
        old_t = self.last_t[ur]
        with np.errstate(invalid="ignore"):
            keep = np.where(np.isfinite(old_t), tstar - old_t,
                            np.inf).astype(dt_)
            d_h = np.where(wrote & np.isfinite(old_t),
                           self.exp(-np.maximum(keep, 0) / dt_.type(e.h)),
                           dt_.type(0.0))
            d_tau = np.where((wrote & np.isfinite(old_t))[:, None],
                             self.exp(-np.maximum(keep, 0)[:, None]
                                    / self.taus[None, :]), dt_.type(0.0))
        ww = ur[wrote]
        self.v_f[ww] = (v_add + d_h * self.v_f[ur])[wrote]
        self.agg[ww] = (agg_add + d_tau[:, :, None] * self.agg[ur])[wrote]
        self.last_t[ww] = tstar[wrote]

        # full-stream control column: every event, weight 1
        tf = np.full(m, -np.inf, self.tdt)
        np.maximum.at(tf, inv, t)
        vfa = np.zeros(m, dt_)
        np.add.at(vfa, inv, self.exp(-(tf[inv] - t).astype(dt_)
                                   / dt_.type(e.h)))
        old_tf = self.last_t_full[ur]
        with np.errstate(invalid="ignore"):
            d_f = np.where(np.isfinite(old_tf),
                           self.exp(-np.maximum(tf - old_tf, 0).astype(dt_)
                                  / dt_.type(e.h)), dt_.type(0.0))
        self.v_full[ur] = vfa + d_f * self.v_full[ur]
        self.last_t_full[ur] = tf


@dataclasses.dataclass(frozen=True)
class Scorer:
    """The scoring MLP's weights, float64: [F] -> hidden -> 1."""
    w1: np.ndarray
    b1: np.ndarray
    w2: np.ndarray
    b2: np.ndarray
    mu: np.ndarray
    sd: np.ndarray


def score(s: Scorer, features: np.ndarray):
    """Anomaly logits in float64, with the bound on how far a single
    bfloat16 pass per matrix product can move them.

    The chip multiplies float32 matrices at default precision in one
    bfloat16 pass: each operand keeps 8 significant bits, so each product
    term carries a relative error of at most 2^-8.  The bound is twice
    that, carried through both layers."""
    f = np.asarray(features, np.float64)
    x = (np.log1p(np.abs(f)) * np.sign(f) - s.mu) / s.sd
    h = np.maximum(x @ s.w1 + s.b1, 0.0)
    logit = (h @ s.w2 + s.b2)[:, 0]
    bound = 2.0 ** -7 * (np.abs(x) @ np.abs(s.w1) @ np.abs(s.w2)
                         + np.abs(h) @ np.abs(s.w2))[:, 0] + 1e-5
    return logit, bound


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))
