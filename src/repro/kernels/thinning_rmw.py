"""Pallas TPU kernel: fused persistence-path RMW decision + update.

One pass over a tile of gathered profile rows performs the paper's whole
worker step (§5.1 steps 2-5): lazy decay of the aggregates, feature
materialization, intensity estimate, inclusion probability (Eq. 2 or Eq. 4),
Bernoulli thresholding of pre-supplied uniforms, the Horvitz-Thompson
masked update, *and* the full-stream control-column update (Eq. 5 numerator
``v_full`` / ``last_t_full``) — without materializing the five intermediate
[B, T, 3] tensors a naive composition round-trips through HBM (DESIGN.md §4).
Carrying the control column means one fused pass covers the entire profile
row: the engine needs a single gather before and a single scatter after.

All five engine policies are compiled in statically via ``policy``:
'pp' (Eq. 2), 'pp_vr' (Eq. 4), 'full' (intensity from the full-stream
column), 'fixed' (constant rate) and 'unfiltered' (p = 1).

Layout: rows (events) on the sublane axis, the 3T aggregate columns +
control scalars on the lane axis.  All math is elementwise/broadcast over
an (block_b, 3T) tile, so the kernel is a single fused VPU pipeline.  Inside
the kernel the aggregate tile is component-major ([c0..cT-1, s0.., q0..]) so
count, sum and sumsq are contiguous lane slices; ``thinning_rmw_pallas``
converts from and back to the callers' tau-major layout around the call.

The gather of rows by entity id (and the conflict-free scatter back) remain
XLA ops around the kernel — see core/engine.py for the batching semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

POLICIES = ("pp", "pp_vr", "full", "fixed", "unfiltered")


def _kernel(taus_ref, last_t_ref, v_f_ref, agg_ref, q_ref, t_ref, u_ref,
            valid_ref, v_full_ref, last_t_full_ref,
            new_last_t_ref, new_v_f_ref, new_agg_ref, new_v_full_ref,
            new_last_t_full_ref, z_ref, p_ref, lam_ref, feat_ref, *,
            h: float, budget: float, alpha: float, policy: str,
            fixed_rate: float, mu_tau_index: int, min_p: float, n_taus: int):
    taus = taus_ref[0]                       # [T]
    last_t = last_t_ref[...]                 # [bb, 1]
    v_f = v_f_ref[...]                       # [bb, 1]
    q = q_ref[...]                           # [bb, 1]
    t = t_ref[...]                           # [bb, 1]
    u = u_ref[...]                           # [bb, 1]
    valid = valid_ref[...] > 0.5             # [bb, 1]
    v_full = v_full_ref[...]                 # [bb, 1]
    last_t_full = last_t_full_ref[...]       # [bb, 1]
    agg = agg_ref[...]                       # [bb, 3T] component-major

    fresh = last_t < -1e30                   # sentinel for "never persisted"
    dt = jnp.where(fresh, 0.0, jnp.maximum(t - last_t, 0.0))

    # ---- lazy decay to decision time (per tau; count/sum/sumsq share beta)
    # dt * (-1/tau) spelling (not -(dt/tau)): keeps rounding identical to
    # the jnp reference across compilation contexts — see ref.py.
    beta_tau = jnp.exp(dt * (-1.0 / taus[None, :]))            # [bb, T]
    beta_tau = jnp.where(fresh, 0.0, beta_tau)
    beta3 = jnp.concatenate([beta_tau] * 3, axis=1)            # [bb, 3T]
    agg_now = agg * beta3

    # contiguous lane slices: the TPU compiler refuses strided lane slices
    cnt = agg_now[:, :n_taus]                                  # [bb, T]
    sm = agg_now[:, n_taus:2 * n_taus]
    sq = agg_now[:, 2 * n_taus:]
    mean = sm / jnp.maximum(cnt, 1e-12)
    var = jnp.maximum(sq / jnp.maximum(cnt, 1e-12) - mean * mean, 0.0)
    feat_ref[...] = jnp.concatenate([cnt, sm, mean, jnp.sqrt(var)], axis=1)

    # ---- intensity estimate + inclusion probability (Eq. 2 / Eq. 4 / Eq. 5)
    beta_h = jnp.where(fresh, 0.0, jnp.exp(dt * (-1.0 / h)))
    fresh_full = last_t_full < -1e30
    dt_full = jnp.where(fresh_full, 0.0, jnp.maximum(t - last_t_full, 0.0))
    beta_hf = jnp.where(fresh_full, 0.0, jnp.exp(dt_full * (-1.0 / h)))
    if policy == "full":
        lam = (1.0 + beta_hf * v_full) * (1.0 / h)             # [bb, 1]
    else:
        lam = (1.0 + beta_h * v_f) * (1.0 / h)
    lam_ref[...] = lam
    base = jnp.minimum(1.0, budget / jnp.maximum(lam, 1e-30))
    if policy == "unfiltered":
        p = jnp.ones_like(lam)
    elif policy == "fixed":
        p = jnp.full_like(lam, fixed_rate)
    elif policy == "pp_vr":
        cold = cnt[:, mu_tau_index:mu_tau_index + 1] < 1.0
        mu_w = jnp.where(cold, 0.0, mean[:, mu_tau_index:mu_tau_index + 1])
        sg = jnp.where(cold, 1e8,
                       jnp.sqrt(var[:, mu_tau_index:mu_tau_index + 1]) + 1e-8)
        zs = jnp.clip((q - mu_w) / jnp.maximum(sg, 1e-8), -8.0, 8.0)
        b = jnp.clip(base, 1e-6, 1.0 - 1e-6)
        # log-free sigmoid(logit(b) + alpha*zs) — same form as ref.py
        p = jnp.where(base >= 1.0 - 1e-6, 1.0,
                      1.0 / (1.0 + ((1.0 - b) / b) * jnp.exp(zs * (-alpha))))
    else:  # 'pp' and the decision half of 'full'
        p = base
    p = jnp.clip(p, min_p, 1.0)

    z = (u < p) & valid                                        # [bb, 1]
    p_ref[...] = p
    z_ref[...] = z.astype(jnp.float32)

    # ---- Horvitz-Thompson masked update (only z rows change)
    inv_p = jnp.where(z, 1.0 / p, 0.0)                         # [bb, 1]
    # [1 .. 1, q .. q, q2 .. q2]: component-major, matching agg's layout
    w_cols = jnp.concatenate(
        [jnp.broadcast_to(w, cnt.shape) for w in (jnp.ones_like(q), q, q * q)],
        axis=1)                                                 # [bb, 3T]
    agg_new = agg_now + inv_p * w_cols
    new_agg_ref[...] = jnp.where(z, agg_new, agg)
    new_v_f_ref[...] = jnp.where(z, inv_p + beta_h * v_f, v_f)
    new_last_t_ref[...] = jnp.where(z, t, last_t)

    # ---- full-stream control column (every valid event, unconditional)
    new_v_full_ref[...] = jnp.where(valid, 1.0 + beta_hf * v_full, v_full)
    new_last_t_full_ref[...] = jnp.where(valid, t, last_t_full)


def thinning_rmw_pallas(taus, last_t, v_f, agg_flat, q, t, u, valid,
                        v_full, last_t_full, *,
                        h: float, budget: float, alpha: float = 0.0,
                        policy: str = "pp", fixed_rate: float = 0.1,
                        mu_tau_index: int = 2,
                        min_p: float = 1e-6, block_b: int = 256,
                        interpret: bool = False):
    """Fused decision+update over gathered rows.

    Shapes: taus [T]; last_t, v_f, q, t, u, valid, v_full, last_t_full: [B];
    agg_flat: [B, 3T] (tau-major: [c0,s0,q0, c1,s1,q1, ...]).  Fresh rows are
    signalled by last_t = -1e38 (finite sentinel; -inf breaks 0*inf masking
    on the VPU); same sentinel for last_t_full.

    Returns (new_last_t, new_v_f, new_agg_flat, z, p, features[B, 4T],
    lam[B], new_v_full, new_last_t_full).
    """
    assert policy in POLICIES, policy
    B = last_t.shape[0]
    n_taus = taus.shape[0]
    block_b = min(block_b, B)
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    col = lambda i: (i, 0)
    as_col = lambda x: x[:, None].astype(jnp.float32)
    # [B, a*b] -> [B, b*a]: tau-major <-> component-major aggregate columns
    swap = lambda x, a, b: x.reshape(B, a, b).swapaxes(1, 2).reshape(B, -1)

    kernel = functools.partial(
        _kernel, h=h, budget=budget, alpha=alpha, policy=policy,
        fixed_rate=fixed_rate, mu_tau_index=mu_tau_index,
        min_p=min_p, n_taus=n_taus)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_taus), lambda i: (0, 0)),       # taus
            pl.BlockSpec((block_b, 1), col),                   # last_t
            pl.BlockSpec((block_b, 1), col),                   # v_f
            pl.BlockSpec((block_b, 3 * n_taus), col),          # agg
            pl.BlockSpec((block_b, 1), col),                   # q
            pl.BlockSpec((block_b, 1), col),                   # t
            pl.BlockSpec((block_b, 1), col),                   # u
            pl.BlockSpec((block_b, 1), col),                   # valid
            pl.BlockSpec((block_b, 1), col),                   # v_full
            pl.BlockSpec((block_b, 1), col),                   # last_t_full
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), col),                   # new_last_t
            pl.BlockSpec((block_b, 1), col),                   # new_v_f
            pl.BlockSpec((block_b, 3 * n_taus), col),          # new_agg
            pl.BlockSpec((block_b, 1), col),                   # new_v_full
            pl.BlockSpec((block_b, 1), col),                   # new_last_t_full
            pl.BlockSpec((block_b, 1), col),                   # z
            pl.BlockSpec((block_b, 1), col),                   # p
            pl.BlockSpec((block_b, 1), col),                   # lam
            pl.BlockSpec((block_b, 4 * n_taus), col),          # features
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 3 * n_taus), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 4 * n_taus), jnp.float32),
        ],
        interpret=interpret,
    )(taus[None, :].astype(jnp.float32), as_col(last_t), as_col(v_f),
      swap(agg_flat.astype(jnp.float32), n_taus, 3), as_col(q), as_col(t),
      as_col(u), as_col(valid), as_col(v_full), as_col(last_t_full))
    (new_last_t, new_v_f, new_agg, new_v_full, new_last_t_full, z, p, lam,
     feats) = outs
    return (new_last_t[:, 0], new_v_f[:, 0], swap(new_agg, 3, n_taus),
            z[:, 0] > 0.5,
            p[:, 0], feats, lam[:, 0], new_v_full[:, 0],
            new_last_t_full[:, 0])
