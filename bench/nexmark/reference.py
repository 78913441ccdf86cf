"""Plain reference of the NEXmark bid deployment.

Each bid updates two profiles, its bidder's row and its auction's row of
one table.  Rows are independent, so the reference is
``bench/reference.py``'s float64 fast mode run over the bids' (row, price,
time) lanes in stream order, each lane with its bid's micro-batch; what
differs is the draw: each lane's uniform is keyed on (row, event id), so
two bids of one row in one millisecond draw independently.  It imports
nothing of the program under test.

One more departure: a row whose count is below 1 (cold) tilts Eq. 4 by
``q / 1e8`` (mean 0, sigma 1e8), and prices of up to 1e6 dollars make that
tilt reach 0.01, where ``bench/reference.py``'s range of float32's reach
takes it as 0.  No cancellation enters that tilt, so the range is the
point itself.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference
from bench.reference import (BFLOAT16, CONTROL, EngineParams,  # noqa: F401
                             engine_key_data, sigmoid)


def uniforms(seed32: int, rows: np.ndarray, event_ids: np.ndarray
             ) -> np.ndarray:
    """Each lane's thinning uniform: threefry keyed on (table row, event
    id), the paper's counter-based draw with the stream's own counter.
    Computed on the host CPU backend."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        root = jax.random.PRNGKey(seed32)

        def one(k, s):
            return jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(root, k), s), ())
        u = jax.jit(jax.vmap(one))(np.asarray(rows, np.uint32),
                                   np.asarray(event_ids, np.uint32))
        return np.asarray(u, np.float64)


class FastReference(reference.FastReference):
    """``bench/reference.py``'s fast mode, with a cold row's tilt in the
    range of float32's reach (the module's docstring)."""

    def _p_reach(self, cnt, sm, sq, q, b, base):
        lo, hi = super()._p_reach(cnt, sm, sq, q, b, base)
        e = self.eng
        cold = (cnt < 1) & (np.abs(cnt - 1) > 16 * 2.0 ** -24)
        z = np.clip(np.asarray(q, np.float64) / 1e8, -8, 8)
        p = np.where(base >= 1 - 1e-6, 1.0,
                     sigmoid(np.log(b) - np.log1p(-b) + e.alpha * z))
        p = np.clip(p, e.min_p, 1.0)
        return np.where(cold, p, lo), np.where(cold, p, hi)
