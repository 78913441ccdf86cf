"""Compilation-context-invariant float32 math for the persistence path.

Why this exists
---------------
The persistence subsystem pins a *byte-level* parity contract: rows stored
by the fast-path ``WriteBehindSink`` (values gathered from the blocked
engine's state) must be identical to rows stored by the per-event
``FeatureWorker`` (values from standalone single-event kernel calls), and
``hydrate_state`` must rebuild the engine state exactly.  That requires the
fused decision+update math to produce bit-identical float32 results in
*every* compilation context it is traced into: the block driver's
``lax.scan`` body, the sink path's per-block jit, and a per-event B=1 call.

Two behaviours of XLA's CPU backend break that assumption:

* ``jnp.exp`` lowers to either a scalar libm call or a vectorized
  polynomial depending on the surrounding program — 1 ulp apart on
  ~10-40 % of inputs.  ``det_exp`` below replaces it on the persistence
  path: Cody-Waite range reduction + degree-6 Horner + an exact
  power-of-two scale, every step individually rounded.
* LLVM contracts ``round(a*b) + c`` into ``fma(a, b, c)`` in some fusion
  contexts and not others.  Neither ``lax.optimization_barrier`` (dropped
  before LLVM) nor a guarding ``select`` (InstCombine sinks the add into
  it) survives to block this.  ``pin`` works: it round-trips the product
  through the integer domain and adds a runtime-derived zero LLVM cannot
  prove to be zero (``min(bitcast(x), 0)`` for a non-negative runtime
  float ``x`` — the kernel uses its uniforms, whose bit patterns are
  non-negative but opaque to range analysis).  The float add then consumes
  a value with no visible multiply, so contraction is structurally
  impossible and the product is rounded exactly once, everywhere.  The
  zero's source must be runtime data in *every* caller: a constant source
  const-folds the pin away and silently re-admits contraction.

(The third context-dependent rewrite — divide-by-constant to
multiply-by-reciprocal — is handled at call sites by spelling the
reciprocal multiply explicitly; see ``ref.thinning_rmw_ref``.)

Only the jnp reference path uses this module (the Pallas TPU kernels keep
the hardware transcendentals; the byte-parity contract is defined on the
reference path, which is what CPU CI runs).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def zero32(runtime_f32: jax.Array) -> jax.Array:
    """int32 zeros LLVM cannot constant-prove, from a runtime float input.

    ``runtime_f32`` must be non-negative (float bit pattern with a clear
    sign bit — e.g. a 0.0/1.0 validity mask, a uniform in [0, 1)).  The
    result is always 0, but only arithmetic that actually knows the input's
    sign could fold it away.
    """
    return jnp.minimum(
        jax.lax.bitcast_convert_type(runtime_f32.astype(jnp.float32),
                                     jnp.int32), 0)


def pin(x: jax.Array, z32: jax.Array) -> jax.Array:
    """Pin ``x`` to its IEEE-rounded value in every compilation context.

    ``z32`` is a ``zero32(...)`` result broadcastable to ``x``.  The
    integer round-trip hides ``x``'s defining multiply from FP pattern
    matchers, so a pinned product feeding an add is never re-rounded as
    ``fma(a, b, c)``.
    """
    xi = jax.lax.bitcast_convert_type(x, jnp.int32) + z32
    return jax.lax.bitcast_convert_type(xi, jnp.float32)


# Cephes expf constants (Eigen's pexp uses the same set).
_LOG2E = 1.4426950408889634
_LN2_HI = 0.693359375
_LN2_LO = -2.12194440e-4
_EXP_P = (1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
          4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1)
# exp(x) underflows f32 below ~-87.33; clamp keeps 2^k representable.
_EXP_LO = -87.0
_EXP_HI = 88.0


def det_exp(x: jax.Array, z32: Optional[jax.Array] = None) -> jax.Array:
    """float32 exp(x), bit-identical in every compilation context.

    Accuracy ~1 ulp vs correctly-rounded exp; exp(0) == 1.0 exactly; inputs
    below -87 return 0.0 (the engine's "fresh row" decay path relies on
    exp(-huge) == 0).  Every multiply feeding an add is ``pin``-ed so the
    evaluation is one fixed sequence of individually-rounded ops.

    ``z32``: optional ``zero32(...)`` tensor broadcastable to ``x``.  When
    omitted it is derived from ``x == x`` (never-NaN inputs); callers that
    already hold a runtime mask should pass it explicitly.
    """
    x = x.astype(jnp.float32)
    if z32 is None:
        z32 = zero32((x == x).astype(jnp.float32))
    xc = jnp.clip(x, _EXP_LO, _EXP_HI)
    kf = jnp.round(xc * _LOG2E)
    # Cody-Waite: r = x - k*ln2, in two exactly-rounded steps.
    r = xc - pin(kf * _LN2_HI, z32)
    r = r - pin(kf * _LN2_LO, z32)
    # Degree-6 Horner for exp(r) on [-ln2/2, ln2/2]; pinned per step.
    y = jnp.full_like(r, _EXP_P[0])
    for c in _EXP_P[1:]:
        y = pin(y * r, z32) + c
    rr = pin(r * r, z32)
    y = pin(y * rr, z32) + r + 1.0
    # 2^k by exponent-bit construction (exact), applied as an exact multiply.
    k = kf.astype(jnp.int32)
    two_k = jax.lax.bitcast_convert_type(
        ((k + 127) << 23).astype(jnp.int32), jnp.float32)
    out = y * two_k
    return jnp.where(x < _EXP_LO, 0.0, out)
