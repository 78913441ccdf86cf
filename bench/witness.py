"""Where the program's float32 error comes from: the reference computed in
float32, with the exponential of its state update taken from NumPy on the
host or from XLA on the default device (the TPU, on a chip), each against
the float64 reference on the cell's sample of keys.

    python3 bench/witness.py --workload iiot800k.ingest --seed 5 \
        --events 3145728

Prints one JSON line: for each exponential, its error in float32 ulps over
[-30, 0] and the comparison's numbers, with each key followed through the
comparison's ``WRITES`` writes and through its whole history.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))

from bench import compare, control, harness  # noqa: E402


def device_exp():
    """float32 exp on JAX's default device; arguments are padded to a
    power of two so that a few programs serve every shape."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(jnp.exp)

    def exp(x):
        x = np.asarray(x, np.float32)
        flat = x.ravel()
        buf = np.zeros(1 << max(10, (flat.size - 1).bit_length()),
                       np.float32)
        buf[:flat.size] = flat
        return np.asarray(f(buf))[:flat.size].reshape(x.shape)
    return exp


def ulps(exp) -> dict:
    """Error of ``exp`` in float32 ulps over [-30, 0]: the largest, the
    mean of its size, and its mean with sign (a bias accumulates in a
    decayed sum, where an error of either sign averages out)."""
    x = np.linspace(-30.0, 0.0, 1 << 20, dtype=np.float32)
    want = np.exp(x.astype(np.float64))
    got = np.asarray(exp(x), np.float64)
    err = (got - want) / np.spacing(want.astype(np.float32))
    return {"max": float(np.abs(err).max()),
            "mean": float(np.abs(err).mean()), "bias": float(err.mean())}


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = harness.cell_spec(args.workload)
    limits = compare.load_limits(harness.BENCH, args.workload)
    out = {"workload": args.workload, "seed": args.seed,
           "events": args.events, "device": jax.devices()[0].device_kind}
    for name, exp in (("numpy", np.exp), ("device", device_exp())):
        out[name] = {"ulps": ulps(exp)}
        for writes in (compare.WRITES, np.inf):
            out[name][f"writes_{writes}"] = control.readings(
                config, traffic, args.seed, args.events,
                limits["p_rel_err"], np.float32, exp, writes)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
