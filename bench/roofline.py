"""Chip peaks and the operations and bytes a kernel call needs.

Peaks are the published numbers of one chip, keyed by the ``device_kind``
JAX reports.  A kind that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (the VPU's
    # float32 elementwise rate is lower, so a compute floor from this peak
    # is a lower bound), 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py")
    return PEAKS[device_kind]


def thinning_rmw_cost(events: int, n_taus: int, policy: str
                      ) -> tuple:
    """(operations, bytes) of one fused decision+update kernel call over
    ``events`` gathered rows with ``n_taus`` decay constants.

    Bytes: each event's row is read once and written once, as the
    algorithm needs it, with no tile padding.  In: last_t, v_f, q, t, u,
    valid, v_full, last_t_full (8 words) and the 3T aggregates; out:
    new last_t, v_f, v_full, last_t_full, z, p, lam (7 words), the 3T
    updated aggregates and the 4T features; plus the T decay constants
    once per call.  Operations: the elementwise arithmetic, compares and
    selects of the kernel body counted one each per event (see
    ``tests/test_bench_roofline.py`` for the count by line): 23 per decay
    constant and 36 per event, plus 21 for Eq. 4's variance tilt."""
    words = 8 + 3 * n_taus + 7 + 3 * n_taus + 4 * n_taus
    nbytes = 4 * (events * words + n_taus)
    per_event = 36 + 23 * n_taus + (21 if policy == "pp_vr" else 0)
    return events * per_event, nbytes


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float:
    """Least time the chip could take over the time taken, in percent."""
    pk = peaks(device_kind)
    floor = max(ops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])
    return 100.0 * floor / seconds
