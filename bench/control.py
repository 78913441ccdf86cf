"""The correctness check's control: the reference in the program's place,
computed in bfloat16, the precision below the float32 the configurations
state.  Its numbers have to fail the cell's limits.

    python3 bench/control.py --workload iiot800k.ingest --seed 5 \
        --events 3000000

``--events`` is how many events (ingest) or requests (serve) a run's
window processes; the control follows the same sample of keys through them,
with full batches as the serve cell's dispatch log.  It runs on the host
and prints one JSON line: the numbers, the limits and ``correct``.
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

from bench import compare, harness, reference, streamgen  # noqa: E402


def readings(config: dict, traffic: dict, seed: int, n_events: int,
             p_limit: float, dtype=reference.BFLOAT16, exp=np.exp,
             writes: float = compare.WRITES) -> dict:
    """The comparison's numbers with ``FastReference(dtype, exp)``
    standing in for the program, each key followed through ``writes`` of
    its writes."""
    st, eng_cfg = config["stream"], config["engine"]
    stream = streamgen.generate(streamgen.StreamSpec.from_config(
        st, n_events), seed)
    followed = compare.sample_keys(
        seed, int(st["n_keys"]), float(traffic["key_share"]), stream.key)
    sampled = np.zeros(int(st["n_keys"]), bool)
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = harness.cell_spec(args.workload)
    limits = compare.load_limits(harness.BENCH, args.workload)
    numbers = readings(config, traffic, args.seed, args.events,
                       limits["p_rel_err"])
    correct, table = compare.judge(numbers, limits)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "events": args.events, "correct": correct,
                      "check": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
