"""The correctness check's control: the configuration's reference in the
program's place, computed in its ``CONTROL`` precision, the one below the
precision the configuration states.  Its numbers have to fail the cell's
limits.

    python3 bench/control.py --workload iiot800k.ingest --seed 5 \
        --events 3000000

``--events`` is how many events (ingest) or requests (serve) a run's
window processes; the control follows the same sample as the cell's check
through them.  It runs on the host and prints one JSON line: the numbers,
the limits and ``correct``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))

from bench import compare, harness  # noqa: E402


def readings(config: dict, traffic: dict, seed: int, n_events: int,
             limits: dict, root: str = harness.ROOT, **low) -> dict:
    """The comparison's numbers with the configuration's reference standing
    in for the program, through the configuration's generator and check;
    ``low`` goes to the check's ``control`` (another ``dtype`` or ``exp``,
    how many ``writes`` each key is followed through)."""
    generator, reference, chk = harness.deployment(root, config)
    stream = generator.make_stream(config, n_events, seed)
    followed = chk.follow(config, traffic, stream, seed)
    return chk.control(reference, config, traffic, stream, seed, followed,
                       limits, **low)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args(argv)
    _, _, config, traffic = harness.cell_spec(args.workload)
    limits = compare.load_limits(harness.BENCH, args.workload)
    numbers = readings(config, traffic, args.seed, args.events, limits)
    correct, table = compare.judge(numbers, limits)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "events": args.events, "correct": correct,
                      "check": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
