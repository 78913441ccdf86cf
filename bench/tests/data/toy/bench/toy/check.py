"""The toy deployment's check: the followed keys' sums against the
reference's, as the largest relative gap."""
import numpy as np


def follow(config, traffic, stream, seed):
    rng = np.random.default_rng([seed, 0x71])
    return np.flatnonzero(rng.random(int(config["n_keys"]))
                          < float(traffic["key_share"]))


def gap(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.maximum(np.abs(want), 1e-30)))


def check(reference, config, traffic, win, stream, seed32, followed,
          limits):
    want = reference.sums(stream.key, stream.value, int(config["n_keys"]))
    return ({"sum_rel_err": gap(win.state["sums"][followed],
                                want[followed])},
            {"followed_keys": int(len(followed))})


def control(reference, config, traffic, stream, seed, followed, limits):
    n = int(config["n_keys"])
    want = reference.sums(stream.key, stream.value, n)
    low = reference.sums(stream.key, stream.value, n, reference.CONTROL)
    return {"sum_rel_err": gap(low[followed], want[followed])}
