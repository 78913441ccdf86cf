"""The toy deployment's plain reference: each key's sum in float64."""
import numpy as np

CONTROL = np.float16


def engine_key_data(seed):
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def sums(keys, values, n_keys, dtype=np.float64):
    out = np.zeros(n_keys, dtype)
    np.add.at(out, keys, np.asarray(values).astype(dtype))
    return out
