"""The toy deployment's events: uniform keys, exponential values."""
import dataclasses

import numpy as np


@dataclasses.dataclass
class Stream:
    key: np.ndarray
    value: np.ndarray

    def __len__(self):
        return len(self.key)


def make_stream(config, n_events, seed):
    rng = np.random.default_rng([seed, 0x70])
    return Stream(key=rng.integers(0, int(config["n_keys"]), n_events,
                                   dtype=np.int32),
                  value=rng.exponential(1.0, n_events).astype(np.float32))
