"""A toy timed path: each key's values summed on the device, one batch of
events at a time, in float32."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import Window


def events(config, traffic, seconds):
    batch = int(traffic["batch"])
    return batch * max(1, round(float(traffic["events_per_s"]) * seconds
                                / batch))


@jax.jit
def add(sums, keys, values):
    return sums.at[keys].add(values)


def prepare(config, traffic, stream, followed, *, seed, seed32, rng,
            seconds, devices, tmp):
    run = {"n_keys": int(config["n_keys"]), "batch": int(traffic["batch"]),
           "device": devices[0]}
    sums = jax.device_put(jnp.zeros(run["n_keys"], jnp.float32),
                          run["device"])
    add(sums, stream.key[:run["batch"]],
        stream.value[:run["batch"]]).block_until_ready()
    return run


def window(run, stream):
    sums = jax.device_put(jnp.zeros(run["n_keys"], jnp.float32),
                          run["device"])
    b = run["batch"]
    t0 = time.perf_counter()
    for lo in range(0, len(stream), b):
        sums = add(sums, stream.key[lo:lo + b], stream.value[lo:lo + b])
    sums = np.asarray(sums)
    return Window(seconds=time.perf_counter() - t0, events=len(stream),
                  completed=len(stream), bytes_written=0, store_bytes=0,
                  sample_pos=None, p=None, z=None, lam=None, batch_id=None,
                  sink_stats={}, store_dir="", state={"sums": sums})
