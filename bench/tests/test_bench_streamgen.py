"""The benchmark's stream generator: deterministic per seed, and the same
stream the program's generator draws with the same Zipf exponent."""
import dataclasses

import numpy as np

from benchtest import harness
from bench import streamgen


def spec(name, n):
    config = harness.load_json(harness.BENCH, "configs", f"{name}.json")
    return config["stream"], streamgen.StreamSpec.from_config(
        config["stream"], n)


def test_deterministic_per_seed():
    _, s = spec("fraud7k", 50_000)
    a, b = streamgen.generate(s, 2**31 + 11), streamgen.generate(s, 2**31 + 11)
    c = streamgen.generate(s, 2**31 + 12)
    for f in ("key", "q", "t", "label"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.key, c.key)
    assert np.all(np.diff(a.t) >= 0)


def test_equals_program_generator(monkeypatch):
    from repro.streaming import workload

    st, s = spec("fraud7k", 40_000)
    monkeypatch.setattr(workload, "calibrate_zipf",
                        lambda n, v, tol=1e-3: st["zipf_exponent"])
    prog = workload.generate(dataclasses.replace(
        workload.REGIMES["fraud"], n_events=s.n_events, n_keys=s.n_keys,
        duration=s.duration_s), seed=9)
    ours = streamgen.generate(s, 9)
    for f in ("key", "q", "t", "label"):
        np.testing.assert_array_equal(getattr(ours, f), getattr(prog, f))


def test_stored_exponent_gives_the_stated_skew():
    from repro.streaming.workload import calibrate_zipf

    for name in ("iiot800k", "fraud7k"):
        st, _ = spec(name, 10)
        assert st["zipf_exponent"] == calibrate_zipf(st["n_keys"],
                                                     st["vol80_share"])
        w = streamgen.zipf_weights(st["n_keys"], st["zipf_exponent"])
        assert abs(streamgen.vol80_share(w) - st["vol80_share"]) < 1e-3
