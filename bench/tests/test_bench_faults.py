"""The comparison that decides ``correct``: a sound run passes, and a run
whose timed path is broken underneath fails, once per fault the cells can
have; the bfloat16 control, through each configuration's reference and
check, fails every cell's limits.

Runs the harness past its look for a chip, on the CPU, at a size the CPU
takes in seconds.  (One chip per cell: no exchange between chips to
drop.)"""
import jax.numpy as jnp
import pytest

from benchtest import CELLS, harness, small_cell, small_spec

import repro.core.engine as engine
import repro.core.stream as stream
import repro.serving.frontend as frontend

SEED = 2**31 + 977


def unchanged_state(orig):
    def step(cfg, state, ev, rng, rng_entity=None):
        _, info = orig(cfg, state, ev, rng, rng_entity)
        return state, info
    return step


def half_batch(orig):
    def step(cfg, state, ev, rng, rng_entity=None):
        keep = jnp.arange(ev.key.shape[0]) < ev.key.shape[0] // 2
        return orig(cfg, state, ev._replace(valid=ev.valid & keep), rng,
                    rng_entity)
    return step


def altered_answer(orig):
    def step(cfg, state, ev, rng, rng_entity=None):
        state, info = orig(cfg, state, ev, rng, rng_entity)
        return state, info._replace(z=info.z.at[::64].set(~info.z[::64]))
    return step


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer}


@pytest.fixture
def cells(monkeypatch):
    small_cell(monkeypatch)
    stream._sink_step.cache_clear()
    yield
    stream._sink_step.cache_clear()


def run(cell):
    return harness.run_cell(cell, SEED, 1.0, False, 0.0,
                            require_chip=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cells, cell):
    res = run(cell)
    assert res["correct"], res["check"]
    assert res["diagnostics"]["compared_events"] > 100


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cells, monkeypatch, cell, fault):
    monkeypatch.setattr(engine, "_step_fast",
                        FAULTS[fault](engine._step_fast))
    res = run(cell)
    assert not res["correct"], res["check"]


def test_fault_in_an_earlier_pass_is_not_correct(cells, monkeypatch):
    """A window of several passes is held to the reference pass by pass:
    an answer altered in the first pass alone is caught."""
    from bench.paths import ingest

    _, _, _, traffic = small_spec("iiot800k.ingest")
    assert ingest.passes(traffic) >= 2
    orig, seen = ingest._pass, []

    def first_altered(run, stream, sink, n_chunks):
        state, (p, z, lam), stats = orig(run, stream, sink, n_chunks)
        if not seen:
            z = z.copy()
            z[::64] = ~z[::64]
        seen.append(1)
        return state, (p, z, lam), stats
    monkeypatch.setattr(ingest, "_pass", first_altered)
    res = run("iiot800k.ingest")
    assert len(seen) == ingest.passes(traffic)
    assert not res["correct"], res["check"]
    assert res["check"]["z_flips"]["value"] > 0


def test_altered_score_is_not_correct(cells, monkeypatch):
    orig = frontend.score_at_width

    def score(scorer, feats, width):
        s = orig(scorer, feats, width).copy()
        s[::8] += 1.0
        return s
    monkeypatch.setattr(frontend, "score_at_width", score)
    res = run("iiot800k.serve")
    assert not res["correct"], res["check"]
    assert res["check"]["score_err"]["value"] > res["check"]["score_err"][
        "limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell):
    from bench import compare, control

    _, _, config, traffic = small_spec(cell)
    n = harness.driver(harness.ROOT, traffic).events(config, traffic, 1.0)
    limits = compare.load_limits(harness.BENCH, cell)
    numbers = control.readings(config, traffic, SEED, n, limits)
    correct, table = compare.judge(numbers, limits)
    assert not correct, table
