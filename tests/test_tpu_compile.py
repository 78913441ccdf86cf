"""Compile the engine for a described TPU v5e, at the deployment's shapes.

Nothing runs: the TPU compiler, which is installed without a chip, refuses
what the chip would refuse — lane slices the Mosaic backend cannot lower, a
program that does not fit the device's memory.  The topology is described
inside a fixture (never at import), so every pytest-xdist worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core import stream as core_stream
from repro.core.engine import make_step
from repro.core.types import Event, init_state
from repro.features.engine import ShardedFeatureEngine
from repro.features.spec import ProfileSpec
from repro.kernels import ops
from repro.kernels import thinning_rmw as trmw

B, T = 4096, 6                  # engine batch; the paper's six windows
N_KEYS = 800_000                # Table 2 IIoT
GROUP = 8                       # flush group of the sink path
HBM_BYTES = 16 * 10 ** 9        # one v5e chip
CFG = ProfileSpec(write_budget_per_min=0.1 / 60.0, variance_alpha=1.0,
                  policy="pp_vr").engine_config()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def pallas_path(monkeypatch):
    """Route ``use_pallas='auto'`` to the Pallas kernel, as it routes on a
    TPU backend; this process's backend is the CPU."""
    monkeypatch.setattr(ops, "_resolve", lambda use_pallas: "pallas")
    jax.clear_caches()          # no trace of the reference path is reused
    yield
    jax.clear_caches()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("policy", trmw.POLICIES)
def test_thinning_kernel_compiles(topo, policy):
    one = SingleDeviceSharding(topo.devices[0])
    col = jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one)
    args = (jax.ShapeDtypeStruct((T,), jnp.float32, sharding=one), col, col,
            jax.ShapeDtypeStruct((B, 3 * T), jnp.float32, sharding=one),
            col, col, col, col, col, col)
    fn = lambda *a: trmw.thinning_rmw_pallas(
        *a, h=3600.0, budget=0.1 / 3600.0, alpha=1.0, policy=policy)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fast_sink_group_step_compiles(topo, pallas_path):
    """The program ``run_stream(..., sink=...)`` dispatches per flush group,
    at 800K keys: the kernel is in it and it fits one chip.  The fast fold
    touches only the batch's rows, so the temporaries hold about one padded
    copy of the carried table (1.64 GB in the ``T(4,128)`` layout) and no
    table-sized accumulators: a table-wide fold takes 5.7 GB."""
    one = SingleDeviceSharding(topo.devices[0])
    state = _sds(jax.eval_shape(lambda: init_state(N_KEYS, T)), one)
    ev = Event(*(jax.ShapeDtypeStruct((GROUP, B), dt, sharding=one)
                 for dt in (jnp.int32, jnp.float32, jnp.float32, jnp.bool_)))
    rng = _sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one)
    gidx = jax.ShapeDtypeStruct((GROUP * B,), jnp.int32, sharding=one)
    step = core_stream.sink_step_for(make_step(CFG, "fast"))
    compiled = step.lower(state, ev, rng, gidx).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert compiled.memory_analysis().temp_size_in_bytes < 2.0e9


def test_sharded_sink_group_step_compiles(topo, pallas_path):
    """The sharded engine's flush-group program on a 4-chip ``data`` mesh
    with ``jax.make_mesh``'s default axis types: the kernel runs in every
    shard and the step moves no profile rows between chips."""
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    eng = ShardedFeatureEngine(CFG, N_KEYS, mesh=mesh, mode="fast")
    W = eng.n_shards * (B // eng.n_shards)
    rows = NamedSharding(mesh, P("data"))
    cols = NamedSharding(mesh, P(None, "data"))
    state = _sds(jax.eval_shape(lambda: init_state(eng.num_entities, T)),
                 rows)
    ev = Event(*(jax.ShapeDtypeStruct((GROUP, W), dt, sharding=cols)
                 for dt in (jnp.int32, jnp.float32, jnp.float32, jnp.bool_)))
    rng = _sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)),
               NamedSharding(mesh, P()))
    gidx = jax.ShapeDtypeStruct((GROUP, W), jnp.int32, sharding=cols)
    step = core_stream.sink_step_for(eng._raw_step(),
                                     gather=eng._shard_gather())
    compiled = step.lower(state, ev, rng, gidx).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert not [l for l in hlo.splitlines()
                if " all-gather(" in l or " all-to-all(" in l]
    assert 0 < _device_bytes(compiled) < HBM_BYTES
