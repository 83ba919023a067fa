"""The fluid engine's chunk steps compile for a TPU v5e at real widths.

Nothing here runs on a chip: each test lowers one jitted step with
``jax.ShapeDtypeStruct`` inputs placed on a *described* ``v5e:2x2``
topology and compiles it with the TPU compiler, which refuses what the
chip would refuse (a program that does not fit its 16 GB, a sharding it
cannot partition).  The widths are those of the registered scenarios at
``scale=1.0``:

* clustered ``fig9_planet``: ~21k super-functions, 256-tick chunks;
* the 96-point ``DEFAULT_SPACE`` sweep at ``fig9_production`` width;
* ``region_failover``'s three cells;
* the function-sharded planet step on a 4-chip mesh.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers import
every test file.  Keep every such compile in this one file.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.eventsim import SimConfig
from repro.core.policies import init_theta
from repro.core.simjax import (_PFLEET, JaxFleet, _chunk_batch,
                               _chunk_batch_fnshard, _init_state,
                               _prep_static, stack_params)
from repro.core.trace import (KA_GRID, TraceConfig, rate_matrix, synthesize,
                              synthesize_rates)
from repro.opt.space import DEFAULT_SPACE
from repro.scenarios import get_scenario

HBM_BYTES = 16e9                  # one v5e chip
PLANET_F = 21_000                 # fig9_planet clustered at 1 rps (~20.9k)
NBINS = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chip-targeted executable cannot be read back without the chip, so
    # keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _step_args(sc, width: int, points: int, *, fleet: bool,
               sharding_of, weights: bool = False):
    """Abstract ``_chunk_batch`` arguments for ``sc`` at ``width``
    functions and ``points`` batch points, built the way
    ``simjax._chunked_summaries`` builds them (the per-function constants
    come from a tiny trace of the same scenario, widened).
    ``sharding_of(shape)`` places each argument."""
    sim = SimConfig(tick_s=sc.policy.tick_s)
    dt = sim.tick_s
    small = TraceConfig(num_functions=2, duration_s=60, target_total_rps=1)
    tiny = synthesize_rates(small, dt) if sc.rate_trace else synthesize(small)
    policy = sc.policy.to_jax()
    _, _, cold_ticks, wbuf, cpu_consts = _prep_static(tiny, policy, sim, dt)
    jf = sc.fleet or JaxFleet()
    prov_ticks = max(1, int(round(jf.provision_s / dt))) if fleet else 1
    arr_dtype = np.float32 if weights else rate_matrix(tiny, dt).dtype
    f32 = np.float32

    def a(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_of(shape))

    state = jax.eval_shape(
        jax.vmap(lambda n: _init_state(width, cold_ticks, wbuf, prov_ticks,
                                       n)),
        jax.ShapeDtypeStruct((points,), f32))
    state = jax.tree.map(lambda s: a(s.shape, s.dtype), state)
    pols = jax.tree.map(lambda x: a(x.shape, f32),
                        stack_params([policy.params()] * points))
    k = len(KA_GRID)
    args = (state, a((sc.chunk_ticks, width), arr_dtype), a((points, width)),
            a((width,)), (a((width, k)), a((width, k))), a((width,)),
            a((width,)), a((width,)), pols, a((points, len(_PFLEET))))
    statics = dict(warm_tick=1000, total_ticks=4800, family=policy.family,
                   dt=dt, cold_ticks=cold_ticks, wbuf=wbuf,
                   prov_ticks=prov_ticks, telem_slots=0)
    return (args, tuple(float(c) for c in cpu_consts), a((NBINS - 1,)),
            a((), np.int32), a((width,)) if weights else None, statics)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return used


def _lower_chunk_batch(topo, sc, width, points, *, fleet, weights=False):
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    args, consts, edges, tick0, w, statics = _step_args(
        sc, width, points, fleet=fleet, sharding_of=lambda s: one,
        weights=weights)
    return _chunk_batch.lower(
        *args, consts, float(sc.num_nodes), edges, tick0,
        has_fleet=fleet, weights=w, **statics)


def test_planet_clustered_step_compiles_for_v5e(topo):
    lowered = _lower_chunk_batch(topo, get_scenario("fig9_planet"),
                                 PLANET_F, 1, fleet=False, weights=True)
    _fits(lowered.compile())


def test_default_space_sweep_step_compiles_for_v5e(topo):
    points = len(DEFAULT_SPACE.points())
    assert points == 96
    lowered = _lower_chunk_batch(topo, get_scenario("fig9_production"),
                                 2000, points, fleet=True)
    _fits(lowered.compile())


def test_region_failover_cells_step_compiles_for_v5e(topo):
    from repro.cells.fluid import _cells_chunk_batch
    sc = get_scenario("region_failover")
    c_n, f = sc.cells.cell_count, sc.base.num_functions
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    args, consts, edges, tick0, _, statics = _step_args(
        sc, f, 1, fleet=True, sharding_of=lambda s: one)
    f32 = np.float32

    def a(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # every per-function leaf gains a leading cell axis (cells_chunked_
    # summaries vmaps _init_state over cells), the reactive latches are
    # (C, K) with K = 0 here
    state = jax.tree.map(
        lambda s: a((s.shape[0], c_n) + s.shape[1:], s.dtype), args[0])
    latches = tuple(a((1, c_n, 0)) for _ in range(3))
    k = len(KA_GRID)
    statics.pop("telem_slots")
    compiled = _cells_chunk_batch.lower(
        (state, *latches), a((sc.chunk_ticks, c_n, f), args[1].dtype),
        a((sc.chunk_ticks, c_n)), a((c_n, f)), a((c_n, f)), a((c_n, f, k)),
        a((c_n, f, k)), a((f,)), a((f,)), a((f,)), args[8], args[9], None,
        consts, a((c_n,)), edges, tick0, fail_cell=sc.cells.fail_cell,
        fail_tick=2160, route_skew_static=sc.cells.route_skew,
        spill_static=sc.cells.spill_threshold, has_fleet=True,
        **statics).compile()
    _fits(compiled)
    # the router and the failover harvest stay elementwise: a float32 dot
    # would run as a single bfloat16 pass on the chip
    hlo = compiled.as_text()
    assert " dot(" not in hlo and "convolution(" not in hlo


def test_planet_function_sharded_step_compiles_on_4_chips(topo):
    sc = get_scenario("fig9_planet")
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("functions",))

    def place(shape):
        # shard the function axis, replicate everything else
        spec = [None] * len(shape)
        if PLANET_F in shape:
            spec[list(shape).index(PLANET_F)] = "functions"
        return NamedSharding(mesh, P(*spec))

    args, consts, edges, tick0, w, statics = _step_args(
        sc, PLANET_F, 1, fleet=False, sharding_of=place, weights=True)
    compiled = _chunk_batch_fnshard.lower(
        *args, edges, tick0, w, mesh=mesh, cpu_consts=consts,
        static_nodes=float(sc.num_nodes), **statics).compile()
    per_device = _fits(compiled)
    assert per_device < HBM_BYTES / 2
    # one cross-device reduction of the scalar sums per chunk
    assert "all-reduce" in compiled.as_text()


def test_learned_policy_step_has_no_dot_on_v5e(topo):
    sc = get_scenario("fleet_cost_stress")
    sc = dataclasses.replace(sc, policy=dataclasses.replace(
        sc.policy, kind="learned", theta=init_theta(0)))
    lowered = _lower_chunk_batch(topo, sc, sc.base.num_functions, 16,
                                 fleet=True)
    # the keepalive network's output layer is a multiply-sum: no float32
    # dot that the chip would run as a single bfloat16 pass
    assert "dot_general" not in lowered.as_text()
    _fits(lowered.compile())
