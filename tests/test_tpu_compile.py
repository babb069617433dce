"""Compile-only checks of the chip's main path against a described TPU v5e.

The TPU compiler is installed alongside JAX, and it compiles for a chip that
is described rather than attached.  These tests lower and compile, for one
described v5e chip in float32, what ``chip_smoke.py`` runs on a real one:

* the ``ChunkedEvaluator`` top-k body and full-output body at chunk 8192,
  and the program that packs the full output's 96 columns for one copy;
* the planner's wave rollout (``vector_sim``) at 2048 scenarios x 64 jobs,
  and with fan-in DAG edges on racks at 512 scenarios x 658 jobs;
* the ``seg_combine`` Pallas kernel at three shapes.

Nothing runs, so these say nothing about results or times; they catch what
the chip's compiler refuses (tiling, fast-memory limits, unsupported ops)
without chip time.  Arguments are shapes with a sharding, not arrays.  The
topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the worker running this file
loads the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def float32_no_cache():
    """Compile as the chip runs (float32, x64 off), and keep JAX's persistent
    cache off: a compile for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _shape(x, sharding):
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _whatif_evaluator(topo):
    from repro.core.hadoop.params import CostFactors, HadoopParams, ProfileStats
    from repro.search import ChunkedEvaluator

    return ChunkedEvaluator(HadoopParams(pNumNodes=16), ProfileStats(),
                            CostFactors(), chunk=1 << 13,
                            devices=[topo.devices[0]])


def _whatif_args(ev, one_chip):
    from benchmarks.bench_whatif import EQ_SPACE, TOPK_EXTRA
    from repro.search import space_block

    rows = space_block({**EQ_SPACE, **TOPK_EXTRA}, 0, ev.chunk)
    batched = {k: jax.ShapeDtypeStruct((ev.chunk,), ev.base_cfg[k].dtype,
                                       sharding=one_chip) for k in rows}
    static = {k: _shape(v, one_chip) for k, v in ev.base_cfg.items()}
    return batched, static


def test_whatif_topk_body_compiles_for_v5e(topo, one_chip):
    ev = _whatif_evaluator(topo)
    batched, static = _whatif_args(ev, one_chip)
    mask = jax.ShapeDtypeStruct((ev.chunk,), jnp.bool_, sharding=one_chip)
    compiled = ev._topk_fn.lower(batched, static, mask, k=10).compile()
    assert ev.chunk == 8192
    assert static["pSortMB"].dtype == jnp.float32
    assert compiled.out_info[1].shape == (10,)      # top-k indices


def test_whatif_evaluate_body_compiles_for_v5e(topo, one_chip):
    ev = _whatif_evaluator(topo)
    batched, static = _whatif_args(ev, one_chip)
    compiled = ev._eval_fn.lower(batched, static).compile()
    out = compiled.out_info
    assert out["j_totalCost"].shape == (ev.chunk,)
    assert out["j_totalCost"].dtype == jnp.float32


def test_whatif_evaluate_pack_compiles_for_v5e(topo, one_chip):
    from repro.search.evaluator import _pack_layout

    ev = _whatif_evaluator(topo)
    out = jax.eval_shape(ev._eval_fn, *_whatif_args(ev, one_chip))
    layout = _pack_layout(out)
    groups = [[jax.ShapeDtypeStruct(out[k].shape, out[k].dtype, sharding=one_chip)
               for k in keys] for keys in layout]
    compiled = ev._pack_fn.lower(groups).compile()
    assert [len(keys) for keys in layout] == [96]
    (packed,) = compiled.out_info
    assert packed.shape == (96, ev.chunk) == (96, 8192)
    assert packed.dtype == jnp.float32


def test_wave_rollout_compiles_for_v5e(topo, one_chip):
    from repro.cluster import default_job_classes, pack_trace, poisson_trace
    from repro.cluster.vector_sim import _compiled, _prepare

    b = 2048
    cols = pack_trace(poisson_trace(default_job_classes(), 64, seed=3))
    tile = lambda a: np.tile(a, (b, 1))          # noqa: E731
    nodes = np.full(b, 16.0)
    scen = {
        "arrival": tile(cols["arrival"]) * 10.0,
        "n_maps": tile(cols["n_maps"]), "n_reds": tile(cols["n_reds"]),
        "map_cost": tile(cols["map_cost"]), "red_work": tile(cols["red_work"]),
        "shuffle": tile(cols["shuffle"]) * ((nodes - 1.0) / nodes)[:, None],
        "policy": np.repeat([0.0, 1.0], b // 2),
        "slowstart": np.full(b, 0.05),
        "map_slots": nodes * 2.0, "red_slots": nodes * 2.0,
    }
    arrs, edges, _, n_steps, flags = _prepare(scen, None, 1)
    arrs = {k: _shape(v.astype(np.float32) if v.dtype == np.float64 else v, one_chip)
            for k, v in arrs.items()}
    assert flags == (True, False, False, False, False, False)
    fn = _compiled((topo.devices[0],), n_steps, *flags)
    compiled = fn.lower(arrs, _shape(edges, one_chip)).compile()
    assert compiled.out_info["p95_latency"].shape == (b,)


def test_wave_rollout_fan_in_dag_on_racks_compiles_for_v5e(topo, one_chip):
    # the Hive TPC-H planner chunk: 256 rows x 2 traces of 658 jobs, up to
    # three parents a job, FIFO and fair rows on racked clusters
    from repro.cluster import default_job_classes, pack_trace, poisson_trace
    from repro.cluster.vector_sim import _compiled, _prepare

    b, j, p = 512, 658, 3
    cols = pack_trace(poisson_trace(default_job_classes(), j, seed=3))
    tile = lambda a: np.tile(a, (b, 1))          # noqa: E731
    nodes = np.full(b, 20.0)
    dep = np.full((b, j, p), -1, dtype=np.int32)
    dep[:, 1:, 0] = np.arange(j - 1)
    dep[:, 3:, 1:] = np.arange(j - 3)[:, None] + np.asarray([1, 2])
    scen = {
        "arrival": np.zeros((b, j)),
        "n_maps": tile(cols["n_maps"]), "n_reds": tile(cols["n_reds"]),
        "map_cost": tile(cols["map_cost"]), "red_work": tile(cols["red_work"]),
        "shuffle": tile(cols["shuffle"]) * ((nodes - 1.0) / nodes)[:, None],
        "policy": np.repeat([0.0, 1.0], b // 2),
        "slowstart": np.full(b, 0.05),
        "map_slots": nodes * 4.0, "red_slots": nodes * 2.0,
        "dep": dep, "dep_kind": np.zeros((b, j, p), dtype=np.int8),
        "topo_racks": np.full(b, 16.0), "topo_cross_bw": np.full(b, 40.0),
        "topo_oversub": np.full(b, 10.0),
    }
    arrs, edges, _, n_steps, flags = _prepare(scen, None, 1)
    assert flags == (True, False, False, False, True, True)    # fair, DAG, racks
    assert edges.shape == (1, j, p)                 # every lane shares one table
    arrs = {k: _shape(v.astype(np.float32) if v.dtype == np.float64 else v, one_chip)
            for k, v in arrs.items()}
    fn = _compiled((topo.devices[0],), n_steps, *flags)
    compiled = fn.lower(arrs, _shape(edges, one_chip)).compile()
    assert compiled.out_info["makespan"].shape == (b,)
    assert compiled.out_info["release_steps"].dtype == jnp.int32


@pytest.mark.parametrize("n, d, parts", [
    (1024, 256, 8),        # the analysis gate's canonical launch
    (65536, 128, 512),     # engine combine: one value column padded to a lane
    (4096, 512, 64),       # two d-blocks
])
def test_seg_combine_compiles_for_v5e(one_chip, n, d, parts):
    from repro.kernels.seg_combine import seg_combine_pallas

    # the block sizes ops.seg_combine picks for these (padded) shapes
    fn = jax.jit(lambda v, p: seg_combine_pallas(v, p, parts,
                                                 block_d=min(256, d)))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
