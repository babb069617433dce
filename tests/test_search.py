"""repro.search: chunked/sharded evaluator, streaming top-k, escape hatch.

Covers the contract the subsystem was built around:
* chunked+sharded evaluation is bit-for-bit identical to the seed's
  unchunked single-device ``jit(vmap(...))`` path;
* padding at non-divisible batch sizes changes nothing;
* a fixed chunk size means ONE compile across arbitrary grid sizes;
* streamed on-device top-k agrees with a numpy argsort oracle;
* an all-invalid grid raises from ``best()`` but the search path routes
  invalid survivors through the exact task-scheduler simulator;
* the multi-device sharded path (8 forced host devices, subprocess) matches
  the single-device result within ``CROSS_PROGRAM_MAX_ULP``, with equal
  ``valid`` flags and top-k order.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.hadoop import CostFactors, HadoopParams, MiB, ProfileStats
from repro.core.whatif import evaluate_grid, evaluate_product_grid
from repro.search import (
    ChunkedEvaluator,
    InvalidGridError,
    TpuEvaluator,
    coordinate_descent_ev,
    evaluate_unchunked,
    grid_search,
    grid_search_ev,
    random_search,
    search_topk,
    space_block,
    space_size,
)

P = HadoopParams(pNumNodes=8, pNumMappers=64, pNumReducers=16, pSplitSize=128 * MiB)
S = ProfileStats(sMapSizeSel=0.8, sReduceSizeSel=0.5)
C = CostFactors()

SPACE = {
    "pSortMB": [25.0, 50.0, 100.0, 200.0, 400.0],
    "pSortFactor": [5.0, 10.0, 25.0, 50.0],
    "pNumReducers": [4.0, 8.0, 16.0, 32.0, 64.0],
    "pIsIntermCompressed": [0.0, 1.0],
}

# numSpills >> pSortFactor**2 everywhere -> closed-form merge math invalid
INVALID_SPACE = {
    "pSortMB": [0.25, 0.5],
    "pSortFactor": [2.0, 3.0],
}


def _oracle_cost(space):
    """Full-grid costs via the seed's unchunked single-device path."""
    ev = ChunkedEvaluator(P, S, C, chunk=64)
    cols = space_block(space, 0, space_size(space))
    out = evaluate_unchunked(ev.base_cfg, cols)
    return np.where(out["valid"] > 0, out["j_totalCost"], np.inf)


# ------------------------------------------------------------------
# chunked == unchunked
# ------------------------------------------------------------------


def test_chunked_matches_unchunked_bit_for_bit():
    ref = _oracle_cost(SPACE)
    for chunk in (7, 64, 1 << 13):  # non-divisible, divisible, one-chunk
        res = evaluate_product_grid(P, S, C, SPACE,
                                    evaluator=ChunkedEvaluator(P, S, C, chunk=chunk))
        assert res.total_cost.shape == ref.shape
        assert np.array_equal(res.total_cost, ref), f"chunk={chunk}"


def test_padding_correct_at_non_divisible_sizes():
    ev = ChunkedEvaluator(P, S, C, chunk=16)
    rng = np.random.default_rng(3)
    vals = rng.choice([25.0, 50.0, 100.0, 200.0], 64)
    # one full-chunk evaluation of every row = the padding-free reference
    full = ev.evaluate({"pSortMB": vals}).outputs["j_totalCost"]
    for n in (1, 15, 16, 17, 33):   # around the chunk boundary
        res = ev.evaluate({"pSortMB": vals[:n]})
        assert len(res.total_cost) == n
        # same compiled chunk executable, rows now padded -> identical bits
        assert np.array_equal(res.outputs["j_totalCost"], full[:n])
        # and still equal (to round-off) to a fresh unchunked compile at size n
        ref = evaluate_unchunked(ev.base_cfg, {"pSortMB": vals[:n]})
        np.testing.assert_allclose(
            res.outputs["j_totalCost"], ref["j_totalCost"], rtol=1e-12
        )


def test_fixed_chunk_means_single_compile_across_grid_sizes():
    ev = ChunkedEvaluator(P, S, C, chunk=32)
    for n in (5, 31, 32, 100):
        ev.evaluate({"pSortMB": np.linspace(32.0, 256.0, n)})
    assert ev.eval_cache_size() == 1
    for n in (40, 64, 333):
        list(search_topk(ev, {"pSortMB": np.linspace(32.0, 256.0, n)}, k=3).entries)
    assert ev.topk_cache_size() == 1


def test_empty_grid_fails_intelligibly():
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    with pytest.raises(ValueError, match="empty"):
        ev.evaluate({"pSortMB": np.array([])})
    with pytest.raises(ValueError, match="empty"):
        ev.chunk_topk({"pSortMB": np.array([])}, k=1)


def test_evaluate_small_matches_chunked_costs():
    ev = ChunkedEvaluator(P, S, C, chunk=64)
    ov = {"pSortMB": np.array([50.0, 100.0, 200.0]), "pSortFactor": 25.0}
    np.testing.assert_allclose(
        ev.evaluate_small(ov).total_cost, ev.evaluate(ov).total_cost, rtol=1e-12
    )


def test_scalar_overrides_and_errors():
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    res = ev.evaluate({"pSortMB": np.array([64.0, 128.0]), "pSortFactor": 25.0})
    ref = ev.evaluate({"pSortMB": np.array([64.0, 128.0]),
                       "pSortFactor": np.array([25.0, 25.0])})
    assert np.array_equal(res.total_cost, ref.total_cost)
    with pytest.raises(KeyError):
        ev.evaluate({"nope": np.array([1.0])})
    with pytest.raises(ValueError):
        ev.evaluate({"pSortMB": 64.0})  # nothing batched
    with pytest.raises(ValueError):
        ev.evaluate({"pSortMB": np.array([1.0, 2.0]),
                     "pSortFactor": np.array([1.0])})


# ------------------------------------------------------------------
# top-k
# ------------------------------------------------------------------


def test_streamed_topk_agrees_with_numpy_oracle():
    ref = _oracle_cost(SPACE)
    k = 7
    # oracle ranking with the same deterministic tie-break (cost, then index)
    order = np.lexsort((np.arange(ref.size), ref))[:k]
    for chunk in (13, 50, 4096):
        ev = ChunkedEvaluator(P, S, C, chunk=chunk)
        res = search_topk(ev, SPACE, k=k)
        assert [e.index for e in res.entries] == [int(i) for i in order], chunk
        assert np.allclose([e.cost for e in res.entries], ref[order], rtol=0, atol=0)
        assert res.n_evaluated == ref.size
        assert res.n_valid == int(np.isfinite(ref).sum())
    # the winning assignment matches the grid row it claims to be
    best = res.entries[0]
    row = space_block(SPACE, best.index, best.index + 1)
    assert best.assignment == {k2: float(v[0]) for k2, v in row.items()}


def test_topk_k_larger_than_grid():
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    res = search_topk(ev, {"pSortMB": [64.0, 128.0]}, k=10)
    assert len(res.entries) == 2


# ------------------------------------------------------------------
# invalid configs: raise vs escape hatch
# ------------------------------------------------------------------


def test_best_raises_on_all_invalid_grid():
    res = evaluate_product_grid(P, S, C, INVALID_SPACE)
    assert not np.isfinite(res.total_cost).any()
    with pytest.raises(InvalidGridError):
        res.best()


def test_escape_hatch_routes_invalid_survivors_to_simulator():
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    res = search_topk(ev, INVALID_SPACE, k=2)
    assert res.n_valid == 0
    assert len(res.entries) == 2
    for e in res.entries:
        assert e.exact and not e.valid
        assert np.isfinite(e.cost) and e.cost > 0
        assert e.cost == pytest.approx(ev.exact_cost(e.assignment))
    assert res.entries[0].cost <= res.entries[1].cost
    # without the hatch the old behavior (nothing rankable) raises
    with pytest.raises(InvalidGridError):
        search_topk(ev, INVALID_SPACE, k=2, exact_fallback=False).best()


def test_coordinate_descent_all_invalid_routes_through_simulator():
    """Regression: on an all-invalid space, argmin of an all-inf sweep used
    to silently return ``best_cost == inf`` with an arbitrary assignment."""
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    res = coordinate_descent_ev(ev, INVALID_SPACE)
    assert np.isfinite(res.best_cost) and res.exact
    # best_cost is the exact-simulator cost of the returned assignment
    assert res.best_cost == pytest.approx(ev.exact_cost(res.best_assignment))
    # ...and it is the optimum the simulator sees over the (tiny) grid
    exact_grid = [
        ev.exact_cost({k: float(v[0]) for k, v in
                       space_block(INVALID_SPACE, i, i + 1).items()})
        for i in range(space_size(INVALID_SPACE))
    ]
    assert res.best_cost == pytest.approx(min(exact_grid))


def test_coordinate_descent_all_invalid_raises_without_hatch():
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    with pytest.raises(InvalidGridError):
        coordinate_descent_ev(ev, INVALID_SPACE, exact_fallback=False)


def test_coordinate_descent_valid_space_unchanged():
    """The hatch must not perturb descent on a space with valid configs."""
    ev = ChunkedEvaluator(P, S, C, chunk=64)
    a = coordinate_descent_ev(ev, SPACE)
    b = coordinate_descent_ev(ev, SPACE, exact_fallback=False)
    assert a.best_assignment == b.best_assignment
    assert a.best_cost == b.best_cost and not a.exact


def test_seed_wrappers_forward_exact_fallback():
    """Regression: grid_search/random_search/coordinate_descent dropped the
    exact_fallback flag instead of forwarding it to the _ev strategies."""
    # hatch on (default): all-invalid space still yields a usable result
    res = grid_search(P, S, C, INVALID_SPACE, chunk=8)
    assert np.isfinite(res.best_cost)
    assert res.topk.best().exact
    # hatch explicitly off: nothing rankable -> raise, not a silent inf
    with pytest.raises(InvalidGridError):
        grid_search(P, S, C, INVALID_SPACE, chunk=8, exact_fallback=False)
    with pytest.raises(InvalidGridError):
        random_search(P, S, C, INVALID_SPACE, samples=16, chunk=8,
                      exact_fallback=False)
    res = random_search(P, S, C, INVALID_SPACE, samples=16, chunk=8)
    assert np.isfinite(res.best_cost)


def test_mixed_grid_prefers_valid_configs():
    space = {"pSortMB": [0.25, 100.0], "pSortFactor": [2.0, 10.0]}
    ev = ChunkedEvaluator(P, S, C, chunk=8)
    res = search_topk(ev, space, k=4)
    assert 0 < res.n_valid < res.n_evaluated
    kinds = [e.valid for e in res.entries]
    # all valid entries come before any exact-costed invalid one
    assert kinds == sorted(kinds, reverse=True)


# ------------------------------------------------------------------
# multi-device sharding (subprocess with 8 forced host devices)
# ------------------------------------------------------------------


def test_sharded_matches_single_device_on_8_devices():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            + os.environ.get("XLA_FLAGS", "")
        )
        import numpy as np, jax
        jax.config.update("jax_enable_x64", True)
        from repro.core.hadoop import CostFactors, HadoopParams, MiB, ProfileStats
        from repro.search import (CROSS_PROGRAM_MAX_ULP, ChunkedEvaluator,
                                  evaluate_unchunked, search_topk, ulp_distance)
        assert jax.local_device_count() == 8
        P = HadoopParams(pNumNodes=8, pNumMappers=64, pNumReducers=16,
                         pSplitSize=128 * MiB)
        S, C = ProfileStats(sMapSizeSel=0.8), CostFactors()
        ev = ChunkedEvaluator(P, S, C, chunk=40)   # rounded up to 8 devices
        assert ev.chunk % 8 == 0
        vals = np.linspace(16.0, 512.0, 101)       # non-divisible batch
        res = ev.evaluate({"pSortMB": vals})
        ref = evaluate_unchunked(ev.base_cfg, {"pSortMB": vals})
        # different executables: agreement within the stated ulp bound
        assert np.array_equal(res.outputs["valid"], ref["valid"])
        ulps = ulp_distance(res.outputs["j_totalCost"], ref["j_totalCost"])
        assert ulps.max() <= CROSS_PROGRAM_MAX_ULP, ulps.max()
        top = search_topk(ev, {"pSortMB": list(vals)}, k=3)
        order = np.lexsort((np.arange(101), np.where(ref["valid"] > 0,
                            ref["j_totalCost"], np.inf)))[:3]
        assert [e.index for e in top.entries] == [int(i) for i in order]
        print("OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ------------------------------------------------------------------
# TPU evaluator behind the same interface
# ------------------------------------------------------------------


def test_tpu_evaluator_shares_the_strategy_stack():
    pytest.importorskip("repro.configs")
    from repro.configs import SHAPES, get_config

    cfg = get_config("gemma2-9b")
    shape = SHAPES["train_4k"]
    ev = TpuEvaluator(cfg, shape, n_chips=256)
    space = {"dp": [16.0, 32.0, 64.0, 3.0], "tp": [16.0, 8.0, 4.0],
             "n_micro": [1.0, 2.0]}
    res = grid_search_ev(ev, space, exact_fallback=False)
    assert np.isfinite(res.best_cost)
    a = res.best_assignment
    assert a["dp"] * a["tp"] == 256          # chip budget respected
    # oracle: direct step_model on every valid candidate
    from repro.core.tpu_model import TpuParams, step_model
    best = min(
        step_model(cfg, shape, TpuParams(dp=dp, tp=tp, n_micro=nm,
                                         ep=1)).overlap_s
        for dp in (16, 32, 64) for tp in (16, 8, 4) for nm in (1, 2)
        if dp * tp == 256 and shape.global_batch % dp == 0
        and (shape.global_batch // dp) % nm == 0
    )
    assert res.best_cost == pytest.approx(best)


# ------------------------------------------------------------------
# ulp distance (the cross-executable agreement bound)
# ------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_ulp_distance_counts_representable_steps(dtype):
    from repro.search import ulp_distance

    x = np.array([1.0, 3.5e3, 0.0, np.inf, 7.0], dtype=dtype)
    up = np.nextafter(x, dtype(np.inf))
    two = np.nextafter(up, dtype(np.inf))
    assert ulp_distance(x, x).tolist() == [0] * 5
    assert ulp_distance(x[:3], up[:3]).tolist() == [1, 1, 1]
    assert ulp_distance(two[:3], x[:3]).tolist() == [2, 2, 2]
    assert ulp_distance(np.array([0.0], dtype), np.array([-0.0], dtype))[0] == 0
    neg = ulp_distance(np.array([1.0], dtype), np.array([-1.0], dtype))[0]
    assert neg == np.iinfo(np.int64).max
    with pytest.raises(TypeError):
        ulp_distance(np.arange(3), np.arange(3))
