"""repro.search: chunked/sharded evaluator, streaming top-k, escape hatch.

Covers the contract the subsystem was built around:
* chunked+sharded evaluation is bit-for-bit identical to the seed's
  unchunked single-device ``jit(vmap(...))`` path;
* padding at non-divisible batch sizes changes nothing;
* the one packed copy per chunk and dtype returns the bits, keys and dtypes
  of copying each output column on its own;
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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hadoop import CostFactors, HadoopParams, MiB, ProfileStats
from repro.core.whatif import evaluate_grid, evaluate_product_grid
from repro.obs import observe
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
from repro.search.evaluator import split_overrides

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


def _per_column_fetch(ev, overrides):
    """Every output column of ``ev``'s model program, chunk by chunk, each
    copied to the host on its own: what ``evaluate`` fetched before it
    packed the columns into one copy per dtype."""
    batched, static, n = ev._split(overrides)
    blocks = {}
    for start in range(0, n, ev.chunk):
        stop = min(start + ev.chunk, n)
        cols, _ = ev._pad(batched, start, stop)
        for k, v in ev._eval_fn(cols, static).items():
            blocks.setdefault(k, []).append(np.asarray(v)[: stop - start])
    return {k: np.concatenate(v) for k, v in blocks.items()}


def _bits(a):
    return a.view(f"u{a.itemsize}")


@pytest.mark.parametrize("x64", [False, True], ids=["float32", "float64"])
@pytest.mark.parametrize("scalar", [False, True], ids=["batched", "scalar"])
def test_evaluate_matches_per_column_fetch_bit_for_bit(x64, scalar):
    with jax.enable_x64(x64):
        ev = ChunkedEvaluator(P, S, C, chunk=32)
        n = 2 * ev.chunk + 7                      # three chunks, ragged tail
        rng = np.random.default_rng(5)
        ov = {"pSortMB": rng.choice(SPACE["pSortMB"], n),
              "pSortFactor": 25.0 if scalar else rng.choice(SPACE["pSortFactor"], n)}
        res = ev.evaluate(ov)
        ref = _per_column_fetch(ev, ov)
    assert set(res.outputs) == set(ref)
    for k, v in ref.items():
        got = res.outputs[k]
        assert got.dtype == v.dtype == (np.float64 if x64 else np.float32), k
        assert got.shape == (n,), k
        assert np.array_equal(_bits(got), _bits(v)), k
    assert np.array_equal(_bits(res.total_cost),
                          _bits(np.where(ref["valid"] > 0, ref["j_totalCost"], np.inf)))
    assert ev.eval_cache_size() == 1


def test_evaluate_keeps_each_output_dtype_exact():
    def toy(cfg):       # float32, int32 and bool outputs from one row
        x = cfg["pSortMB"]
        return {"j_totalCost": (x * 1.5).astype(jnp.float32),
                "valid": (x > 20.0).astype(jnp.int32),
                "big": x > 100.0,
                "count": x.astype(jnp.int32) + 16_777_217}   # not a float32

    ev = ChunkedEvaluator(P, S, C, chunk=16, model_fn=toy)
    vals = np.linspace(8.0, 400.0, 40)                 # three chunks
    with observe() as ob:
        res = ev.evaluate({"pSortMB": vals})
    ref = _per_column_fetch(ev, {"pSortMB": vals})
    want = {"j_totalCost": np.float32, "valid": np.int32, "big": np.bool_,
            "count": np.int32}
    assert {k: v.dtype for k, v in res.outputs.items()} == want
    for k, v in ref.items():
        assert np.array_equal(_bits(res.outputs[k]), _bits(v)), k
    assert np.array_equal(res.outputs["count"],
                          vals.astype(np.int32) + 16_777_217)
    assert np.array_equal(res.outputs["big"], vals > 100.0)
    # one copy per chunk per output dtype
    assert ob.registry.counter("evaluator.d2h_copies").value == 3 * 3


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
# split_overrides: columns cast on the host
# ------------------------------------------------------------------


def _split_via_device(base_cfg, overrides):
    """The reference split: every override cast by ``jnp.asarray`` on the
    device, batched columns copied back to the host."""
    static = dict(base_cfg)
    batched = {}
    n = None
    for k, v in overrides.items():
        if k not in base_cfg:
            raise KeyError(f"unknown config key: {k!r}")
        arr = jnp.asarray(v, dtype=base_cfg[k].dtype)
        if arr.ndim > 1:
            raise ValueError(f"override {k!r} must be scalar or 1-D")
        if arr.ndim == 1:
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise ValueError("all batched overrides must share a length")
            batched[k] = np.asarray(arr)
        else:
            static[k] = arr
    if n is None:
        raise ValueError("at least one override must be batched")
    if n == 0:
        raise ValueError("batched overrides are empty (0-length grid)")
    return batched, static, n


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


# float32 rounding edges: ties to even either way, subnormals and the tie
# below the smallest one, overflow (incl. the tie above float32's max), signed
# zeros and the non-finite values
EDGE_F64 = np.array([
    1 + 2.0**-24, 1 + 3 * 2.0**-24, -(1 + 2.0**-24), 1e-40, 2.0**-149,
    2.0**-150, 1.5 * 2.0**-150, 1e39, -1e39, 3.4028235677973366e38,
    0.0, -0.0, np.nan, -np.nan, np.inf, -np.inf, 0.1, 1e20,
])
EDGE_I64 = np.array([0, 1, -1, 2**24 + 1, 2**53 + 1, 2**62, -(2**63)] * 2 + [7, 9, 11, 13],
                    dtype=np.int64)
assert EDGE_I64.size == EDGE_F64.size

# each builds one column inside the base dtype's x64 setting
COLUMNS = {
    "float64": lambda: EDGE_F64.copy(),
    "float32": lambda: EDGE_F64.astype(np.float32),
    "int64": lambda: EDGE_I64.copy(),
    "bool": lambda: np.arange(EDGE_F64.size) % 3 == 0,
    "float list": lambda: EDGE_F64.tolist(),
    "int list": lambda: [int(x) for x in EDGE_I64],
    "jax float64": lambda: jnp.asarray(EDGE_F64),
    "jax float32": lambda: jnp.asarray(EDGE_F64.astype(np.float32)),
    "jax int": lambda: jnp.asarray(EDGE_I64 // 4),
}
# (base dtype, x64): float32 and bfloat16 as the chip runs them and as the
# tests' x64 default holds them; float64 only exists under x64
BASES = [("float32", False), ("float32", True), ("bfloat16", False),
         ("bfloat16", True), ("float64", True)]


@pytest.mark.parametrize("column", list(COLUMNS))
@pytest.mark.parametrize("base", BASES, ids=lambda b: f"{b[0]}-x64{int(b[1])}")
def test_split_overrides_matches_device_round_trip(base, column):
    dt, x64 = base
    keys = ["col", "col2", "s_float", "s_int", "s_bool", "s_np", "s_jax", "kept"]
    with jax.enable_x64(x64), np.errstate(over="ignore", invalid="ignore"):
        base_cfg = {k: jnp.asarray(0.5, dtype=dt) for k in keys}
        overrides = {
            "col": COLUMNS[column](), "col2": np.linspace(0.0, 1.0, EDGE_F64.size),
            "s_float": 0.1, "s_int": 7, "s_bool": True,
            "s_np": np.float64(2.0**-150), "s_jax": jnp.asarray(1e39),
        }
        batched, static, n = split_overrides(base_cfg, overrides)
        ref_b, ref_s, ref_n = _split_via_device(base_cfg, overrides)
    assert n == ref_n == EDGE_F64.size
    assert batched.keys() == ref_b.keys() == {"col", "col2"}
    for k, v in batched.items():
        assert type(v) is np.ndarray and v.dtype == ref_b[k].dtype == np.dtype(dt), k
        assert np.array_equal(_bits(v), _bits(ref_b[k])), k
    assert static.keys() == ref_s.keys() == set(keys)
    for k, v in static.items():
        assert isinstance(v, jax.Array) and v.dtype == ref_s[k].dtype, k
        assert np.array_equal(_bits(v), _bits(ref_s[k])), k
    assert static["kept"] is base_cfg["kept"]


@pytest.mark.parametrize("overrides, err, match", [
    ({"nope": np.ones(2)}, KeyError, "unknown config key: 'nope'"),
    ({"a": np.ones((2, 2))}, ValueError, "override 'a' must be scalar or 1-D"),
    ({"a": np.ones(2), "b": np.ones(3)}, ValueError, "must share a length"),
    ({"a": 1.0}, ValueError, "at least one override must be batched"),
    ({"a": np.ones(0)}, ValueError, r"empty \(0-length grid\)"),
], ids=["unknown", "2-d", "lengths", "no-batched", "empty"])
def test_split_overrides_errors_unchanged(overrides, err, match):
    base_cfg = {"a": jnp.asarray(0.0, jnp.float32), "b": jnp.asarray(0.0, jnp.float32)}
    for split in (split_overrides, _split_via_device):
        with pytest.raises(err, match=match):
            split(base_cfg, overrides)


def test_split_overrides_puts_no_column_on_the_device():
    # float32 base, as on the chip: the grid's float64 columns need a cast
    with jax.enable_x64(False):
        ev = ChunkedEvaluator(P, S, C, chunk=64)
        cols = space_block(SPACE, 0, 50)
        with jax.transfer_guard_host_to_device("disallow"):
            batched, static, n = split_overrides(ev.base_cfg, cols)
            # the guard is live: the old split casts each column on the device
            with pytest.raises(Exception, match="Disallowed host-to-device transfer"):
                _split_via_device(ev.base_cfg, cols)
    assert n == 50 and batched.keys() == cols.keys()
    assert static.keys() == ev.base_cfg.keys()


@pytest.mark.parametrize("dt, x64", [("float32", False), ("float32", True),
                                     ("float64", True)])
def test_split_overrides_copies_caller_columns(dt, x64):
    with jax.enable_x64(x64):
        base_cfg = {"a": jnp.asarray(0.0, dt), "b": jnp.asarray(0.0, dt)}
        a = np.linspace(1.0, 2.0, 9, dtype=dt)    # the base's dtype: no cast
        b = np.linspace(3.0, 4.0, 9)
        batched, _, _ = split_overrides(base_cfg, {"a": a, "b": b})
    want = {k: v.copy() for k, v in batched.items()}
    assert not np.shares_memory(batched["a"], a)
    a[:] = -1.0
    b[:] = -1.0
    for k in want:
        assert np.array_equal(batched[k], want[k]), k


@pytest.mark.parametrize("space", [
    SPACE,
    {"pSortMB": [0.25, 1.0, 100.0, 200.0], "pSortFactor": [2.0, 10.0],
     "pNumReducers": [0.0, 4.0, 16.0]},
], ids=["valid", "mixed"])
def test_chunk_topk_unchanged_by_host_casts(space, monkeypatch):
    import repro.search.evaluator as evaluator

    n = space_size(space)
    with jax.enable_x64(False):     # float32 base, as on the chip
        ev = ChunkedEvaluator(P, S, C, chunk=256)
        cols = space_block(space, 0, n)
        new = ev.chunk_topk(cols, k=5)
        monkeypatch.setattr(evaluator, "split_overrides", _split_via_device)
        old = ev.chunk_topk(cols, k=5)
    assert 0 < old.n_valid <= n and new.n_valid == old.n_valid
    for f in ("costs", "idx", "inv_costs", "inv_idx"):
        a, b = getattr(new, f), getattr(old, f)
        assert a.dtype == b.dtype and np.array_equal(_bits(a), _bits(b)), f
    assert new.reason_counts == old.reason_counts


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
        # the packed fetch of sharded columns: the same bits as copying
        # each column of the model program's output on its own
        batched, static, n = ev._split({"pSortMB": vals})
        cols = {}
        for start in range(0, n, ev.chunk):
            stop = min(start + ev.chunk, n)
            padded, _ = ev._pad(batched, start, stop)
            for k, v in ev._eval_fn(padded, static).items():
                cols.setdefault(k, []).append(np.asarray(v)[: stop - start])
        assert set(cols) == set(res.outputs)
        for k, v in cols.items():
            v = np.concatenate(v)
            got = res.outputs[k]
            assert got.dtype == v.dtype, k
            assert np.array_equal(got.view(f"u{got.itemsize}"),
                                  v.view(f"u{v.itemsize}")), k
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
