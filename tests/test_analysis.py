"""repro.analysis — the analyzer's own regression suite.

Three layers:

* interval-domain unit tests (the abstract arithmetic the checkers rely on),
* known-bad fixtures — every checker must fire on its fixture and stay
  silent on the registered models (modulo the checked-in baseline),
* the guard-reversion gate: monkeypatching the PR-6 double-``where`` guard
  in ``core/hadoop/model.py`` back to single-``where`` MUST re-fire
  nan-hazard, proving the CI gate actually protects that fix.
"""

from __future__ import annotations

import json
import math
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# ---------------------------------------------------------------------------
# interval domain
# ---------------------------------------------------------------------------


def test_interval_attains_zero_respects_openness():
    from repro.analysis.interval import Interval

    assert Interval(0.0, 5.0).attains_zero
    assert not Interval(0.0, 5.0, lo_open=True).attains_zero
    assert Interval(-1.0, 1.0).attains_zero          # interior zero
    assert not Interval(1.0, 2.0).attains_zero


def test_interval_open_infinity_is_not_attained():
    from repro.analysis.interval import Interval

    unbounded = Interval(0.0, math.inf, False, True)   # open at inf
    assert not unbounded.attains_pinf
    literal_inf = Interval(0.0, math.inf, False, False)
    assert literal_inf.attains_pinf


def test_interval_mul_has_no_spurious_zero_times_inf_corner():
    from repro.analysis.interval import Interval

    a = Interval(0.0, math.inf, True, True)            # (0, inf)
    p = a.mul(a)
    assert (p.lo, p.hi) == (0.0, math.inf)
    assert p.lo_open and p.hi_open                     # still (0, inf)
    assert not p.maybe_nan


def test_interval_mul_signs_and_nan():
    from repro.analysis.interval import Interval

    a = Interval(-2.0, 3.0)
    b = Interval(-1.0, 4.0)
    p = a.mul(b)
    assert (p.lo, p.hi) == (-8.0, 12.0)
    # attained 0 times attained inf => possible nan
    z = Interval(0.0, 1.0)
    inf = Interval(0.0, math.inf, False, False)
    assert z.mul(inf).maybe_nan


def test_interval_div_by_zero_capable_denominator():
    from repro.analysis.interval import Interval

    num = Interval(1.0, 2.0)
    den = Interval(0.0, 5.0)
    q = num.div(den)
    assert q.hi == math.inf
    # guarded denominator (0 excluded) divides clean
    den_open = Interval(0.0, 5.0, lo_open=True)
    q2 = num.div(den_open)
    assert not q2.maybe_nan


def test_interval_hull_and_intersect():
    from repro.analysis.interval import Interval

    a = Interval(0.0, 2.0)
    b = Interval(1.0, 5.0, hi_open=True)
    h = a.hull(b)
    assert (h.lo, h.hi, h.lo_open, h.hi_open) == (0.0, 5.0, False, True)
    i = a.intersect(b)
    assert (i.lo, i.hi) == (1.0, 2.0)


# ---------------------------------------------------------------------------
# abstract interpretation end-to-end
# ---------------------------------------------------------------------------


def test_absint_flags_unguarded_division():
    import jax
    import jax.numpy as jnp

    from repro.analysis.absint import analyze_jaxpr
    from repro.analysis.interval import Interval

    def f(x):
        return 1.0 / x

    closed = jax.make_jaxpr(f)(jnp.asarray(1.0))
    an = analyze_jaxpr(closed, [Interval(0.0, math.inf, False, True)])
    assert any(e.kind == "div0" for e in an.events)


def test_absint_double_where_guard_suppresses_div0():
    import jax
    import jax.numpy as jnp

    from repro.analysis.absint import analyze_jaxpr
    from repro.analysis.interval import Interval

    def f(x):
        ok = x > 0.0
        return jnp.where(ok, 1.0 / jnp.where(ok, x, 1.0), jnp.inf)

    closed = jax.make_jaxpr(f)(jnp.asarray(1.0))
    an = analyze_jaxpr(closed, [Interval(0.0, math.inf, False, True)])
    assert not [e for e in an.events if e.kind == "div0"], (
        "guard refinement through jit[_where]/select_n broke")


def test_absint_ste_interior_exempt_in_grad_mode():
    import jax
    import jax.numpy as jnp

    from repro.analysis.absint import analyze_jaxpr
    from repro.analysis.interval import FINITE_TOP
    from repro.core.hadoop.merge_math import ste_floor

    def good(x):
        return ste_floor(x) * x

    def bad(x):
        return jnp.floor(x) * x

    x = jnp.asarray(4.0)
    an_good = analyze_jaxpr(jax.make_jaxpr(good)(x), [FINITE_TOP],
                            grad_mode=True)
    an_bad = analyze_jaxpr(jax.make_jaxpr(bad)(x), [FINITE_TOP],
                           grad_mode=True)
    assert not [e for e in an_good.events if e.kind == "rounding"]
    assert [e for e in an_bad.events if e.kind == "rounding"]


def test_ste_helpers_forward_values_unchanged():
    import jax
    import jax.numpy as jnp

    from repro.core.hadoop.merge_math import ste_ceil, ste_floor, ste_round

    x = jnp.asarray([0.2, 1.5, -2.7, 3.0])
    assert jnp.array_equal(ste_floor(x), jnp.floor(x))
    assert jnp.array_equal(ste_ceil(x), jnp.ceil(x))
    assert jnp.array_equal(ste_round(x), jnp.round(x))
    # straight-through gradient is 1 (not 0) on finite inputs
    g = jax.grad(lambda v: ste_floor(v) * 2.0)(1.7)
    assert float(g) == 2.0


# ---------------------------------------------------------------------------
# checkers: known-bad fixtures fire, registered models stay clean
# ---------------------------------------------------------------------------


def test_every_checker_fires_on_its_fixture():
    from repro.analysis.fixtures import selftest

    results = selftest()
    assert sorted(results) == sorted(
        ["nan-hazard", "grad-blocker", "recompile-hazard", "mask-contract",
         "pallas-kernel"])
    for name, findings in results.items():
        assert findings, f"checker {name} no longer fires on its fixture"


def test_fixture_finding_kinds():
    from repro.analysis.fixtures import selftest

    results = selftest()
    kinds = {n: {f.kind for f in fs} for n, fs in results.items()}
    assert "div0" in kinds["nan-hazard"]
    assert "rounding" in kinds["grad-blocker"]
    assert {"weak_type_input", "trace_error"} <= kinds["recompile-hazard"]
    assert "unmasked_total" in kinds["mask-contract"]
    assert {"block_divisibility", "index_map_arity"} <= kinds["pallas-kernel"]


@pytest.fixture(scope="module")
def full_report():
    from repro.analysis import run_all

    return run_all()


def test_registered_models_clean_or_baselined(full_report):
    from repro.analysis import DEFAULT_BASELINE, load_baseline

    baseline = load_baseline(str(ROOT / DEFAULT_BASELINE))
    new = full_report.new_findings(baseline)
    assert not new, (
        "non-baselined findings on registered models:\n" + "\n".join(
            f"{f.checker}/{f.kind} {f.target} {f.location}: {f.message}"
            for f in new))


def test_report_covers_every_registered_target(full_report):
    from repro.analysis import iter_targets

    names = {t.name for t in iter_targets()}
    assert {"hadoop-model", "hadoop-grad", "calib-loss", "tuner-objective",
            "cluster-rollout", "tpu-model"} <= names
    # untraceable targets are reported as skipped-with-reason, not dropped
    assert "tpu-model" in full_report.skipped
    assert full_report.skipped["tpu-model"]


def test_no_unmodeled_primitives_on_registered_models(full_report):
    assert not full_report.coverage_gaps, (
        "interval transfer functions missing for primitives: "
        f"{full_report.coverage_gaps}")


# ---------------------------------------------------------------------------
# the reversion gate: un-fixing the PR-6 guard must fail CI
# ---------------------------------------------------------------------------


def test_reverting_masked_div_guard_refires_nan_hazard(monkeypatch):
    import jax.numpy as jnp

    import repro.core.hadoop.model as model
    from repro.analysis import run_all

    def single_where_div(num, den, ok):    # the pre-PR-6 buggy form
        return jnp.where(ok, num / den, jnp.inf)

    monkeypatch.setattr(model, "_masked_div", single_where_div)
    report = run_all(checkers=["nan-hazard"])
    hits = [f for f in report.findings
            if f.kind == "div0" and f.target == "hadoop-model"]
    assert hits, (
        "nan-hazard no longer detects the single-where masked division — "
        "the CI gate would not catch a reversion of the PR-6 guard")
    # and the gate logic itself: these findings are not in the baseline
    from repro.analysis import DEFAULT_BASELINE, load_baseline

    baseline = load_baseline(str(ROOT / DEFAULT_BASELINE))
    assert report.new_findings(baseline), "reversion finding was baselined?!"


def test_reverting_p95_latency_guard_refires_nan_hazard():
    """The cluster-side true positive fixed in this PR: ``jnp.percentile``
    computes ``lo*(1-frac) + hi*frac`` between sorted neighbours; whenever
    ``0.95*(n-1)`` lands on an integer (n=21 jobs, say) one weight is
    exactly 0 and an infinite (unconverged) latency makes it ``0 * inf``."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.absint import analyze_jaxpr
    from repro.analysis.interval import Interval

    def unguarded(latency):
        return jnp.percentile(latency, 95.0)

    def guarded(latency):
        finite = jnp.isfinite(latency)
        lat_safe = jnp.where(finite, latency, 0.0)
        return jnp.percentile(lat_safe, 95.0)

    lat = jnp.zeros((21,))                 # 0.95 * 20 == 19: frac == 0
    ival = [Interval(0.0, math.inf, False, False)]   # inf is attained
    an_bad = analyze_jaxpr(jax.make_jaxpr(unguarded)(lat), ival)
    an_good = analyze_jaxpr(jax.make_jaxpr(guarded)(lat), ival)
    bad_kinds = {e.kind for e in an_bad.events}
    assert "zero_times_inf" in bad_kinds, bad_kinds
    assert not an_good.events


# ---------------------------------------------------------------------------
# baseline round-trip + CLI
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    from repro.analysis import Report, load_baseline, save_baseline
    from repro.analysis.findings import Finding

    f = Finding(checker="nan-hazard", target="demo", kind="div0",
                message="m", location="a/b.py:3 in fn")
    rep = Report(findings=[f])
    path = tmp_path / "baseline.json"
    save_baseline(str(path), rep)
    fps = load_baseline(str(path))
    assert fps == {f.fingerprint()}
    assert not rep.new_findings(fps)
    # fingerprints survive a line-number move but not a file move
    f2 = Finding(checker="nan-hazard", target="demo", kind="div0",
                 message="m", location="a/b.py:99 in fn")
    assert f2.fingerprint() in fps
    f3 = Finding(checker="nan-hazard", target="demo", kind="div0",
                 message="m", location="a/other.py:3 in fn")
    assert f3.fingerprint() not in fps
    assert rep.stale_baseline(fps | {"ghost|x|y|z|w"}) == ["ghost|x|y|z|w"]


def test_missing_baseline_is_empty():
    from repro.analysis import load_baseline

    assert load_baseline("/nonexistent/baseline.json") == set()


def _cli_env():
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_ENABLE_X64"] = "1"
    return env


def test_cli_smoke_subprocess():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--smoke"],
        capture_output=True, text=True, cwd=str(ROOT), env=_cli_env(),
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all checkers fire" in proc.stdout


def test_cli_json_report():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json",
         "--checker", "pallas-kernel", "--checker", "mask-contract"],
        capture_output=True, text=True, cwd=str(ROOT), env=_cli_env(),
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["checkers_run"] == ["mask-contract", "pallas-kernel"]
    assert payload["findings"] == []
    assert "new_findings" in payload and "stale_baseline" in payload


# ---------------------------------------------------------------------------
# pallas geometry validation (pure, no monkeypatching needed)
# ---------------------------------------------------------------------------


def test_validate_launch_accepts_good_geometry():
    import jax

    from repro.analysis.checkers.pallas_kernel import validate_launch

    class Spec:
        def __init__(self, block_shape, index_map):
            self.block_shape = block_shape
            self.index_map = index_map

    class Op:
        def __init__(self, shape):
            self.shape = shape

    out = validate_launch(
        name="demo",
        kernel=lambda x_ref, o_ref: None,
        grid=(4, 8),
        in_specs=[Spec((1, 128), lambda i, j: (i, j))],
        out_specs=Spec((1, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((4, 1024), "float32"),
        scratch_shapes=None,
        compiler_params=None,
        operands=[Op((4, 1024))],
        location="test")
    assert out == []


def test_validate_launch_rejects_bad_geometry():
    import jax

    from repro.analysis.checkers.pallas_kernel import validate_launch

    class Spec:
        def __init__(self, block_shape, index_map):
            self.block_shape = block_shape
            self.index_map = index_map

    class Op:
        def __init__(self, shape):
            self.shape = shape

    out = validate_launch(
        name="demo",
        kernel=lambda x_ref: None,               # missing the out ref
        grid=(4,),
        in_specs=[Spec((1, 300), lambda i, j: (i, j))],   # 2-ary for 1-d grid
        out_specs=Spec((1, 300), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, 1000), "float32"),
        scratch_shapes=None,
        compiler_params=None,
        operands=[Op((4, 1000))],
        location="test")
    kinds = {f.kind for f in out}
    assert {"block_divisibility", "index_map_arity", "kernel_arity"} <= kinds
