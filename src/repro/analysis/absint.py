"""Jaxpr abstract interpretation over the :mod:`repro.analysis.interval` domain.

:func:`analyze_jaxpr` walks a closed jaxpr, propagating an
:class:`~repro.analysis.interval.Interval` per variable, and records
**events** at hazardous primitives (division, log, rsqrt, ...) for the
checkers to turn into findings.  Three mechanics beyond plain interval
arithmetic:

* **Sub-jaxpr recursion with provenance.**  ``jnp.where`` traces to a
  ``jit[name=_where]`` wrapping ``select_n``; every higher-order primitive
  (``jit``, ``custom_jvp_call``, ``while``, ``scan``, ``cond``,
  ``remat``/``checkpoint``) is entered with an environment mapping so
  refinement information crosses the call boundary.  Each abstract value
  carries a *provenance token* — the id of the outermost variable it is a
  pass-through of — so a comparison on ``x`` can refine a ``select_n`` case
  that is ``x`` routed through a jit boundary.

* **Predicate refinement at select_n.**  ``select_n(pred, on_false,
  on_true)`` with ``pred = gt(x, c)`` (or ge/lt/le/isfinite) narrows the
  interval of the ``on_true`` case when that case *is* ``x`` (by
  provenance), and symmetrically for ``on_false``.  This is exactly how a
  double-``where`` guard proves the guarded denominator non-zero — and why
  reverting the guard (dividing *before* the select) re-fires the hazard.

* **while fixpoint with widening.**  Loop bodies are iterated with the
  carry intervals joined; after a few iterations unstable bounds widen to
  open infinities ("unbounded but finite"), which terminates and stays
  sound for the attainability predicates.

Unknown primitives produce :data:`~repro.analysis.interval.FINITE_TOP` and
are recorded as coverage gaps rather than silently trusted.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

from .interval import BOOL, FINITE_TOP, TOP, Interval

__all__ = ["AbsValue", "Event", "Analysis", "analyze_jaxpr", "format_frame"]

_INF = math.inf


@dataclass(frozen=True)
class AbsValue:
    """An interval plus the provenance token of the value it passes through.

    ``origin`` is an opaque token (the jaxpr ``Var`` object at the outermost
    scope where the value was introduced).  It survives shape-only ops
    (broadcast, convert, reshape, ...) and sub-jaxpr boundaries, so guard
    predicates can be matched to the value they actually constrain.
    """

    ival: Interval
    origin: Any = None

    def with_ival(self, ival: Interval) -> "AbsValue":
        # a changed interval from a pass-through op keeps the origin;
        # callers that compute fresh values should construct AbsValue anew
        return AbsValue(ival, self.origin)


@dataclass
class Event:
    """One potentially hazardous primitive occurrence."""

    kind: str                 # "div0", "inf_minus_inf", "log_domain", ...
    prim: str                 # primitive name
    frame: Any                # source_info_util Frame or None
    detail: str               # human-readable interval story
    chain: tuple[str, ...]    # enclosing higher-order primitive path


@dataclass
class Analysis:
    """Result of one :func:`analyze_jaxpr` run."""

    events: list[Event] = field(default_factory=list)
    unknown_prims: set[str] = field(default_factory=set)
    out_vals: list[AbsValue] = field(default_factory=list)


# ---------------------------------------------------------------------------
# source locations
# ---------------------------------------------------------------------------


def _user_frame(eqn):
    try:
        from jax._src import source_info_util
        return source_info_util.user_frame(eqn.source_info)
    except Exception:
        return None


def format_frame(frame) -> str:
    if frame is None:
        return "<unknown>"
    fn = getattr(frame, "file_name", "?")
    line = getattr(frame, "start_line", getattr(frame, "line_num", 0))
    func = getattr(frame, "function_name", "?")
    return f"{fn}:{line} in {func}"


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

# ops whose single data operand passes through unchanged enough to keep
# provenance (shape/dtype adjustments and no-op math)
_PASS_THROUGH = {
    "broadcast_in_dim", "convert_element_type", "reshape", "squeeze",
    "expand_dims", "copy", "stop_gradient", "slice", "dynamic_slice",
    "transpose", "rev", "gather", "reduce_precision",
}

# comparison primitive -> (refinement for TRUE case, refinement for FALSE case)
# as functions of the comparison constant interval
def _refine_gt(c: Interval):
    t = Interval(c.lo, _INF, True, True)        # x > c: lo open at c.lo
    f = Interval(-_INF, c.hi, True, False)      # x <= c
    return t, f


def _refine_ge(c: Interval):
    t = Interval(c.lo, _INF, False, True)
    f = Interval(-_INF, c.hi, True, True)
    return t, f


def _refine_lt(c: Interval):
    t = Interval(-_INF, c.hi, True, True)
    f = Interval(c.lo, _INF, False, True)
    return t, f


def _refine_le(c: Interval):
    t = Interval(-_INF, c.hi, True, False)
    f = Interval(c.lo, _INF, True, True)
    return t, f


def _refine_isfinite(_c: Interval):
    t = Interval(-_INF, _INF, True, True)       # finite: open infinities
    f = TOP
    return t, f


_CMP_REFINERS: dict[str, Callable] = {
    "gt": _refine_gt, "ge": _refine_ge, "lt": _refine_lt, "le": _refine_le,
    "is_finite": _refine_isfinite,
}


@dataclass
class _Guard:
    """pred_var -> (origin being constrained, true-interval, false-interval)."""

    origin: Any
    true_ival: Interval
    false_ival: Interval


class _Interp:
    def __init__(self, analysis: Analysis, *,
                 grad_mode: bool = False,
                 max_while_iters: int = 3):
        self.an = analysis
        self.grad_mode = grad_mode
        self.max_while_iters = max_while_iters
        # predicate provenance: var-id of a boolean -> _Guard
        self.guards: dict[int, _Guard] = {}
        # values derived purely from comparisons/constants (validity flags);
        # stop_gradient on these is benign for the grad-blocker
        self.bool_derived: set[int] = set()
        self.chain: list[str] = []

    # ---- environment helpers ----

    @staticmethod
    def _is_literal(v) -> bool:
        return hasattr(v, "val") and not hasattr(v, "count")

    def read(self, env: dict, v) -> AbsValue:
        if self._is_literal(v):
            import numpy as np
            val = np.asarray(v.val)
            if val.size == 1:
                return AbsValue(Interval.point(float(val.reshape(-1)[0])), v)
            lo = float(val.min())
            hi = float(val.max())
            if math.isnan(lo) or math.isnan(hi):
                return AbsValue(Interval.point(float("nan")), v)
            return AbsValue(Interval(min(lo, hi), max(lo, hi)), v)
        return env[v]

    def is_bool_derived(self, env: dict, v) -> bool:
        if self._is_literal(v):
            return True
        av = env.get(v)
        if av is None:
            return False
        if id(av.origin) in self.bool_derived:
            return True
        # point-constants (e.g. a literal routed through a sub-jaxpr invar)
        # carry no gradient — neutral for the validity-flag taint
        iv = av.ival
        return iv.lo == iv.hi and not iv.maybe_nan

    def record(self, kind: str, eqn, detail: str):
        self.an.events.append(Event(
            kind=kind,
            prim=eqn.primitive.name,
            frame=_user_frame(eqn),
            detail=detail,
            chain=tuple(self.chain),
        ))

    # ---- main walk ----

    def run(self, jaxpr, in_vals: list[AbsValue]) -> list[AbsValue]:
        env: dict = {}
        for v, av in zip(jaxpr.invars, in_vals):
            # give fresh provenance to inputs that have none
            env[v] = av if av.origin is not None else AbsValue(av.ival, v)
        for cv in jaxpr.constvars:
            env[cv] = AbsValue(self._const_ival(cv), cv)
        for eqn in jaxpr.eqns:
            self.eqn(env, eqn)
        return [self.read(env, v) for v in jaxpr.outvars]

    def _const_ival(self, cv) -> Interval:
        aval = getattr(cv, "aval", None)
        # consts in closed jaxprs are bound separately; a bare constvar in a
        # sub-jaxpr is opaque here — treat as finite-unknown
        del aval
        return FINITE_TOP

    def run_closed(self, closed_jaxpr, in_vals: list[AbsValue]) -> list[AbsValue]:
        import numpy as np

        jaxpr = closed_jaxpr.jaxpr
        env: dict = {}
        for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
            try:
                arr = np.asarray(cval)
                if arr.dtype.kind in "fiub" and arr.size:
                    lo, hi = float(arr.min()), float(arr.max())
                    if math.isnan(lo) or math.isnan(hi):
                        ival = Interval.point(float("nan"))
                    else:
                        ival = Interval(lo, hi)
                else:
                    ival = FINITE_TOP
            except Exception:
                ival = FINITE_TOP
            env[cv] = AbsValue(ival, cv)
        for v, av in zip(jaxpr.invars, in_vals):
            env[v] = av if av.origin is not None else AbsValue(av.ival, v)
        for eqn in jaxpr.eqns:
            self.eqn(env, eqn)
        return [self.read(env, v) for v in jaxpr.outvars]

    # ---- per-equation transfer ----

    def eqn(self, env: dict, eqn):
        name = eqn.primitive.name
        handler = getattr(self, f"prim_{name}", None)
        if handler is not None:
            outs = handler(env, eqn)
        elif name in _PASS_THROUGH:
            src = self.read(env, eqn.invars[0])
            if name == "stop_gradient" and self.grad_mode \
                    and not self.is_bool_derived(env, eqn.invars[0]):
                self.record("stop_gradient", eqn,
                            "stop_gradient on a non-boolean value inside a "
                            "differentiated path zeroes its cotangent")
            outs = [src] * len(eqn.outvars)
        else:
            outs = self.generic(env, eqn)
        for v, av in zip(eqn.outvars, outs):
            env[v] = av

    def generic(self, env: dict, eqn):
        name = eqn.primitive.name
        ins = [self.read(env, v) for v in eqn.invars]
        out_ival = self._generic_ival(name, ins, eqn)
        if out_ival is None:
            self.an.unknown_prims.add(name)
            out_ival = FINITE_TOP
        return [AbsValue(out_ival, eqn.outvars[0] if eqn.outvars else None)
                for _ in eqn.outvars]

    # transfer functions for first-order math prims without special handling
    def _generic_ival(self, name: str, ins: list[AbsValue], eqn):
        iv = [a.ival for a in ins]
        if name in ("reduce_sum", "cumsum", "cumlogsumexp", "add_any"):
            # bounded-count over-approximation: n * per-element hull
            n = self._reduction_count(eqn)
            return iv[0].scale_by_count(n)
        if name in ("reduce_max", "reduce_min", "cummax", "cummin",
                    "reduce_and", "reduce_or", "argmax", "argmin",
                    "reduce_prod", "sort"):
            if name == "reduce_prod":
                return FINITE_TOP if not iv[0].maybe_nan else \
                    Interval(-_INF, _INF, True, True, True)
            if name in ("argmax", "argmin"):
                return Interval(0.0, _INF, False, True)
            if name in ("reduce_and", "reduce_or"):
                return BOOL
            return iv[0]
        if name in ("sin", "cos"):
            return Interval(-1.0, 1.0, maybe_nan=iv[0].maybe_nan
                            or iv[0].attains_inf)
        if name == "tanh":
            return Interval(-1.0, 1.0, maybe_nan=iv[0].maybe_nan)
        if name == "logistic":
            return Interval(0.0, 1.0, True, True, iv[0].maybe_nan)
        if name == "sign":
            return Interval(-1.0, 1.0, maybe_nan=iv[0].maybe_nan)
        if name in ("iota",):
            return Interval(0.0, _INF, False, True)
        if name in ("and", "or", "xor", "not"):
            return BOOL
        if name in ("eq", "ne"):
            return BOOL
        if name in ("clamp",):
            lo, x, hi = iv
            return x.max_(lo).min_(hi)
        if name in ("nextafter",):
            return iv[0]
        if name in ("erf",):
            return Interval(-1.0, 1.0, maybe_nan=iv[0].maybe_nan)
        if name in ("concatenate", "pad", "select_and_scatter_add",
                    "scatter", "scatter_add", "dynamic_update_slice"):
            out = iv[0]
            for other in iv[1:]:
                out = out.hull(other)
            return out
        if name in ("dot_general", "conv_general_dilated"):
            a, b = iv[0], iv[1]
            prod = a.mul(b)
            return prod.scale_by_count(self._reduction_count(eqn, default=64))
        if name == "square":
            return iv[0].mul(iv[0])
        if name == "percentile":
            return iv[0]
        return None

    @staticmethod
    def _reduction_count(eqn, default: int = 1 << 20) -> int:
        try:
            shape = eqn.invars[0].aval.shape
            n = 1
            for d in shape:
                n *= int(d)
            return max(n, 1)
        except Exception:
            return default

    # ---- arithmetic prims ----

    def _taint_binop(self, env, eqn):
        """Propagate the validity-flag taint: a value computed only from
        comparisons/constants stays bool-derived through arithmetic."""
        if all(self.is_bool_derived(env, v) for v in eqn.invars):
            self.bool_derived.add(id(eqn.outvars[0]))

    def _binop(self, env, eqn, fn) -> list[AbsValue]:
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        self._taint_binop(env, eqn)
        return [AbsValue(fn(a.ival, b.ival), eqn.outvars[0])]

    def prim_add(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        out = a.ival.add(b.ival)
        if (a.ival.attains_pinf and b.ival.attains_ninf) or \
                (a.ival.attains_ninf and b.ival.attains_pinf):
            self.record("inf_minus_inf", eqn,
                        f"add of {a.ival} and {b.ival} can be inf + -inf")
        self._taint_binop(env, eqn)
        return [AbsValue(out, eqn.outvars[0])]

    def prim_sub(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        out = a.ival.sub(b.ival)
        if (a.ival.attains_pinf and b.ival.attains_pinf) or \
                (a.ival.attains_ninf and b.ival.attains_ninf):
            self.record("inf_minus_inf", eqn,
                        f"sub of {a.ival} and {b.ival} can be inf - inf")
        return [AbsValue(out, eqn.outvars[0])]

    def prim_mul(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        if (a.ival.attains_inf and b.ival.attains_zero) or \
                (a.ival.attains_zero and b.ival.attains_inf):
            self.record("zero_times_inf", eqn,
                        f"mul of {a.ival} and {b.ival} can be 0 * inf")
        self._taint_binop(env, eqn)
        return [AbsValue(a.ival.mul(b.ival), eqn.outvars[0])]

    def prim_div(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        if b.ival.attains_zero:
            self.record("div0", eqn,
                        f"denominator {b.ival} attains 0 "
                        f"(numerator {a.ival})")
        if a.ival.attains_inf and b.ival.attains_inf:
            self.record("inf_over_inf", eqn,
                        f"inf/inf possible: {a.ival} / {b.ival}")
        return [AbsValue(a.ival.div(b.ival), eqn.outvars[0])]

    def prim_rem(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        if b.ival.attains_zero:
            self.record("div0", eqn, f"mod denominator {b.ival} attains 0")
        hi = b.ival.abs_().hi
        return [AbsValue(Interval(-hi, hi, True, True,
                                  a.ival.maybe_nan or b.ival.maybe_nan),
                         eqn.outvars[0])]

    def prim_max(self, env, eqn):
        return self._binop(env, eqn, Interval.max_)

    def prim_min(self, env, eqn):
        return self._binop(env, eqn, Interval.min_)

    def prim_neg(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.neg(), eqn.outvars[0])]

    def prim_abs(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.abs_(), eqn.outvars[0])]

    def prim_pow(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        if a.ival.contains_negative() and not (
                b.ival.lo == b.ival.hi and float(b.ival.lo).is_integer()):
            self.record("pow_domain", eqn,
                        f"negative base {a.ival} to non-integer power {b.ival}")
        return [AbsValue(FINITE_TOP, eqn.outvars[0])]

    def prim_integer_pow(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        y = int(eqn.params.get("y", 2))
        out = Interval.point(1.0)
        base = a.ival
        if y == 2:
            out = base.mul(base)
        elif y > 0:
            out = base
            for _ in range(min(y - 1, 4)):
                out = out.mul(base)
        elif y < 0:
            inv = Interval.point(1.0).div(base)
            if base.attains_zero:
                self.record("div0", eqn,
                            f"integer_pow({y}) of {base} divides by 0")
            out = inv
        return [AbsValue(out, eqn.outvars[0])]

    # ---- domain-restricted unary prims ----

    def _domain_unary(self, env, eqn, kind, lo_bad, fn, nan_at=None):
        a = self.read(env, eqn.invars[0])
        if a.ival.lo < lo_bad or (nan_at is not None and a.ival.attains(nan_at)):
            self.record(kind, eqn,
                        f"argument {a.ival} reaches the singular domain")
        return [AbsValue(a.ival.monotone(fn, nan_below=lo_bad, nan_at=nan_at),
                         eqn.outvars[0])]

    def prim_log(self, env, eqn):
        return self._domain_unary(env, eqn, "log_domain", 0.0, math.log,
                                  nan_at=0.0)

    def prim_log1p(self, env, eqn):
        return self._domain_unary(env, eqn, "log_domain", -1.0, math.log1p,
                                  nan_at=-1.0)

    def prim_sqrt(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        if a.ival.lo < 0:
            self.record("sqrt_domain", eqn,
                        f"argument {a.ival} can be negative")
        return [AbsValue(a.ival.monotone(math.sqrt, nan_below=0.0),
                         eqn.outvars[0])]

    def prim_rsqrt(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        if a.ival.lo < 0:
            self.record("sqrt_domain", eqn,
                        f"rsqrt argument {a.ival} can be negative")
        if a.ival.attains_zero:
            self.record("div0", eqn, f"rsqrt argument {a.ival} attains 0")
        return [AbsValue(Interval(0.0, _INF,
                                  True, not a.ival.attains_zero,
                                  a.ival.maybe_nan or a.ival.lo < 0),
                         eqn.outvars[0])]

    def prim_exp(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.monotone(math.exp), eqn.outvars[0])]

    def prim_expm1(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.monotone(math.expm1), eqn.outvars[0])]

    def prim_exp2(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.monotone(lambda v: 2.0 ** min(v, 1e3)),
                         eqn.outvars[0])]

    # ---- rounding (grad-relevant) ----

    def _rounding(self, env, eqn, mode):
        if self.grad_mode:
            self.record("rounding", eqn,
                        f"{mode} has zero derivative — gradients through "
                        "this path silently vanish")
        a = self.read(env, eqn.invars[0])
        return [AbsValue(a.ival.round_like(mode), eqn.outvars[0])]

    def prim_floor(self, env, eqn):
        return self._rounding(env, eqn, "floor")

    def prim_ceil(self, env, eqn):
        return self._rounding(env, eqn, "ceil")

    def prim_round(self, env, eqn):
        return self._rounding(env, eqn, "round")

    # ---- comparisons: register guards ----

    def _comparison(self, env, eqn, name):
        a = self.read(env, eqn.invars[0])
        b = self.read(env, eqn.invars[1])
        out = AbsValue(BOOL, eqn.outvars[0])
        refiner = _CMP_REFINERS.get(name)
        if refiner is not None and a.origin is not None:
            const = b.ival
            t, f = refiner(const)
            self.guards[id(eqn.outvars[0])] = _Guard(a.origin, t, f)
        self.bool_derived.add(id(eqn.outvars[0]))
        env[eqn.outvars[0]] = out
        return [out]

    def prim_gt(self, env, eqn):
        return self._comparison(env, eqn, "gt")

    def prim_ge(self, env, eqn):
        return self._comparison(env, eqn, "ge")

    def prim_lt(self, env, eqn):
        return self._comparison(env, eqn, "lt")

    def prim_le(self, env, eqn):
        return self._comparison(env, eqn, "le")

    def prim_is_finite(self, env, eqn):
        a = self.read(env, eqn.invars[0])
        out = AbsValue(BOOL, eqn.outvars[0])
        if a.origin is not None:
            t, f = _refine_isfinite(a.ival)
            self.guards[id(eqn.outvars[0])] = _Guard(a.origin, t, f)
        self.bool_derived.add(id(eqn.outvars[0]))
        return [out]

    def prim_convert_element_type(self, env, eqn):
        src = self.read(env, eqn.invars[0])
        new_dtype = eqn.params.get("new_dtype")
        if self.grad_mode and new_dtype is not None and \
                getattr(new_dtype, "kind", "f") in "iub" and \
                getattr(eqn.invars[0].aval.dtype, "kind", "f") == "f" and \
                not self.is_bool_derived(env, eqn.invars[0]):
            self.record("int_cast", eqn,
                        "float -> integer cast inside a differentiated path")
        if self.is_bool_derived(env, eqn.invars[0]):
            self.bool_derived.add(id(eqn.outvars[0]))
        # bool -> float conversions land in [0, 1]
        src_dtype = getattr(eqn.invars[0].aval, "dtype", None)
        if src_dtype is not None and getattr(src_dtype, "kind", "") == "b":
            return [AbsValue(BOOL, src.origin)]
        return [src]

    # ---- selection with guard refinement ----

    def prim_select_n(self, env, eqn):
        pred_v = eqn.invars[0]
        cases = [self.read(env, v) for v in eqn.invars[1:]]
        guard = None if self._is_literal(pred_v) else \
            self.guards.get(id(self._guard_key(env, pred_v)))
        refined = []
        for idx, case in enumerate(cases):
            ival = case.ival
            if guard is not None and case.origin is guard.origin:
                # select_n(pred, on_false, on_true)
                ref = guard.true_ival if idx == 1 else guard.false_ival
                ival = ival.intersect(ref)
            refined.append(ival)
        out = refined[0]
        for iv in refined[1:]:
            out = out.hull(iv)
        if all(self.is_bool_derived(env, v) for v in eqn.invars[1:]):
            self.bool_derived.add(id(eqn.outvars[0]))
        return [AbsValue(out, eqn.outvars[0])]

    def _guard_key(self, env, pred_v):
        """The variable whose guard entry applies to this predicate: the
        predicate itself, or — if it is a pass-through of another var —
        its origin."""
        if id(pred_v) in self.guards:
            return pred_v
        av = env.get(pred_v)
        if av is not None and av.origin is not None:
            return av.origin
        return pred_v

    # ---- higher-order prims ----

    def _enter(self, tag: str):
        self.chain.append(tag)

    def _exit(self):
        self.chain.pop()

    def _sub_jaxpr_vals(self, env, eqn, invars) -> list[AbsValue]:
        return [self.read(env, v) for v in invars]

    def prim_jit(self, env, eqn):
        closed = eqn.params["jaxpr"]
        name = eqn.params.get("name", "jit")
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        self._enter(f"jit:{name}")
        try:
            outs = self.run_closed(closed, ins)
        finally:
            self._exit()
        return [AbsValue(o.ival, o.origin) for o in outs]

    def prim_closed_call(self, env, eqn):
        return self.prim_jit(env, eqn)

    def prim_core_call(self, env, eqn):
        closed = eqn.params.get("call_jaxpr")
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        self._enter("call")
        try:
            if hasattr(closed, "consts"):
                outs = self.run_closed(closed, ins)
            else:
                outs = self.run(closed, ins)
        finally:
            self._exit()
        return outs

    prim_remat2 = prim_core_call
    prim_checkpoint = prim_core_call

    def prim_custom_jvp_call(self, env, eqn):
        closed = eqn.params["call_jaxpr"]
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        # grad-blocker principle: inside custom_jvp the author owns the
        # gradient — rounding there is intentional (the ste_* pattern)
        saved, self.grad_mode = self.grad_mode, False
        self._enter("custom_jvp")
        try:
            outs = self.run_closed(closed, ins)
        finally:
            self._exit()
            self.grad_mode = saved
        return outs

    def prim_custom_vjp_call(self, env, eqn):
        closed = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        saved, self.grad_mode = self.grad_mode, False
        self._enter("custom_vjp")
        try:
            outs = self.run_closed(closed, ins)
        finally:
            self._exit()
            self.grad_mode = saved
        return outs

    prim_custom_vjp_call_jaxpr = prim_custom_vjp_call

    def prim_while(self, env, eqn):
        p = eqn.params
        body, cond = p["body_jaxpr"], p["cond_jaxpr"]
        nb, nc = p.get("body_nconsts", 0), p.get("cond_nconsts", 0)
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        cond_consts = ins[:nc]
        body_consts = ins[nc:nc + nb]
        carry = ins[nc + nb:]
        del cond_consts, cond
        self._enter("while")
        try:
            for it in range(self.max_while_iters + 1):
                outs = self.run_closed(body, body_consts + carry)
                new_carry = []
                changed = False
                for old, new in zip(carry, outs):
                    if it >= self.max_while_iters:
                        joined = old.ival.widen_against(new.ival)
                    else:
                        joined = old.ival.hull(new.ival)
                    if joined != old.ival:
                        changed = True
                    new_carry.append(AbsValue(joined, old.origin))
                carry = new_carry
                if not changed:
                    break
        finally:
            self._exit()
        return carry

    def prim_scan(self, env, eqn):
        p = eqn.params
        body = p["jaxpr"]
        n_consts = p.get("num_consts", 0)
        n_carry = p.get("num_carry", 0)
        length = int(p.get("length", 1) or 1)
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        consts = ins[:n_consts]
        carry = ins[n_consts:n_consts + n_carry]
        xs = ins[n_consts + n_carry:]
        self._enter("scan")
        ys_hull: list[Interval] | None = None
        try:
            iters = min(length, self.max_while_iters + 1)
            for it in range(iters):
                outs = self.run_closed(body, consts + carry + xs)
                new_carry = outs[:n_carry]
                ys = outs[n_carry:]
                joined = []
                for old, new in zip(carry, new_carry):
                    if it >= self.max_while_iters or iters < length:
                        j = old.ival.widen_against(new.ival) \
                            if it == iters - 1 and iters < length \
                            else old.ival.hull(new.ival)
                    else:
                        j = old.ival.hull(new.ival)
                    joined.append(AbsValue(j, old.origin))
                carry = joined
                cur = [y.ival for y in ys]
                ys_hull = cur if ys_hull is None else [
                    a.hull(b) for a, b in zip(ys_hull, cur)]
        finally:
            self._exit()
        ys_vals = [AbsValue(iv, None) for iv in (ys_hull or [])]
        return carry + ys_vals

    def prim_cond(self, env, eqn):
        branches = eqn.params["branches"]
        ins = self._sub_jaxpr_vals(env, eqn, eqn.invars)
        operands = ins[1:]
        self._enter("cond")
        try:
            branch_outs = [self.run_closed(br, list(operands))
                           for br in branches]
        finally:
            self._exit()
        n_out = len(branch_outs[0])
        outs = []
        for i in range(n_out):
            iv = branch_outs[0][i].ival
            for bo in branch_outs[1:]:
                iv = iv.hull(bo[i].ival)
            outs.append(AbsValue(iv, None))
        return outs


def analyze_jaxpr(closed_jaxpr, in_intervals: list[Interval], *,
                  grad_mode: bool = False) -> Analysis:
    """Walk ``closed_jaxpr`` with the given input intervals.

    ``grad_mode`` additionally records rounding / stop_gradient / int-cast
    events (the grad-blocker hazard set) — use it on jaxprs whose inputs are
    differentiated.
    """
    analysis = Analysis()
    interp = _Interp(analysis, grad_mode=grad_mode)
    in_vals = [AbsValue(iv) for iv in in_intervals]
    analysis.out_vals = interp.run_closed(closed_jaxpr, in_vals)
    return analysis
