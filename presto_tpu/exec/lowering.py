"""RowExpression -> XLA lowering.

The TPU replacement for the reference's JVM bytecode expression JIT
(presto-main-base/.../sql/gen/ExpressionCompiler.java:63 /
PageFunctionCompiler.java:127) and for Velox expression eval on the native
worker: expressions become jax functions over Batch columns, fused by XLA into
the surrounding pipeline.

Semantics notes:
- Null propagation: scalar functions return NULL if any input is NULL
  (result nulls = OR of arg nulls); AND/OR use Kleene 3-valued logic.
- Decimals are unscaled int64; scale bookkeeping uses the expression types
  (planner-computed), matching reference DecimalOperators semantics.
- Dictionary-encoded varchar: predicates against literals are precomputed
  host-side into per-code boolean tables (static), then gathered on device —
  the string never reaches the TPU.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional


def like_matcher(pattern: str, escape: Optional[str] = None):
    """SQL LIKE pattern -> predicate.  Unlike a naive fnmatch translation,
    glob metacharacters in the pattern stay literal; only % and _ are
    wildcards (reference LikeFunctions semantics)."""
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if escape and ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    rx = re.compile("".join(out), re.DOTALL)
    return lambda s: rx.fullmatch(s) is not None

import jax
import jax.numpy as jnp
import numpy as np

from ..common.types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, BooleanType,
                            CharType, DateType, DecimalType, DoubleType,
                            IntegerType, RealType, Type, VarcharType)
from ..spi.expr import (BoundParameterExpression, CallExpression,
                        ConstantExpression, RowExpression,
                        SpecialFormExpression, VariableReferenceExpression)
from .batch import Batch, Column

# Canonical scalar function names; presto internal operator handles map here.
_CANONICAL = {
    "$operator$add": "add", "$operator$subtract": "subtract",
    "$operator$multiply": "multiply", "$operator$divide": "divide",
    "$operator$modulus": "modulus", "$operator$negation": "negate",
    "$operator$equal": "eq", "$operator$not_equal": "neq",
    "$operator$less_than": "lt", "$operator$less_than_or_equal": "lte",
    "$operator$greater_than": "gt", "$operator$greater_than_or_equal": "gte",
    "$operator$between": "between", "$operator$cast": "cast",
    "presto.default.$operator$add": "add",
    "not": "not",
}


def canonical_name(name: str) -> str:
    n = name.lower()
    return _CANONICAL.get(n, n.split(".")[-1])


def _scale_of(t: Type) -> Optional[int]:
    return t.scale if isinstance(t, DecimalType) else None


def _pow10(k: int):
    return 10 ** k


def _is_decimal(t):
    return isinstance(t, DecimalType)


def _combine_nulls(*cols) -> Optional[jnp.ndarray]:
    masks = [c.nulls for c in cols if c.nulls is not None]
    if not masks:
        return None
    out = masks[0]
    for m in masks[1:]:
        out = out | m
    return out


def _numeric(col: Column, typ: Type):
    """Values ready for arithmetic: decimals stay unscaled ints."""
    return col.values


def _rescale(values, from_scale: int, to_scale: int):
    if to_scale == from_scale:
        return values
    if to_scale > from_scale:
        return values * _pow10(to_scale - from_scale)
    # scale down with round-half-up (reference decimal semantics)
    f = _pow10(from_scale - to_scale)
    return _div_round_half_up(values, f)


def _div_round_half_up(num, den_const: int):
    """Divide by positive constant, rounding half away from zero."""
    return (jnp.sign(num) * ((jnp.abs(num) + den_const // 2) // den_const)
            ).astype(num.dtype)


def _to_common_numeric(col: Column, typ: Type, target: Type):
    """Coerce values of `typ` to the numeric domain of `target` for comparison
    or arithmetic: decimal scales aligned, ints widened, doubles floated."""
    v = col.values
    if _is_decimal(target):
        if _is_decimal(typ):
            return _rescale(v, typ.scale, target.scale)
        return v * _pow10(target.scale)  # integer -> decimal
    if isinstance(target, (DoubleType, RealType)):
        if _is_decimal(typ):
            return v.astype(jnp.float64) / _pow10(typ.scale)
        return v.astype(jnp.float64 if isinstance(target, DoubleType) else jnp.float32)
    return v


def _common_super(t1: Type, t2: Type) -> Type:
    if isinstance(t1, (DoubleType,)) or isinstance(t2, (DoubleType,)):
        return DOUBLE
    if isinstance(t1, RealType) or isinstance(t2, RealType):
        return DOUBLE
    if _is_decimal(t1) and _is_decimal(t2):
        s = max(t1.scale, t2.scale)
        return DecimalType(38, s)
    if _is_decimal(t1):
        return DecimalType(38, t1.scale)
    if _is_decimal(t2):
        return DecimalType(38, t2.scale)
    return BIGINT


# ---------------------------------------------------------------------------
# constant encoding
# ---------------------------------------------------------------------------

def constant_device_value(value, typ: Type):
    """Python literal -> device scalar in the column's logical domain."""
    if value is None:
        return None
    if isinstance(typ, DecimalType):
        from decimal import Decimal
        if isinstance(value, Decimal):
            return int(value.scaleb(typ.scale).to_integral_value())
        if isinstance(value, str):
            return int(Decimal(value).scaleb(typ.scale).to_integral_value())
        return int(value)  # already unscaled
    if isinstance(typ, DateType) and isinstance(value, str):
        return int(np.datetime64(value, "D").astype(np.int64))
    return value


# ---------------------------------------------------------------------------
# main lowering
# ---------------------------------------------------------------------------

def expr_has_params(expr: RowExpression) -> bool:
    """Whether a RowExpression tree contains serving-tier bound-parameter
    leaves (pipeline/fused use this at compile time to decide whether a
    step takes the parameter vector as a jit argument)."""
    if isinstance(expr, BoundParameterExpression):
        return True
    if isinstance(expr, (CallExpression, SpecialFormExpression)):
        return any(expr_has_params(a) for a in expr.arguments)
    return False


class Lowering:
    """Compiles a RowExpression tree to a function Batch -> Column."""

    def __init__(self):
        pass

    def compile(self, expr: RowExpression) -> Callable[[Batch], Column]:
        def fn(batch: Batch) -> Column:
            return self.eval(expr, batch)
        return fn

    def eval(self, expr: RowExpression, batch: Batch) -> Column:
        if isinstance(expr, VariableReferenceExpression):
            return batch.column(expr.name)
        if isinstance(expr, ConstantExpression):
            return self._constant(expr, batch)
        if isinstance(expr, CallExpression):
            return self._call(expr, batch)
        if isinstance(expr, SpecialFormExpression):
            return self._special(expr, batch)
        if isinstance(expr, BoundParameterExpression):
            return self._parameter(expr, batch)
        raise NotImplementedError(type(expr).__name__)

    # -- constants --------------------------------------------------------
    def _constant(self, expr: ConstantExpression, batch: Batch) -> Column:
        cap = batch.capacity
        if expr.value is None:
            if isinstance(expr.type, (VarcharType, CharType)):
                # typed NULL string: all-null dictionary column so string
                # consumers (union dictionary merge, output blocks) work
                return Column(jnp.zeros(cap, dtype=jnp.int32),
                              jnp.ones(cap, dtype=bool), ("",))
            z = jnp.zeros(cap, dtype=_jnp_dtype(expr.type))
            return Column(z, jnp.ones(cap, dtype=bool))
        v = constant_device_value(expr.value, expr.type)
        if isinstance(expr.type, (VarcharType, CharType)):
            # string literal: single-entry dictionary, code 0 everywhere
            return Column(jnp.zeros(cap, dtype=jnp.int32), None, (str(v),))
        arr = jnp.full(cap, v, dtype=_jnp_dtype(expr.type))
        return Column(arr, None)

    def _parameter(self, expr: BoundParameterExpression, batch: Batch) -> Column:
        if batch.params is None:
            raise RuntimeError(
                f"BoundParameterExpression ?{expr.index} evaluated on a batch "
                "with no bound-parameter vector attached (serving bug: the "
                "step was compiled without params plumbing)")
        v = batch.params[expr.index]
        arr = jnp.full(batch.capacity, v, dtype=_jnp_dtype(expr.type))
        return Column(arr, None)

    # -- calls ------------------------------------------------------------
    def _call(self, expr: CallExpression, batch: Batch) -> Column:
        name = canonical_name(expr.display_name)
        args = expr.arguments

        if name in ("add", "subtract", "multiply", "divide", "modulus"):
            return self._arith(name, expr, batch)
        if name in ("eq", "neq", "lt", "lte", "gt", "gte"):
            return self._compare(name, args[0], args[1], batch)
        if name == "between":
            lo = self._compare("gte", args[0], args[1], batch)
            hi = self._compare("lte", args[0], args[2], batch)
            return _kleene_and(lo, hi)
        if name == "not":
            c = self.eval(args[0], batch)
            return Column(~c.values.astype(bool), c.nulls)
        if name == "negate":
            c = self.eval(args[0], batch)
            return Column(-c.values, c.nulls)
        if name == "abs":
            c = self.eval(args[0], batch)
            return Column(jnp.abs(c.values), c.nulls)
        if name in ("year", "month", "day", "quarter"):
            c = self.eval(args[0], batch)
            y, m, d = _civil_from_days(c.values)
            part = {"year": y, "month": m, "day": d, "quarter": (m + 2) // 3}[name]
            return Column(part.astype(jnp.int64), c.nulls)
        if name == "cast":
            return self._cast(args[0], expr.type, batch)
        if name == "like":
            return self._like(args[0], args[1], batch)
        if name == "substr":
            return self._substr(expr, batch)
        if name == "length":
            c = self.eval(args[0], batch)
            if c.dictionary is None:
                raise NotImplementedError("length on non-dictionary varchar")
            table = jnp.asarray(np.array([len(s) for s in c.dictionary],
                                         dtype=np.int64))
            return Column(table[c.values], c.nulls)
        if name in ("coalesce",):
            return self._coalesce([self.eval(a, batch) for a in args])
        if name in _DOUBLE_FNS:
            c = self.eval(args[0], batch)
            v = _to_common_numeric(c, args[0].type, DoubleType())
            if name == "power":
                b = self.eval(args[1], batch)
                bv = _to_common_numeric(b, args[1].type, DoubleType())
                return Column(jnp.power(v, bv), _combine_nulls(c, b))
            return Column(_DOUBLE_FNS[name](v), c.nulls)
        if name in ("ceiling", "ceil", "floor"):
            c = self.eval(args[0], batch)
            t = args[0].type
            if isinstance(t, (DoubleType, RealType)):
                f = jnp.ceil if name != "floor" else jnp.floor
                return Column(f(c.values), c.nulls)
            if _is_decimal(t) and t.scale > 0:
                den = 10 ** t.scale
                v = c.values
                out = (-((-v) // den)) if name != "floor" else (v // den)
                return Column(out, c.nulls)
            return Column(c.values, c.nulls)
        if name == "sign":
            c = self.eval(args[0], batch)
            return Column(jnp.sign(c.values), c.nulls)
        if name == "truncate":
            c = self.eval(args[0], batch)
            v = _to_common_numeric(c, args[0].type, DoubleType())
            return Column(jnp.trunc(v), c.nulls)
        if name == "round":
            c = self.eval(args[0], batch)
            if len(args) > 1 and not isinstance(args[1],
                                                ConstantExpression):
                raise NotImplementedError(
                    "round with non-constant digits")
            digits = int(args[1].value) if len(args) > 1 else 0
            if _is_decimal(expr.type):
                s = args[0].type.scale if _is_decimal(args[0].type) else 0
                v = c.values
                if digits < s:
                    den = 10 ** (s - digits)
                    q = jnp.sign(v) * ((jnp.abs(v) + den // 2) // den) * den
                    v = q.astype(c.values.dtype)
                return Column(_rescale(v, s, expr.type.scale), c.nulls)
            v = _to_common_numeric(c, args[0].type, DoubleType())
            scale = 10.0 ** digits
            # SQL rounds half AWAY from zero (jnp.round is half-even)
            out = jnp.sign(v) * jnp.floor(jnp.abs(v) * scale + 0.5) / scale
            if isinstance(expr.type, (DoubleType, RealType)):
                return Column(out, c.nulls)
            return Column(out.astype(c.values.dtype), c.nulls)
        if name in ("greatest", "least"):
            cols = [self.eval(a, batch) for a in args]
            # compare/return in the DECLARED result type so the emitted
            # scaled values match the planner's precision/scale
            vals = [_to_common_numeric(c, a.type, expr.type)
                    for c, a in zip(cols, args)]
            op = jnp.maximum if name == "greatest" else jnp.minimum
            out = vals[0]
            for v in vals[1:]:
                out = op(out, v)
            return Column(out, _combine_nulls(*cols))
        if name in _STRING_TO_STRING or name in _STRING_TO_VALUE \
                or name == "concat":
            return self._string_fn(name, expr, batch)
        if name in ("date_trunc", "date_add", "date_diff", "day_of_week",
                    "day_of_year", "week"):
            return self._date_fn(name, expr, batch)
        if name in ("array_constructor", "subscript", "element_at",
                    "cardinality", "contains", "array_max", "array_min",
                    "array_position", "repeat", "sequence"):
            return self._array_fn(name, expr, batch)
        # -- math/bitwise breadth (MathFunctions.java, BitwiseFunctions.java)
        if name == "log":
            b = self.eval(args[0], batch)
            x = self.eval(args[1], batch)
            bv = _to_common_numeric(b, args[0].type, DoubleType())
            xv = _to_common_numeric(x, args[1].type, DoubleType())
            return Column(jnp.log(xv) / jnp.log(bv), _combine_nulls(b, x))
        if name == "atan2":
            y = self.eval(args[0], batch)
            x = self.eval(args[1], batch)
            yv = _to_common_numeric(y, args[0].type, DoubleType())
            xv = _to_common_numeric(x, args[1].type, DoubleType())
            return Column(jnp.arctan2(yv, xv), _combine_nulls(y, x))
        if name in ("is_nan", "is_finite", "is_infinite"):
            c = self.eval(args[0], batch)
            v = _to_common_numeric(c, args[0].type, DoubleType())
            out = {"is_nan": jnp.isnan, "is_finite": jnp.isfinite,
                   "is_infinite": jnp.isinf}[name](v)
            return Column(out, c.nulls)
        if name in ("bitwise_and", "bitwise_or", "bitwise_xor"):
            a = self.eval(args[0], batch)
            b = self.eval(args[1], batch)
            op = {"bitwise_and": jnp.bitwise_and,
                  "bitwise_or": jnp.bitwise_or,
                  "bitwise_xor": jnp.bitwise_xor}[name]
            return Column(op(a.values.astype(jnp.int64),
                             b.values.astype(jnp.int64)),
                          _combine_nulls(a, b))
        if name == "bitwise_not":
            c = self.eval(args[0], batch)
            return Column(~c.values.astype(jnp.int64), c.nulls)
        if name in ("bitwise_left_shift", "bitwise_right_shift",
                    "bitwise_arithmetic_shift_right"):
            a = self.eval(args[0], batch)
            b = self.eval(args[1], batch)
            av = a.values.astype(jnp.int64)
            shv = b.values.astype(jnp.int64)
            # int64 shift semantics: counts >= 64 shift everything out
            # (0 for left/logical-right; arithmetic-right saturates to
            # the sign fill); Presto ERRORS on negative counts, relaxed
            # to NULL here (error->NULL convention, width_bucket-style)
            big = shv >= 64
            sh = jnp.clip(shv, 0, 63)
            if name == "bitwise_left_shift":
                out = jnp.where(big, 0, av << sh)
            elif name == "bitwise_arithmetic_shift_right":
                out = av >> jnp.where(big, 63, sh)
            else:       # logical right shift
                out = jnp.where(big, 0,
                                jax.lax.shift_right_logical(av, sh))
            nulls = _combine_nulls(a, b)
            bad = shv < 0
            nulls = bad if nulls is None else (nulls | bad)
            return Column(out, nulls)
        if name == "width_bucket":
            x = self.eval(args[0], batch)
            lo = self.eval(args[1], batch)
            hi = self.eval(args[2], batch)
            n = self.eval(args[3], batch)
            xv = _to_common_numeric(x, args[0].type, DoubleType())
            lov = _to_common_numeric(lo, args[1].type, DoubleType())
            hiv = _to_common_numeric(hi, args[2].type, DoubleType())
            nv = n.values.astype(jnp.int64)
            span = jnp.where(hiv == lov, 1.0, hiv - lov)
            v = (xv - lov) * nv / span
            # 1-ulp tolerance before the floor: XLA's CPU fast-math may
            # reassociate a*n/b as a*(n/b), landing a hair under exact
            # bucket edges; the oracle applies the same nudge, making the
            # edge definition shared rather than compiler-dependent
            bucket = jnp.floor(v * (1 + 2.0 ** -40)).astype(jnp.int64) + 1
            out = jnp.clip(bucket, 0, jnp.maximum(nv + 1, 0))
            # Presto ERRORS on bucketCount <= 0; relaxed to NULL here
            # (the documented error->NULL convention), oracle-mirrored
            nulls = _combine_nulls(x, lo, hi, n)
            bad = nv <= 0
            nulls = bad if nulls is None else (nulls | bad)
            return Column(out, nulls)
        raise NotImplementedError(f"scalar function {expr.display_name!r}")

    # -- array functions (fixed-width (capacity, W) representation) --------
    def _array_fn(self, name: str, expr: CallExpression,
                  batch: Batch) -> Column:
        """Array kernels over the padded (capacity, W) element matrix
        (reference ArrayFunctions.java / ArraySubscriptOperator.java;
        element NULLs inside arrays are not represented yet — Presto's
        out-of-bounds subscript ERROR is relaxed to NULL, element_at
        semantics)."""
        args = expr.arguments
        if name == "array_constructor":
            cols = [self.eval(a, batch) for a in args]
            if not cols:
                return Column(jnp.zeros((batch.capacity, 0),
                                        dtype=jnp.int64),
                              None, None, None,
                              jnp.zeros(batch.capacity, dtype=jnp.int32))
            if any(c.dictionary is not None or c.lazy is not None
                   or c.lengths is not None for c in cols):
                raise NotImplementedError(
                    "array elements must be scalar numerics")
            if any(c.nulls is not None for c in cols):
                raise NotImplementedError(
                    "NULL array elements not supported")
            dt = jnp.result_type(*[c.values.dtype for c in cols])
            vals = jnp.stack([c.values.astype(dt) for c in cols], axis=1)
            lengths = jnp.full(batch.capacity, len(cols), dtype=jnp.int32)
            return Column(vals, None, None, None, lengths)
        arr = self.eval(args[0], batch)
        if name == "repeat":
            elem = arr      # repeat(x, n): x is scalar, n constant
            if not isinstance(args[1], ConstantExpression):
                raise NotImplementedError("repeat with non-constant count")
            # negative count clamps to the empty array (Presto ERRORS;
            # relaxed per the error->NULL/identity convention, and the
            # oracle clamps identically)
            n = max(int(args[1].value), 0)
            vals = jnp.tile(elem.values[:, None], (1, max(n, 1)))
            if n == 0:
                vals = vals[:, :0]
            return Column(vals, elem.nulls, None, None,
                          jnp.full(batch.capacity, n, dtype=jnp.int32))
        if name == "sequence":
            if not all(isinstance(a, ConstantExpression) for a in args):
                raise NotImplementedError(
                    "sequence with non-constant bounds")
            lo, hi = int(args[0].value), int(args[1].value)
            step = int(args[2].value) if len(args) > 2 else 1
            seq = jnp.arange(lo, hi + (1 if step > 0 else -1), step,
                             dtype=jnp.int64)
            vals = jnp.tile(seq[None, :], (batch.capacity, 1))
            return Column(vals, None, None, None,
                          jnp.full(batch.capacity, seq.shape[0],
                                   dtype=jnp.int32))
        if arr.lengths is None:
            raise NotImplementedError(f"{name} on non-array input")
        W = arr.values.shape[1]
        lens = arr.lengths
        if name == "cardinality":
            return Column(lens.astype(jnp.int64), arr.nulls)
        if name in ("subscript", "element_at"):
            idx = self.eval(args[1], batch)
            raw = idx.values.astype(jnp.int64)
            if name == "element_at":
                # element_at(-n) indexes from the end (ArrayFunctions.java)
                raw = jnp.where(raw < 0, lens.astype(jnp.int64) + raw + 1,
                                raw)
            i0 = raw - 1                                   # 1-based
            oob = (i0 < 0) | (i0 >= lens.astype(jnp.int64))
            safe = jnp.clip(i0, 0, max(W - 1, 0))
            if W == 0:
                out = jnp.zeros(batch.capacity, dtype=arr.values.dtype)
            else:
                out = jnp.take_along_axis(
                    arr.values, safe[:, None], axis=1)[:, 0]
            nulls = oob | arr.null_mask()
            if idx.nulls is not None:
                nulls = nulls | idx.nulls
            return Column(out, nulls)
        live = jnp.arange(W, dtype=jnp.int32)[None, :] \
            < lens[:, None]                                 # (cap, W)
        if name == "contains":
            x = self.eval(args[1], batch)
            hit = jnp.any(live & (arr.values == x.values[:, None]), axis=1)
            nulls = arr.nulls
            if x.nulls is not None:
                nulls = x.nulls if nulls is None else nulls | x.nulls
            return Column(hit, nulls)
        if name in ("array_max", "array_min"):
            big = jnp.asarray(
                jnp.inf if jnp.issubdtype(arr.values.dtype, jnp.floating)
                else jnp.iinfo(arr.values.dtype).max, arr.values.dtype)
            ident = big if name == "array_min" else (
                -big if jnp.issubdtype(arr.values.dtype, jnp.floating)
                else jnp.asarray(jnp.iinfo(arr.values.dtype).min,
                                 arr.values.dtype))
            masked = jnp.where(live, arr.values, ident)
            red = jnp.min if name == "array_min" else jnp.max
            out = red(masked, axis=1) if W else \
                jnp.zeros(batch.capacity, dtype=arr.values.dtype)
            empty = lens == 0
            nulls = empty | arr.null_mask()
            return Column(out, nulls)
        if name == "array_position":
            x = self.eval(args[1], batch)
            eq = live & (arr.values == x.values[:, None])
            first = jnp.argmax(eq, axis=1)
            found = jnp.any(eq, axis=1)
            out = jnp.where(found, first + 1, 0).astype(jnp.int64)
            nulls = arr.nulls
            if x.nulls is not None:
                nulls = x.nulls if nulls is None else nulls | x.nulls
            return Column(out, nulls)
        raise NotImplementedError(name)

    # -- string functions over dictionary columns -------------------------
    def _string_fn(self, name: str, expr: CallExpression,
                   batch: Batch) -> Column:
        """String functions computed host-side over the (static) dictionary
        and applied as a code remap / lookup — the dictionary-encoding
        equivalent of the reference's per-row varchar kernels
        (presto-main-base/.../operator/scalar/StringFunctions.java)."""
        args = expr.arguments
        if name == "concat":
            return self._concat(args, batch)
        c = self.eval(args[0], batch)
        if c.dictionary is None:
            raise NotImplementedError(f"{name} on non-dictionary varchar")
        extra = []
        for a in args[1:]:
            if not isinstance(a, ConstantExpression):
                raise NotImplementedError(f"{name} with non-constant args")
            extra.append(a.value)
        if name in _STRING_TO_STRING:
            fn = _STRING_TO_STRING[name]
            mapped = [fn(s, *extra) for s in c.dictionary]
            return _reencode(c, mapped)
        fn, dtype = _STRING_TO_VALUE[name]
        raw = [fn(s, *extra) for s in c.dictionary]
        table = jnp.asarray(np.array([0 if v is None else v for v in raw],
                                     dtype=dtype))
        out_nulls = c.nulls
        if any(v is None for v in raw):
            null_tab = jnp.asarray(np.array([v is None for v in raw]))
            out_nulls = null_tab[c.values] if out_nulls is None \
                else (null_tab[c.values] | out_nulls)
        return Column(table[c.values], out_nulls)

    def _concat(self, args, batch: Batch) -> Column:
        cols = [self.eval(a, batch) for a in args]
        dict_cols = [c for c in cols if c.dictionary is not None
                     and len(c.dictionary) > 1]
        if any(c.dictionary is None for c in cols):
            raise NotImplementedError("concat on non-dictionary varchar")
        if len(dict_cols) > 2 or (
                len(dict_cols) == 2
                and len(dict_cols[0].dictionary)
                * len(dict_cols[1].dictionary) > 65536):
            raise NotImplementedError("concat dictionary product too large")
        nulls = None
        for c in cols:
            if c.nulls is not None:
                nulls = _or_null(nulls, c.nulls)
        if any(not c.dictionary for c in cols):
            # an empty dictionary (empty table / all-null column, e.g.
            # after an empty CTAS) has no representable value: emit an
            # all-null empty-dictionary result instead of indexing [0]
            ref = cols[0]
            return Column(jnp.zeros_like(ref.values),
                          jnp.ones(ref.values.shape, dtype=bool),
                          ("",))
        if len(dict_cols) <= 1:
            base = dict_cols[0] if dict_cols else cols[0]
            mapped = ["".join(c.dictionary[0] if c is not base else s
                              for c in cols)
                      for s in base.dictionary]
            return _reencode(Column(base.values, nulls, base.dictionary),
                             mapped)
        a, b = dict_cols
        nb = len(b.dictionary)
        product = []
        for sa in a.dictionary:
            for sb in b.dictionary:
                parts = []
                for c in cols:
                    if c is a:
                        parts.append(sa)
                    elif c is b:
                        parts.append(sb)
                    else:
                        parts.append(c.dictionary[0])
                product.append("".join(parts))
        codes = a.values * nb + b.values
        return _reencode(Column(codes, nulls, tuple(product)), product)

    # -- date functions ---------------------------------------------------
    def _date_fn(self, name: str, expr: CallExpression,
                 batch: Batch) -> Column:
        args = expr.arguments
        if name in ("day_of_week", "day_of_year", "week"):
            c = self.eval(args[0], batch)
            days = c.values.astype(jnp.int64)
            if name == "day_of_week":
                return Column((days + 3) % 7 + 1, c.nulls)
            y, m, d = _civil_from_days(days)
            doy = days - _days_from_civil(y, jnp.ones_like(m),
                                          jnp.ones_like(d)) + 1
            if name == "day_of_year":
                return Column(doy, c.nulls)
            dow = (days + 3) % 7 + 1
            w0 = (10 + doy - dow) // 7
            # nested on the ORIGINAL w: a w0<1 resolved to last year's 53
            # must not be re-clamped by this year's 52-week count
            w = jnp.where(w0 < 1, _iso_weeks_in_year(y - 1),
                          jnp.where(w0 > _iso_weeks_in_year(y), 1, w0))
            return Column(w, c.nulls)
        unit = str(args[0].value).lower()
        if name == "date_trunc":
            c = self.eval(args[1], batch)
            days = c.values.astype(jnp.int64)
            if unit == "day":
                return Column(days.astype(c.values.dtype), c.nulls)
            if unit == "week":
                return Column((days - (days + 3) % 7)
                              .astype(c.values.dtype), c.nulls)
            y, m, _d = _civil_from_days(days)
            if unit == "quarter":
                m = ((m - 1) // 3) * 3 + 1
            elif unit == "year":
                m = jnp.ones_like(m)
            out = _days_from_civil(y, m, jnp.ones_like(m))
            return Column(out.astype(c.values.dtype), c.nulls)
        if name == "date_add":
            n = self.eval(args[1], batch).values.astype(jnp.int64)
            c = self.eval(args[2], batch)
            days = c.values.astype(jnp.int64)
            if unit in ("day", "week"):
                out = days + n * (7 if unit == "week" else 1)
                return Column(out.astype(c.values.dtype), c.nulls)
            months = n * {"month": 1, "quarter": 3, "year": 12}[unit]
            out = _add_months(days, months)
            return Column(out.astype(c.values.dtype), c.nulls)
        # date_diff(unit, a, b) = b - a in whole units, truncated toward 0
        a = self.eval(args[1], batch)
        b = self.eval(args[2], batch)
        nulls = _combine_nulls(a, b)
        da = a.values.astype(jnp.int64)
        db = b.values.astype(jnp.int64)
        if unit in ("day", "week"):
            diff = db - da
            den = 7 if unit == "week" else 1
            out = jnp.sign(diff) * (jnp.abs(diff) // den)
            return Column(out, nulls)
        ya, ma, dda = _civil_from_days(da)
        yb, mb, ddb = _civil_from_days(db)
        months = (yb * 12 + mb) - (ya * 12 + ma)
        # partial months don't count: back off one when the day-of-month
        # hasn't been reached yet (sign-aware)
        months = jnp.where((months > 0) & (ddb < dda), months - 1, months)
        months = jnp.where((months < 0) & (ddb > dda), months + 1, months)
        den = {"month": 1, "quarter": 3, "year": 12}[unit]
        out = jnp.sign(months) * (jnp.abs(months) // den)
        return Column(out, nulls)

    def _arith(self, name, expr: CallExpression, batch: Batch) -> Column:
        a_expr, b_expr = expr.arguments
        a, b = self.eval(a_expr, batch), self.eval(b_expr, batch)
        ta, tb, tr = a_expr.type, b_expr.type, expr.type
        nulls = _combine_nulls(a, b)

        if isinstance(tr, (DoubleType, RealType)):
            av = _to_common_numeric(a, ta, tr)
            bv = _to_common_numeric(b, tb, tr)
            op = {"add": jnp.add, "subtract": jnp.subtract,
                  "multiply": jnp.multiply, "divide": jnp.divide,
                  "modulus": jnp.mod}[name]
            return Column(op(av, bv), nulls)

        if _is_decimal(tr):
            rs = tr.scale
            sa = ta.scale if _is_decimal(ta) else 0
            sb = tb.scale if _is_decimal(tb) else 0
            av, bv = a.values, b.values
            if name == "multiply":
                out = av * bv  # scale sa+sb
                return Column(_rescale(out, sa + sb, rs), nulls)
            if name == "divide":
                safe_b = jnp.where(bv == 0, 1, bv)
                nulls = _or_null(nulls, bv == 0)
                up = rs + sb - sa
                pa = ta.precision if _is_decimal(ta) else 19
                if up <= 0 or pa + up <= 18:
                    # numerator scaled to rs + sb (it fits: a short
                    # decimal's digits plus the shift stay inside int64),
                    # then round-half-up divide
                    num = _rescale(av, sa, rs + sb)
                    q = jnp.sign(num) * jnp.sign(safe_b) * (
                        (jnp.abs(num) + jnp.abs(safe_b) // 2)
                        // jnp.abs(safe_b))
                    return Column(q.astype(av.dtype), nulls)
                # a long decimal (a sum): scaling the numerator up first
                # leaves int64 long before the quotient does (TPC-H Q14's
                # 100.00 * sum / sum at sf1), so divide digit by digit:
                # the remainder stays below the divisor, and times ten
                # inside int64 for any divisor under 9.2e17
                na, nb = jnp.abs(av), jnp.abs(safe_b)
                q = na // nb
                r = na - q * nb
                for _ in range(up):
                    r = r * 10
                    d = r // nb
                    q, r = q * 10 + d, r - d * nb
                q = q + (r >= nb - r)               # half away from zero
                q = jnp.sign(av) * jnp.sign(safe_b) * q
                return Column(q.astype(av.dtype), nulls)
            av = _rescale(av, sa, rs)
            bv = _rescale(bv, sb, rs)
            if name == "modulus":
                # same contract as the integer path: dividend-sign result,
                # NULL on a zero divisor (jnp.mod's divisor-sign
                # convention differs from SQL's)
                safe_b = jnp.where(bv == 0, 1, bv)
                r = (jnp.sign(av)
                     * (jnp.abs(av) % jnp.abs(safe_b))).astype(av.dtype)
                return Column(r, _or_null(nulls, bv == 0))
            op = {"add": jnp.add, "subtract": jnp.subtract}[name]
            return Column(op(av, bv), nulls)

        # integer domain
        av, bv = a.values, b.values
        if name == "divide":
            safe_b = jnp.where(bv == 0, 1, bv)
            # SQL integer division truncates toward zero
            q = (jnp.sign(av) * jnp.sign(safe_b)
                 * (jnp.abs(av) // jnp.abs(safe_b))).astype(av.dtype)
            return Column(q, _or_null(nulls, bv == 0))
        if name == "modulus":
            safe_b = jnp.where(bv == 0, 1, bv)
            r = (jnp.sign(av) * (jnp.abs(av) % jnp.abs(safe_b))).astype(av.dtype)
            return Column(r, _or_null(nulls, bv == 0))
        op = {"add": jnp.add, "subtract": jnp.subtract,
              "multiply": jnp.multiply}[name]
        return Column(op(av, bv), nulls)

    def _compare(self, name, a_expr, b_expr, batch: Batch) -> Column:
        a, b = self.eval(a_expr, batch), self.eval(b_expr, batch)
        nulls = _combine_nulls(a, b)

        # dictionary-coded strings
        if a.dictionary is not None or b.dictionary is not None:
            return self._compare_strings(name, a, b, nulls)

        common = _common_super(a_expr.type, b_expr.type)
        av = _to_common_numeric(a, a_expr.type, common)
        bv = _to_common_numeric(b, b_expr.type, common)
        op = {"eq": jnp.equal, "neq": jnp.not_equal, "lt": jnp.less,
              "lte": jnp.less_equal, "gt": jnp.greater,
              "gte": jnp.greater_equal}[name]
        return Column(op(av, bv), nulls)

    def _compare_strings(self, name, a: Column, b: Column, nulls) -> Column:
        if a.dictionary is None or b.dictionary is None:
            raise NotImplementedError("string comparison requires dictionaries")
        if len(b.dictionary) == 1:
            # column vs literal: precompute per-code truth table (host)
            lit = b.dictionary[0]
            import operator as _op
            pyop = {"eq": _op.eq, "neq": _op.ne, "lt": _op.lt,
                    "lte": _op.le, "gt": _op.gt, "gte": _op.ge}[name]
            table = jnp.asarray(np.array([pyop(s, lit) for s in a.dictionary],
                                         dtype=bool))
            return Column(table[a.values], nulls)
        if len(a.dictionary) == 1:
            flip = {"eq": "eq", "neq": "neq", "lt": "gt", "lte": "gte",
                    "gt": "lt", "gte": "lte"}[name]
            return self._compare_strings(flip, b, a, nulls)
        if a.dictionary == b.dictionary:
            op = {"eq": jnp.equal, "neq": jnp.not_equal, "lt": jnp.less,
                  "lte": jnp.less_equal, "gt": jnp.greater,
                  "gte": jnp.greater_equal}[name]
            if name in ("eq", "neq"):
                return Column(op(a.values, b.values), nulls)
            # order comparisons need rank order == code order; our dictionaries
            # are sorted at build time (batch.py), so codes are rank codes.
            return Column(op(a.values, b.values), nulls)
        # different dictionaries: map b's codes into a's dictionary (host)
        index = {s: i for i, s in enumerate(a.dictionary)}
        remap = jnp.asarray(np.array(
            [index.get(s, -1) for s in b.dictionary], dtype=np.int32))
        bv = remap[b.values]
        if name == "eq":
            return Column((a.values == bv) & (bv >= 0), nulls)
        if name == "neq":
            return Column((a.values != bv) | (bv < 0), nulls)
        raise NotImplementedError("ordering across distinct dictionaries")

    def _like(self, value_expr, pattern_expr, batch: Batch) -> Column:
        if not isinstance(pattern_expr, ConstantExpression):
            raise NotImplementedError("LIKE with non-constant pattern")
        c = self.eval(value_expr, batch)
        if c.dictionary is None:
            raise NotImplementedError("LIKE on non-dictionary varchar")
        match = like_matcher(str(pattern_expr.value))
        table = jnp.asarray(np.array(
            [match(s) for s in c.dictionary], dtype=bool))
        return Column(table[c.values], c.nulls)

    def _substr(self, expr: CallExpression, batch: Batch) -> Column:
        args = expr.arguments
        c = self.eval(args[0], batch)
        if c.dictionary is None:
            raise NotImplementedError("substr on non-dictionary varchar")
        if not all(isinstance(a, ConstantExpression) for a in args[1:]):
            raise NotImplementedError("substr with non-constant bounds")
        start = int(args[1].value)
        length = int(args[2].value) if len(args) > 2 else None
        def sub(s):
            i = start - 1 if start > 0 else len(s) + start
            return s[i:i + length] if length is not None else s[i:]
        new_values = [sub(s) for s in c.dictionary]
        uniq = sorted(set(new_values))
        remap = jnp.asarray(np.array([uniq.index(v) for v in new_values],
                                     dtype=np.int32))
        return Column(remap[c.values], c.nulls, tuple(uniq))

    def _cast(self, arg: RowExpression, to: Type, batch: Batch) -> Column:
        c = self.eval(arg, batch)
        frm = arg.type
        if frm.signature == to.signature:
            return c
        if isinstance(to, DoubleType):
            if _is_decimal(frm):
                return Column(c.values.astype(jnp.float64) / _pow10(frm.scale),
                              c.nulls)
            return Column(c.values.astype(jnp.float64), c.nulls)
        if _is_decimal(to):
            if _is_decimal(frm):
                return Column(_rescale(c.values, frm.scale, to.scale), c.nulls)
            if isinstance(frm, (DoubleType, RealType)):
                scaled = c.values * _pow10(to.scale)
                return Column(jnp.round(scaled).astype(jnp.int64), c.nulls)
            return Column(c.values.astype(jnp.int64) * _pow10(to.scale), c.nulls)
        if isinstance(to, (IntegerType,)):
            return Column(c.values.astype(jnp.int32), c.nulls)
        if to.signature == "bigint":
            if _is_decimal(frm):
                return Column(_rescale(c.values, frm.scale, 0), c.nulls)
            return Column(c.values.astype(jnp.int64), c.nulls)
        if isinstance(to, (VarcharType, CharType)) and c.dictionary is not None:
            return c
        raise NotImplementedError(f"cast {frm} -> {to}")

    def _coalesce(self, cols: List[Column]) -> Column:
        out_v = cols[-1].values
        out_n = cols[-1].null_mask()
        for c in reversed(cols[:-1]):
            isnull = c.null_mask()
            out_v = jnp.where(isnull, out_v, c.values)
            out_n = isnull & out_n
        has = any(c.nulls is not None for c in cols)
        return Column(out_v, out_n if has else None)

    # -- special forms ----------------------------------------------------
    def _special(self, expr: SpecialFormExpression, batch: Batch) -> Column:
        form = expr.form
        args = expr.arguments
        if form == "AND":
            cols = [self.eval(a, batch) for a in args]
            out = cols[0]
            for c in cols[1:]:
                out = _kleene_and(out, c)
            return out
        if form == "OR":
            cols = [self.eval(a, batch) for a in args]
            out = cols[0]
            for c in cols[1:]:
                out = _kleene_or(out, c)
            return out
        if form == "IS_NULL":
            c = self.eval(args[0], batch)
            return Column(c.null_mask(), None)
        if form == "IF":
            cond = self.eval(args[0], batch)
            t = self.eval(args[1], batch)
            f = self.eval(args[2], batch)
            pred = cond.values.astype(bool) & ~cond.null_mask()
            t, f = _merge_dictionaries(t, f)
            values = jnp.where(pred, t.values, f.values)
            nulls = jnp.where(pred, t.null_mask(), f.null_mask())
            has = t.nulls is not None or f.nulls is not None
            return Column(values, nulls if has else None, t.dictionary)
        if form == "COALESCE":
            return self._coalesce([self.eval(a, batch) for a in args])
        if form == "IN":
            return self._in(args[0], args[1:], batch)
        if form == "NULL_IF":
            a = self.eval(args[0], batch)
            b = self.eval(args[1], batch)
            # NULLIF(x, y) is x unless x == y with both non-null
            eq = (a.values == b.values) & ~a.null_mask() & ~b.null_mask()
            return Column(a.values, _or_null(a.nulls, eq))
        raise NotImplementedError(f"special form {form}")

    def _in(self, value_expr, list_exprs, batch: Batch) -> Column:
        c = self.eval(value_expr, batch)
        consts = [e for e in list_exprs if isinstance(e, ConstantExpression)]
        if len(consts) != len(list_exprs):
            raise NotImplementedError("IN with non-constant list")
        if c.dictionary is not None:
            values = {str(e.value) for e in consts}
            table = jnp.asarray(np.array([s in values for s in c.dictionary],
                                         dtype=bool))
            return Column(table[c.values], c.nulls)
        out = jnp.zeros(batch.capacity, dtype=bool)
        for e in consts:
            v = constant_device_value(e.value, value_expr.type)
            out = out | (c.values == v)
        return Column(out, c.nulls)


def _merge_dictionaries(a: Column, b: Column):
    """Remap two dictionary-coded columns onto one union dictionary (static,
    host-side) so their codes are directly comparable/mixable."""
    if a.dictionary is None or b.dictionary is None or \
            a.dictionary == b.dictionary:
        return a, b
    union = tuple(sorted(set(a.dictionary) | set(b.dictionary)))
    index = {s: i for i, s in enumerate(union)}
    remap_a = jnp.asarray(np.array([index[s] for s in a.dictionary],
                                   dtype=np.int32))
    remap_b = jnp.asarray(np.array([index[s] for s in b.dictionary],
                                   dtype=np.int32))
    return (Column(remap_a[a.values], a.nulls, union),
            Column(remap_b[b.values], b.nulls, union))


def _or_null(nulls, extra_mask):
    if nulls is None:
        return extra_mask
    return nulls | extra_mask


def _kleene_and(a: Column, b: Column) -> Column:
    av = a.values.astype(bool)
    bv = b.values.astype(bool)
    an, bn = a.null_mask(), b.null_mask()
    value = (av | an) & (bv | bn)  # true unless a definite false
    nulls = value & (an | bn)      # null if not definitively false
    has = a.nulls is not None or b.nulls is not None
    return Column(av & bv if not has else (value & ~nulls), nulls if has else None)


def _kleene_or(a: Column, b: Column) -> Column:
    av = a.values.astype(bool)
    bv = b.values.astype(bool)
    an, bn = a.null_mask(), b.null_mask()
    definite_true = (av & ~an) | (bv & ~bn)
    nulls = ~definite_true & (an | bn)
    has = a.nulls is not None or b.nulls is not None
    return Column(definite_true if has else (av | bv), nulls if has else None)


def _jnp_dtype(typ: Type):
    if isinstance(typ, DoubleType):
        return jnp.float64
    if isinstance(typ, RealType):
        return jnp.float32
    if isinstance(typ, BooleanType):
        return jnp.bool_
    if isinstance(typ, IntegerType) or isinstance(typ, DateType):
        return jnp.int32
    return jnp.int64


_DOUBLE_FNS = {
    "sqrt": jnp.sqrt, "exp": jnp.exp, "ln": jnp.log,
    "log2": lambda v: jnp.log(v) / jnp.log(2.0),
    "log10": lambda v: jnp.log(v) / jnp.log(10.0),
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "cbrt": jnp.cbrt, "degrees": jnp.degrees, "radians": jnp.radians,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "power": None,     # binary; handled inline
}


def _lpad(s, n, fill=" "):
    """Presto lpad: pad cycles from the START of the fill string."""
    n, fill = int(n), str(fill)
    if len(s) >= n:
        return s[:n]
    pad = n - len(s)
    return (fill * (pad // len(fill) + 1))[:pad] + s


def _rpad(s, n, fill=" "):
    n, fill = int(n), str(fill)
    if len(s) >= n:
        return s[:n]
    pad = n - len(s)
    return s + (fill * (pad // len(fill) + 1))[:pad]


def _replace(s, find, repl=""):
    return s.replace(str(find), str(repl))


# -- regexp / URL / JSON / split scalar kernels (pure python over
# dictionary entries or host-materialized strings; the per-entry
# semantics follow the reference's operator/scalar implementations:
# RegexpFunctions (re2j semantics approximated by `re`),
# UrlFunctions.java, JsonFunctions.java, StringFunctions.split_part).
# A kernel may return None = SQL NULL; the dictionary remap carries it
# into the null mask.

def _re_compiled(pattern):
    import re
    return re.compile(str(pattern))


def _regexp_like(s, pattern):
    return _re_compiled(pattern).search(s) is not None


def _regexp_extract(s, pattern, group=0):
    m = _re_compiled(pattern).search(s)
    if m is None:
        return None
    try:
        return m.group(int(group))
    except IndexError:
        return None


def _regexp_replace(s, pattern, repl=""):
    import re
    # Presto replacement references are $N / ${name}; python wants \N
    py = re.sub(r"\$(\d+)", r"\\\1", str(repl))
    py = re.sub(r"\$\{(\w+)\}", r"\\g<\1>", py)
    return _re_compiled(pattern).sub(py, s)


def _split_part(s, delim, index):
    parts = s.split(str(delim))
    i = int(index)
    if i < 1 or i > len(parts):
        return None
    return parts[i - 1]


def _url_parts(s):
    from urllib.parse import urlparse
    return urlparse(s)


def _json_extract_scalar(s, path):
    """Subset of the reference JsonExtract path language:
    $.a.b[0].c — object fields and array subscripts."""
    import json as _json
    import re
    try:
        v = _json.loads(s)
    except (ValueError, TypeError):
        return None
    p = str(path)
    if not p.startswith("$"):
        return None
    for tok in re.findall(r"\.([A-Za-z_][\w]*)|\[(\d+)\]|\[\"([^\"]+)\"\]",
                          p[1:]):
        field, idx, qfield = tok
        key = field or qfield
        if key:
            if not isinstance(v, dict) or key not in v:
                return None
            v = v[key]
        else:
            if not isinstance(v, list) or int(idx) >= len(v):
                return None
            v = v[int(idx)]
    if isinstance(v, (dict, list)) or v is None:
        return None          # scalar extraction only (reference contract)
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


_STRING_TO_STRING = {
    "upper": lambda s: s.upper(),
    "lower": lambda s: s.lower(),
    "trim": lambda s: s.strip(),
    "ltrim": lambda s: s.lstrip(),
    "rtrim": lambda s: s.rstrip(),
    "reverse": lambda s: s[::-1],
    "replace": _replace,
    "lpad": _lpad,
    "rpad": _rpad,
    "regexp_extract": _regexp_extract,
    "regexp_replace": _regexp_replace,
    "split_part": _split_part,
    "url_extract_protocol": lambda s: _url_parts(s).scheme or None,
    "url_extract_host": lambda s: _url_parts(s).hostname or None,
    "url_extract_path": lambda s: _url_parts(s).path,
    "url_extract_query": lambda s: _url_parts(s).query or None,
    "url_extract_fragment": lambda s: _url_parts(s).fragment or None,
    "json_extract_scalar": _json_extract_scalar,
}

_STRING_TO_VALUE = {
    # name -> (fn(entry, *const_args), numpy dtype)
    "strpos": (lambda s, sub: s.find(str(sub)) + 1, np.int64),
    "starts_with": (lambda s, p: s.startswith(str(p)), bool),
    "ends_with": (lambda s, p: s.endswith(str(p)), bool),
    "regexp_like": (_regexp_like, bool),
    "codepoint": (lambda s: ord(s[0]) if s else None, np.int64),
    "url_extract_port": (lambda s: _url_port(s), np.int64),
}


def _url_port(s):
    try:
        return _url_parts(s).port       # None when absent
    except ValueError:                  # malformed port -> NULL (Presto
        return None                     # UrlFunctions returns null)


def _reencode(c: Column, mapped) -> Column:
    """Remap a dictionary column through transformed entries, dedup+sort the
    result so codes stay rank codes (grouping and order comparisons depend
    on it).  None entries become NULL rows."""
    uniq = tuple(sorted({s for s in mapped if s is not None}))
    index = {s: i for i, s in enumerate(uniq)}
    remap = jnp.asarray(np.array([0 if s is None else index[s]
                                  for s in mapped], dtype=np.int32))
    if any(s is None for s in mapped):
        null_tab = jnp.asarray(np.array([s is None for s in mapped]))
        nulls = null_tab[c.values]
        if c.nulls is not None:
            nulls = nulls | c.nulls
        return Column(remap[c.values], nulls, uniq or ("",))
    return Column(remap[c.values], c.nulls, uniq or ("",))


def _civil_from_days(z):
    """Days-since-epoch -> (year, month, day); Hinnant's algorithm, integer
    ops only so XLA fuses it."""
    z = z.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def _days_from_civil(y, m, d):
    """(year, month, day) -> days since epoch; inverse of
    _civil_from_days (Hinnant)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _iso_weeks_in_year(y):
    """52 or 53 (ISO-8601): 53 iff Jan 1 or Dec 31 falls on Thursday."""
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    dec31 = _days_from_civil(y, jnp.full_like(y, 12), jnp.full_like(y, 31))
    thu = lambda days: (days + 3) % 7 + 1 == 4  # noqa: E731
    return jnp.where(thu(jan1) | thu(dec31), 53, 52)


def _add_months(days, months):
    """Calendar month addition with end-of-month clamping (Presto
    date_add('month'): Jan 31 + 1 month = Feb 28/29)."""
    y, m, d = _civil_from_days(days)
    total = (m - 1) + months
    y2 = y + total // 12
    m2 = total % 12 + 1
    first = _days_from_civil(y2, m2, jnp.ones_like(m2))
    nxt_total = total + 1
    next_first = _days_from_civil(y + nxt_total // 12,
                                  nxt_total % 12 + 1, jnp.ones_like(m2))
    dim = next_first - first
    return first + jnp.minimum(d, dim) - 1
