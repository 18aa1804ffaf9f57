"""Decimal and date RPN lowered to the integer RPN the device evaluates.

The device has no DECIMAL and its fused kernel takes int32 planes only.
What it can hold is a DECIMAL column whose FieldType fixes its scale, as
the integer ``value * 10**scale`` (``Column.frac``, copr/region_cache.py
``scaled_frac``), and a DATE column as its packed core's upper bits
(``datatype/tile.py date_plane``: the low 41 bits of a DATE are zero).
Over such planes decimal arithmetic IS integer arithmetic with a scale
carried beside it, which is MySQL's own rule (datatype/mydecimal.py):

- ``PlusDecimal`` / ``MinusDecimal``: both sides at the larger scale (a
  column raised by a FIXED power of ten, a constant rescaled here,
  exactly), then ``PlusInt`` / ``MinusInt``; the result has that scale.
- ``MultiplyDecimal``: ``MultiplyInt``; the scales add.
- the six decimal comparisons: both sides at the larger scale, then the
  integer comparison.
- the six time comparisons of a DATE column with a constant whose low 41
  bits are zero too, or with another DATE column: the integer comparison
  of the upper bits.

``lower`` does this at plan analysis (device/runner.py
``_analyze_uncached``), on a plan-cache miss only.  It proves nothing
about magnitudes: integer arithmetic on the device wraps at the planes'
width, so ``fits`` proves, from the columns' value bounds when a feed's
dtypes are chosen, that no intermediate of a lowered expression and no
SUM over the feed's rows leaves the width it is computed in.  Where
either cannot be shown the plan goes where it went before this module
existed: the host pipeline, whose answers are the reference.
"""

from __future__ import annotations

from decimal import Decimal
from typing import Optional, Sequence

from ..datatype import EvalType, FieldTypeTp
from ..datatype.mydecimal import frac_of, to_scaled
from ..datatype.tile import DATE_SHIFT
from ..expr.functions import FUNCTIONS
from ..expr.rpn import RpnColumnRef, RpnConst, RpnExpression, RpnFnCall

# a scaled value is an int64: at most 18 digits right of the point
MAX_FRAC = 18

_CMP = ("Gt", "Ge", "Lt", "Le", "Eq", "Ne")
_DEC_CMP = {stem + "Decimal": stem + "Int" for stem in _CMP}
_TIME_CMP = {stem + "Time": stem + "Int" for stem in _CMP}
_DEC_ADD = {"PlusDecimal": "PlusInt", "MinusDecimal": "MinusInt"}
_DATE_TPS = (FieldTypeTp.DATE, FieldTypeTp.NEW_DATE)
_LOW_BITS = (1 << DATE_SHIFT) - 1

# aggregates of a lowered DECIMAL argument the device answers: SUM comes
# back as a DECIMAL of the argument's scale, COUNT as an integer.  MIN /
# MAX / AVG / FIRST of a DECIMAL stay with the host pipeline.
_DEC_AGGS = ("sum", "count")


class NotLowerable(Exception):
    """The plan uses a DECIMAL in a way the device has no exact integer
    form for: it is not a device plan."""


class _Redo(Exception):
    """A DATE column met a use its int32 plane cannot serve: lower the
    plan again with that column on its packed-core plane."""


def _call(name: str, n_args: int = 2) -> RpnFnCall:
    return RpnFnCall(FUNCTIONS[name], n_args)


def fixed_const(value: int) -> RpnConst:
    """A constant that belongs to the plan's STRUCTURE (a rescaling
    power of ten): ``selection.split_params`` never hoists it."""
    return RpnConst(value, EvalType.INT, True)


class _Val:
    """One stack entry of the lowering: the lowered nodes and what
    they evaluate to: ``raw`` (nothing to
    lower), ``dec`` (a scaled integer of scale ``frac``), ``decconst``
    (a Decimal constant, rescaled by whoever consumes it), ``date`` (a
    DATE column ``col`` on its int32 plane), ``timeconst``."""

    __slots__ = ("low", "tag", "frac", "value", "col")

    def __init__(self, low, tag, frac=None, value=None, col=None):
        self.low, self.tag = low, tag
        self.frac, self.value, self.col = frac, value, col


def _at_frac(v: _Val, frac: int) -> list:
    """``v``'s lowered nodes at scale ``frac`` (not below its own)."""
    if v.tag == "decconst":
        scaled = to_scaled(v.value, frac)
        if scaled is None:
            raise NotLowerable("constant beyond int64 at its scale")
        return [RpnConst(scaled, EvalType.INT)]
    if v.frac == frac:
        return list(v.low)
    return list(v.low) + [fixed_const(10 ** (frac - v.frac)),
                          _call("MultiplyInt")]


def _own_frac(v: _Val) -> int:
    return frac_of(v.value) if v.tag == "decconst" else v.frac


def _lower_one(rpn: RpnExpression, scan, raw_dates: set) -> _Val:
    from ..copr.region_cache import scaled_frac
    stack: list = []
    for node in rpn.nodes:
        if isinstance(node, RpnColumnRef):
            ft = scan.columns[node.col_idx].field_type
            if node.eval_type is EvalType.DECIMAL:
                frac = scaled_frac(ft)
                if frac is None:
                    raise NotLowerable("DECIMAL column without a scale "
                                       "int64 carries")
                stack.append(_Val([RpnColumnRef(
                    node.col_idx, EvalType.INT)], "dec", frac=frac))
            elif node.eval_type is EvalType.DATETIME and \
                    ft.tp in _DATE_TPS and node.col_idx not in raw_dates:
                stack.append(_Val([RpnColumnRef(
                    node.col_idx, EvalType.INT)], "date",
                    col=node.col_idx))
            else:
                stack.append(_Val([node], "raw"))
        elif isinstance(node, RpnConst):
            if isinstance(node.value, Decimal):
                if not node.value.is_finite() or \
                        frac_of(node.value) > MAX_FRAC:
                    raise NotLowerable("constant beyond a scaled int64")
                stack.append(_Val(None, "decconst",
                                  value=node.value))
            elif node.eval_type is EvalType.DECIMAL:
                raise NotLowerable("NULL DECIMAL constant")
            elif node.eval_type is EvalType.DATETIME and \
                    isinstance(node.value, int):
                stack.append(_Val([node], "timeconst",
                                  value=node.value))
            else:
                stack.append(_Val([node], "raw"))
        else:
            args = stack[-node.n_args:] if node.n_args else []
            if node.n_args:
                del stack[-node.n_args:]
            stack.append(_lower_call(node, args))
    (out,) = stack
    return out


def _lower_call(node: RpnFnCall, args: list) -> _Val:
    name = node.meta.name
    decs = [a for a in args if a.tag in ("dec", "decconst")]
    if name in _DEC_ADD or name in _DEC_CMP or name == "MultiplyDecimal":
        if len(decs) != 2:
            raise NotLowerable(f"{name} of a non-DECIMAL operand")
        a, b = args
        if name == "MultiplyDecimal":
            frac = _own_frac(a) + _own_frac(b)
            if frac > MAX_FRAC:
                raise NotLowerable("product scale beyond int64")
            low = _at_frac(a, _own_frac(a)) + _at_frac(b, _own_frac(b)) + \
                [_call("MultiplyInt")]
            return _Val(low, "dec", frac=frac)
        frac = max(_own_frac(a), _own_frac(b))
        if frac > MAX_FRAC:
            raise NotLowerable("scale beyond int64")
        low = _at_frac(a, frac) + _at_frac(b, frac)
        if name in _DEC_ADD:
            return _Val(low + [_call(_DEC_ADD[name])], "dec",
                        frac=frac)
        return _Val(low + [_call(_DEC_CMP[name])], "raw")
    if decs:
        raise NotLowerable(f"{name} has no integer form")
    dates = [a for a in args if a.tag == "date"]
    if name in _TIME_CMP and dates and all(
            a.tag == "date" or (a.tag == "timeconst" and
                                not a.value & _LOW_BITS) for a in args):
        low = []
        for a in args:
            low += a.low if a.tag == "date" else \
                [RpnConst(a.value >> DATE_SHIFT, EvalType.INT)]
        return _Val(low + [_call(_TIME_CMP[name])], "raw")
    if dates:
        raise _Redo({a.col for a in dates})
    return _Val([n for a in args for n in a.low] + [node], "raw")


class Lowered:
    """What ``lower`` made of a plan's expressions, over the scan's
    column offsets as they came."""

    __slots__ = ("sel_rpns", "agg_rpns", "agg_fracs", "key_rpn",
                 "date_cols", "dec_cols")

    def __init__(self):
        self.sel_rpns: list = []
        self.agg_rpns: list = []
        # per aggregate: the scale its result comes back at, or None
        self.agg_fracs: list = []
        self.key_rpn = None
        self.date_cols: set = set()     # scan offsets on the int32 plane
        self.dec_cols: set = set()      # scan offsets of scaled DECIMALs


def _mentions(rpns: Sequence, scan) -> bool:
    for r in rpns:
        if r is None:
            continue
        for n in r.nodes:
            if isinstance(n, RpnConst) and isinstance(n.value, Decimal):
                return True
            if isinstance(n, RpnColumnRef) and \
                    n.col_idx < len(scan.columns):
                ft = scan.columns[n.col_idx].field_type
                if ft.eval_type is EvalType.DECIMAL or \
                        ft.tp in _DATE_TPS:
                    return True
    return False


def needs_lowering(scan, sel_rpns, agg_rpns, key_rpn) -> bool:
    """Whether any expression of the aggregation touches a DECIMAL or a
    DATE column or a Decimal constant (else ``lower`` has nothing to
    do)."""
    return _mentions(list(sel_rpns) + list(agg_rpns) + [key_rpn], scan)


def lower(scan, sel_rpns, agg_rpns, agg_kinds, key_rpn=None) -> Lowered:
    """Lower a plan's expressions (module doc).  Raises ``NotLowerable``
    where the plan is not a device plan."""
    raw_dates: set = set()
    while True:
        try:
            return _lower_all(scan, sel_rpns, agg_rpns, agg_kinds,
                              key_rpn, raw_dates)
        except _Redo as e:
            raw_dates |= e.args[0]


def _finish(v: _Val) -> RpnExpression:
    return RpnExpression(tuple(v.low))


def _lower_all(scan, sel_rpns, agg_rpns, agg_kinds, key_rpn,
               raw_dates: set) -> Lowered:
    out = Lowered()

    def plain(r, what: str) -> RpnExpression:
        v = _lower_one(r, scan, raw_dates)
        if v.tag in ("dec", "decconst"):
            raise NotLowerable(f"a DECIMAL {what}")
        if v.tag == "date":
            raise _Redo({v.col})    # the bare column: its packed core
        return _finish(v)

    out.sel_rpns = [plain(r, "predicate") for r in sel_rpns]
    for r, kind in zip(agg_rpns, agg_kinds):
        if r is None:
            out.agg_rpns.append(None)
            out.agg_fracs.append(None)
            continue
        v = _lower_one(r, scan, raw_dates)
        if v.tag == "date":
            raise _Redo({v.col})
        if v.tag == "decconst":
            raise NotLowerable("aggregate of a DECIMAL constant")
        if v.tag == "dec" and kind not in _DEC_AGGS:
            raise NotLowerable(f"{kind} of a DECIMAL")
        out.agg_rpns.append(_finish(v))
        out.agg_fracs.append(v.frac if v.tag == "dec" and kind == "sum"
                             else None)
    if key_rpn is not None:
        out.key_rpn = plain(key_rpn, "GROUP BY key")
    for r in out.sel_rpns + [r for r in out.agg_rpns if r is not None] + \
            [r for r in (out.key_rpn,) if r is not None]:
        for n in r.nodes:
            if isinstance(n, RpnColumnRef) and \
                    n.eval_type is EvalType.INT:
                ft = scan.columns[n.col_idx].field_type
                if ft.eval_type is EvalType.DECIMAL:
                    out.dec_cols.add(n.col_idx)
                elif ft.eval_type is EvalType.DATETIME:
                    out.date_cols.add(n.col_idx)
    return out


# ---------------------------------------------------------------- bounds

_I32 = (-(1 << 31), (1 << 31) - 1)
_I64 = (-(1 << 63), (1 << 63) - 1)
_WIDTH = {"int32": _I32, "int64": _I64}


def _interval(rpn: RpnExpression, col_bounds: Sequence,
              width: tuple) -> Optional[tuple]:
    """``rpn``'s value interval from its columns' bounds, every
    intermediate inside ``width``; None where one may leave it or the
    expression has a function this does not know."""
    stack: list = []
    for n in rpn.nodes:
        if isinstance(n, RpnColumnRef):
            iv = col_bounds[n.col_idx]
        elif isinstance(n, RpnConst):
            if not isinstance(n.value, int):
                return None
            iv = (n.value, n.value)
        else:
            name = n.meta.name
            args = stack[-n.n_args:] if n.n_args else []
            if n.n_args:
                del stack[-n.n_args:]
            if name.endswith("Int") and name[:2] in _CMP or \
                    name.startswith(("Logical", "UnaryNot", "IsNull")):
                iv = (0, 1)
            elif name in ("PlusInt", "MinusInt", "MultiplyInt") and \
                    len(args) == 2:
                (a0, a1), (b0, b1) = args
                if name == "PlusInt":
                    iv = (a0 + b0, a1 + b1)
                elif name == "MinusInt":
                    iv = (a0 - b1, a1 - b0)
                else:
                    ps = (a0 * b0, a0 * b1, a1 * b0, a1 * b1)
                    iv = (min(ps), max(ps))
            else:
                return None
        if iv is None or iv[0] < width[0] or iv[1] > width[1]:
            return None
        stack.append(iv)
    (out,) = stack
    return out


def fits(plan, col_bounds: Sequence, dtypes: Sequence, n_rows: int) -> bool:
    """Whether the lowered plan's integer arithmetic is exact over a
    feed whose used column ``i`` holds values in ``col_bounds[i]`` on a
    plane of ``dtypes[i]``: an expression is computed at the width of
    its widest plane (a hoisted constant rides at the width of its
    device dtype bucket and is bounded by it, so the proof holds for
    every constant of the plan's const-blind class); every intermediate
    stays inside that width; a lowered SUM's argument fits int32 where
    the fused kernel would slice it into byte planes, and its sum over
    ``n_rows`` rows stays inside int64."""
    from .selection import split_params
    items = [(r, None) for r in plan.sel_rpns] + \
        [(r, f) for r, f in zip(plan.agg_rpns, plan.agg_fracs)
         if r is not None]
    if plan.key_rpn is not None:
        items.append((plan.key_rpn, None))
    n_cols = len(dtypes)
    param_rpns, _vals, param_dts = split_params([r for r, _f in items],
                                                n_cols)
    bounds = list(col_bounds)
    widths = list(dtypes)
    for dt in param_dts:
        if dt not in _WIDTH:
            return False        # a float among lowered integers
        bounds.append(_WIDTH[dt])
        widths.append(dt)
    for r, (_orig, frac) in zip(param_rpns, items):
        used = {n.col_idx for n in r.nodes if isinstance(n, RpnColumnRef)}
        width = _I64 if any(widths[c] != "int32" for c in used) else _I32
        iv = _interval(r, bounds, width)
        if iv is None:
            return False
        if frac is not None and \
                max(abs(iv[0]), abs(iv[1])) * max(n_rows, 1) > _I64[1]:
            return False
    return True
