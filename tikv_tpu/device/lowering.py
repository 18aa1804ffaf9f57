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

- a CHAR column of at most four bytes under a binary or ``_bin``
  collation as its int32 CODE plane (``datatype/tile.py code_plane``):
  a GROUP BY key or a COUNT's argument; any function of one stays with
  the host.

A Decimal constant that is COMPARED, or only added to, becomes an
operand of the kernel (``selection.split_params`` hoists it: one kernel
for every value).  One inside an aggregate's argument that lies under a
PRODUCT (the 1 of ``l_extendedprice * (1 - l_discount)``) is the plan's
structure, as a rescaling power of ten is: ``fixed_const``, in the
kernel's identity by value, and ``fits`` bounds the expression by the
value it has.  As an operand it would be bounded by everything its width
may hold, and no product of that is ever inside a width: such a plan was
never a device plan, so nothing that shared a kernel stops sharing it.

A SUM whose argument needs more than 31 bits, on int32 planes: where the
argument is a product whose factors stay inside int32 and it does not,
``fit`` asks for the LIMB split (``split_limbs``): ``x * m`` with ``x =
hi * 2**16 + lo`` (``hi = x >> 16``, arithmetic; ``lo = x & 0xFFFF``) is
``hi * m * 2**16 + lo * m``, so ``SUM(x * m) = SUM(hi * m) * 2**16 +
SUM(lo * m)`` exactly, for either sign of ``x`` and of ``m``; both limb
products are int32 where ``fit`` proves them so, the kernel sums them as
two aggregates and the finalize puts them together in int64.

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

import numpy as np

from ..datatype import EvalType, FieldTypeTp
from ..datatype.mydecimal import frac_of, to_scaled
from ..datatype.tile import DATE_SHIFT, code_width
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

_LIMB_BITS = 16

# aggregates of a lowered DECIMAL argument the device answers: SUM comes
# back as a DECIMAL of the argument's scale, COUNT as an integer.  AVG /
# MIN / MAX / FIRST of a DECIMAL stay with the host pipeline (this
# store's AVG answers the quotient: a SQL layer that wants TiKV's
# (COUNT, SUM) pair asks for COUNT(x), SUM(x)).
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
    DATE column ``col`` on its int32 plane), ``timeconst``, ``code`` (a
    CHAR column ``col`` on its code plane)."""

    __slots__ = ("low", "tag", "frac", "value", "col", "fixed")

    def __init__(self, low, tag, frac=None, value=None, col=None,
                 fixed=False):
        self.low, self.tag = low, tag
        self.frac, self.value, self.col = frac, value, col
        self.fixed = fixed      # a ``decconst`` that is structure


def _at_frac(v: _Val, frac: int) -> list:
    """``v``'s lowered nodes at scale ``frac`` (not below its own)."""
    if v.tag == "decconst":
        scaled = to_scaled(v.value, frac)
        if scaled is None:
            raise NotLowerable("constant beyond int64 at its scale")
        return [fixed_const(scaled) if v.fixed
                else RpnConst(scaled, EvalType.INT)]
    if v.frac == frac:
        return list(v.low)
    return list(v.low) + [fixed_const(10 ** (frac - v.frac)),
                          _call("MultiplyInt")]


def _own_frac(v: _Val) -> int:
    return frac_of(v.value) if v.tag == "decconst" else v.frac


def _under_product(rpn: RpnExpression) -> set:
    """Indices of ``rpn``'s constants that lie under a
    ``MultiplyDecimal``."""
    under: set = set()
    stack: list = []
    for i, node in enumerate(rpn.nodes):
        if isinstance(node, RpnFnCall):
            consts = set().union(*stack[-node.n_args:]) \
                if node.n_args else set()
            if node.n_args:
                del stack[-node.n_args:]
            if node.meta.name == "MultiplyDecimal":
                under |= consts
            stack.append(consts)
        else:
            stack.append({i} if isinstance(node, RpnConst) else set())
    return under


def _lower_one(rpn: RpnExpression, scan, raw_dates: set,
               in_agg: bool = False) -> _Val:
    """One expression lowered; ``in_agg``: it is an aggregate's
    argument, where a constant under a product is structure."""
    from ..copr.region_cache import scaled_frac
    fixed = _under_product(rpn) if in_agg else ()
    stack: list = []
    for i, node in enumerate(rpn.nodes):
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
            elif node.eval_type is EvalType.BYTES and \
                    code_width(ft) is not None:
                stack.append(_Val([RpnColumnRef(
                    node.col_idx, EvalType.INT)], "code",
                    col=node.col_idx))
            else:
                stack.append(_Val([node], "raw"))
        elif isinstance(node, RpnConst):
            if isinstance(node.value, Decimal):
                if not node.value.is_finite() or \
                        frac_of(node.value) > MAX_FRAC:
                    raise NotLowerable("constant beyond a scaled int64")
                stack.append(_Val(None, "decconst", value=node.value,
                                  fixed=i in fixed))
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
    if any(a.tag == "code" for a in args):
        raise NotLowerable(f"{name} of a CHAR code plane")
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

    __slots__ = ("sel_rpns", "agg_rpns", "agg_fracs", "key_rpns",
                 "key_codes", "date_cols", "dec_cols", "code_cols")

    def __init__(self):
        self.sel_rpns: list = []
        self.agg_rpns: list = []
        # per aggregate: the scale its SUM comes back at, or None
        self.agg_fracs: list = []
        # the GROUP BY's keys, and per key the bytes of its code plane
        # (0: an integer key)
        self.key_rpns: list = []
        self.key_codes: list = []
        self.date_cols: set = set()     # scan offsets on the int32 plane
        self.dec_cols: set = set()      # scan offsets of scaled DECIMALs
        self.code_cols: set = set()     # scan offsets on a code plane

    def fixed_consts(self) -> int:
        """Constants that are structure in the aggregates' arguments."""
        return sum(1 for r in self.agg_rpns if r is not None
                   for n in r.nodes
                   if isinstance(n, RpnConst) and n.fixed)


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
                        ft.tp in _DATE_TPS or \
                        code_width(ft) is not None:
                    return True
    return False


def needs_lowering(scan, sel_rpns, agg_rpns, key_rpns) -> bool:
    """Whether any expression of the aggregation touches a DECIMAL, a
    DATE or a short CHAR column or a Decimal constant (else ``lower``
    has nothing to do).  ``key_rpns``: the GROUP BY's keys."""
    return _mentions(list(sel_rpns) + list(agg_rpns) + list(key_rpns),
                     scan)


def lower(scan, sel_rpns, agg_rpns, agg_kinds, key_rpns=()) -> Lowered:
    """Lower a plan's expressions (module doc).  ``key_rpns``: the GROUP
    BY's keys.  Raises ``NotLowerable`` where the plan is not a
    device plan."""
    raw_dates: set = set()
    while True:
        try:
            return _lower_all(scan, sel_rpns, agg_rpns, agg_kinds,
                              list(key_rpns), raw_dates)
        except _Redo as e:
            raw_dates |= e.args[0]


def _finish(v: _Val) -> RpnExpression:
    return RpnExpression(tuple(v.low))


def _lower_all(scan, sel_rpns, agg_rpns, agg_kinds, key_rpns,
               raw_dates: set) -> Lowered:
    out = Lowered()

    def plain(r, what: str, code_ok: bool = False) -> _Val:
        v = _lower_one(r, scan, raw_dates)
        if v.tag in ("dec", "decconst"):
            raise NotLowerable(f"a DECIMAL {what}")
        if v.tag == "code" and not code_ok:
            raise NotLowerable(f"a CHAR {what}")
        if v.tag == "date":
            raise _Redo({v.col})    # the bare column: its packed core
        return v

    out.sel_rpns = [_finish(plain(r, "predicate")) for r in sel_rpns]
    for r, kind in zip(agg_rpns, agg_kinds):
        if r is None:
            out.agg_rpns.append(None)
            out.agg_fracs.append(None)
            continue
        v = _lower_one(r, scan, raw_dates, in_agg=True)
        if v.tag == "date":
            raise _Redo({v.col})
        if v.tag == "decconst":
            raise NotLowerable("aggregate of a DECIMAL constant")
        if v.tag == "dec" and kind not in _DEC_AGGS:
            raise NotLowerable(f"{kind} of a DECIMAL")
        if v.tag == "code" and kind != "count":
            raise NotLowerable(f"{kind} of a CHAR code plane")
        out.agg_rpns.append(_finish(v))
        out.agg_fracs.append(v.frac if v.tag == "dec" and kind == "sum"
                             else None)
    for r in key_rpns:
        v = plain(r, "GROUP BY key", code_ok=True)
        out.key_rpns.append(_finish(v))
        out.key_codes.append(
            code_width(scan.columns[v.col].field_type)
            if v.tag == "code" else 0)
    for r in out.sel_rpns + [r for r in out.agg_rpns if r is not None] + \
            out.key_rpns:
        for n in r.nodes:
            if isinstance(n, RpnColumnRef) and \
                    n.eval_type is EvalType.INT:
                ft = scan.columns[n.col_idx].field_type
                if ft.eval_type is EvalType.DECIMAL:
                    out.dec_cols.add(n.col_idx)
                elif ft.eval_type is EvalType.DATETIME:
                    out.date_cols.add(n.col_idx)
                elif ft.eval_type is EvalType.BYTES:
                    out.code_cols.add(n.col_idx)
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
            elif name in ("RightShift", "BitAndSig") and len(args) == 2 \
                    and args[1][0] == args[1][1] and args[1][0] >= 0:
                # a limb of ``split_limbs``: by a constant
                (a0, a1), (k, _k) = args
                iv = (a0 >> k, a1 >> k) if name == "RightShift" and k < 64 \
                    else (0, k) if name == "BitAndSig" else None
            else:
                return None
        if iv is None or iv[0] < width[0] or iv[1] > width[1]:
            return None
        stack.append(iv)
    (out,) = stack
    return out


def _top_product(rpn: RpnExpression) -> Optional[tuple]:
    """``(left nodes, right nodes)`` of an expression that is a
    ``MultiplyInt`` at the top, else None."""
    nodes = rpn.nodes
    top = nodes[-1]
    if not isinstance(top, RpnFnCall) or top.meta.name != "MultiplyInt":
        return None
    need, i = 1, len(nodes) - 1     # walk the right operand back
    while need:
        i -= 1
        n = nodes[i]
        need += (n.n_args if isinstance(n, RpnFnCall) else 0) - 1
    return nodes[:i], nodes[i:-1]


def _limbs(rpn: RpnExpression, split_right: bool) -> tuple:
    """``x * m`` as ``(hi(x) * m, lo(x) * m)`` (module doc); the factor
    split is the left one, or the right one where ``split_right``."""
    left, right = _top_product(rpn)
    x, m = (right, left) if split_right else (left, right)
    mul = _call("MultiplyInt")
    return (RpnExpression(tuple(x) + (
                fixed_const(_LIMB_BITS), _call("RightShift")) +
                tuple(m) + (mul,)),
            RpnExpression(tuple(x) + (
                fixed_const((1 << _LIMB_BITS) - 1), _call("BitAndSig")) +
                tuple(m) + (mul,)))


def split_limbs(plan, need: tuple) -> tuple:
    """The plan's aggregates with those ``need`` names (``(index,
    split_right)`` pairs, ``fit``'s answer) limb-split → ``(agg_rpns,
    agg_kinds, agg_fracs, recipes)``: each such SUM is two SUMs, and
    ``recipes`` says per aggregate of the plan which of the DEVICE's it
    is: an index, or a limb pair's ``(hi, lo)``."""
    sides = dict(need)
    rpns, kinds, fracs, recipes = [], [], [], []
    for j, (r, spec, frac) in enumerate(zip(plan.agg_rpns, plan.specs,
                                            plan.agg_fracs)):
        if j in sides:
            recipes.append((len(rpns), len(rpns) + 1))
            rpns += _limbs(r, sides[j])
            kinds += ["sum", "sum"]
            fracs += [frac, frac]
        else:
            recipes.append(len(rpns))
            rpns.append(r)
            kinds.append(spec.kind)
            fracs.append(frac)
    return rpns, kinds, fracs, recipes


def recipe_planes(recipes, planes: list) -> list:
    """The device aggregates' finalized ``(values, validity)`` planes →
    the plan's aggregates': a limb pair put together in int64 (``fit``
    proved it holds)."""
    out = []
    for src in recipes:
        if isinstance(src, int):
            out.append(planes[src])
            continue
        (hv, hok), (lv, _lok) = planes[src[0]], planes[src[1]]
        out.append(((np.asarray(hv, np.int64) << _LIMB_BITS) +
                    np.asarray(lv, np.int64), hok))
    return out


def _bounded(plan, col_bounds: Sequence, dtypes: Sequence):
    """``fit``'s view of a plan: its expressions with their hoisted
    constants as bounded operands → ``(items, param rpns, bounds,
    widths)``, ``items`` the ``(rpn, None | (device aggregate, scale))``
    pairs the rpns were made of; None where an operand is a float."""
    from .selection import split_params
    items = [(r, None) for r in plan.sel_rpns] + \
        [(r, (j, f)) for j, (r, f) in enumerate(zip(plan.agg_rpns,
                                                   plan.agg_fracs))
         if r is not None]
    items += [(r, None) for r in plan.key_rpns]
    param_rpns, _vals, param_dts = split_params([r for r, _f in items],
                                                len(dtypes))
    bounds = list(col_bounds)
    widths = list(dtypes)
    for dt in param_dts:
        if dt not in _WIDTH:
            return None         # a float among lowered integers
        bounds.append(_WIDTH[dt])
        widths.append(dt)
    return items, param_rpns, bounds, widths


def _width_of(rpn: RpnExpression, widths: list) -> tuple:
    used = {n.col_idx for n in rpn.nodes if isinstance(n, RpnColumnRef)}
    return _I64 if any(widths[c] != "int32" for c in used) else _I32


def agg_intervals(plan, col_bounds: Sequence, dtypes: Sequence) -> dict:
    """{device aggregate: its argument's proven ``(lo, hi)``} over a
    feed of these bounds, for every constant of the plan's const-blind
    class: what the byte planes of a computed argument are sized by
    (aggregate.py ``_arg_nbytes``).  An aggregate it cannot bound is
    left out."""
    got = _bounded(plan, col_bounds, dtypes)
    out = {}
    if got is not None:
        items, param_rpns, bounds, widths = got
        for r, (_orig, agg) in zip(param_rpns, items):
            if agg is not None:
                iv = _interval(r, bounds, _width_of(r, widths))
                if iv is not None:
                    out[agg[0]] = iv
    return out


def fit(plan, col_bounds: Sequence, dtypes: Sequence,
        n_rows: int) -> Optional[tuple]:
    """Whether the lowered plan's integer arithmetic is exact over a
    feed whose used column ``i`` holds values in ``col_bounds[i]`` on a
    plane of ``dtypes[i]`` → ``()`` where it is as it stands, the
    aggregates to limb-split where it is once they are (``(index,
    split_right)`` pairs for ``split_limbs``), None where it cannot be
    shown.

    An expression is computed at the width of its widest plane (a
    hoisted constant rides at the width of its device dtype bucket and
    is bounded by it, so the proof holds for every constant of the
    plan's const-blind class; a ``fixed`` one is the value it is);
    every intermediate stays inside that width; a lowered SUM's
    argument fits int32 where the fused kernel would slice it into
    byte planes, and its sum over ``n_rows`` rows stays inside int64.
    A SUM over int32 planes whose argument is a product that leaves
    int32 while its factors do not is asked to be limb-split where both
    limb products then stay inside (module doc), the sum of the whole
    product inside int64 with the room the limbs' recombination takes."""
    got = _bounded(plan, col_bounds, dtypes)
    if got is None:
        return None
    items, param_rpns, bounds, widths = got
    rows = max(n_rows, 1)
    need = []
    for r, (_orig, agg) in zip(param_rpns, items):
        width = _width_of(r, widths)
        iv = _interval(r, bounds, width)
        frac = None if agg is None else agg[1]
        if iv is None:
            side = None if frac is None or width is not _I32 else \
                _limb_side(r, bounds, rows)
            if side is None:
                return None
            need.append((agg[0], side))
        elif frac is not None and \
                max(abs(iv[0]), abs(iv[1])) * rows > _I64[1]:
            return None
    return tuple(need)


def _limb_side(rpn: RpnExpression, bounds: list, rows: int):
    """Which factor of the int32-leaving product ``rpn`` to limb-split
    (False: the left, True: the right), or None where neither split
    keeps both limb products inside int32 and their sums inside
    int64."""
    if _top_product(rpn) is None:
        return None
    for split_right in (False, True):
        ivs = [_interval(limb, bounds, _I32)
               for limb in _limbs(rpn, split_right)]
        if None in ivs:
            continue
        (h0, h1), (l0, l1) = ivs
        if (max(abs(h0), abs(h1)) << _LIMB_BITS) * rows + \
                max(abs(l0), abs(l1)) * rows <= _I64[1]:
            return split_right
    return None


def fits(plan, col_bounds: Sequence, dtypes: Sequence, n_rows: int) -> bool:
    """``fit`` with nothing left to split."""
    return fit(plan, col_bounds, dtypes, n_rows) == ()
