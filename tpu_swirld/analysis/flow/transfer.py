"""Per-primitive transfer functions over the interval×dtype lattice.

Every first-order primitive the consensus kernels use has an entry in
``TRANSFERS``; :func:`apply_transfer` dispatches an eqn through it and
then runs the centralized safety checks:

- **SW008** (overflow-reachable): the transfer computes the
  *mathematical* result interval in unbounded Python arithmetic; if an
  integer output's interval escapes its dtype range the site is
  reported, then the interval is clamped to the dtype range so one
  overflow doesn't cascade into a wall of downstream findings.  The
  same check covers the f32-tally exactness argument: an
  integer-valued float accumulation whose bound reaches 2**(mantissa+1)
  can no longer be exact, which is reported as SW008 and the
  ``integral`` flag dropped.
- **SW009** (unproven bounds): ``gather``/``scatter`` sites whose mode
  is ``PROMISE_IN_BOUNDS`` must have index intervals provably inside
  the operand extent (``CLIP``/``FILL_OR_DROP`` modes are runtime
  guards and pass).  ``dynamic_slice``/``dynamic_update_slice`` starts
  are checked against ``dim - slice_size`` — XLA clamps them, so the
  failure mode is a silently *wrong window*, not a crash, which is
  exactly why it must be proven statically.
- **SW010** (lossy narrowing): ``convert_element_type`` where the
  operand interval is not provably representable in the target dtype
  (including int→float casts past the float's exact-integer range).
- **SW011** (sentinel collision): ``select_n`` where one arm is a
  constant equal to a declared padding sentinel and another arm's
  interval contains that value — the sentinel becomes indistinguishable
  from live data.

Unknown primitives raise :class:`UnknownPrimitiveError` — the registry
never guesses (exit code 2 at the CLI; there is no "assume top" path).

``select_n`` performs pattern-based path refinement: when the predicate
is itself ``lt/le/gt/ge/eq(v, k)`` and an arm is ``v`` or ``v ± c`` of
the *same* variable, the arm's interval is first met with the branch
condition.  jnp lowers every ``x[i]`` through
``select_n(i < 0, i, i + n)`` for negative-index normalization, so
without this refinement every plain gather in the pipeline would be an
SW009 false positive.
"""

from __future__ import annotations

import numpy as np

from tpu_swirld.analysis.flow.lattice import (
    AbsVal,
    Interval,
    NEG_INF,
    POS_INF,
    dtype_range,
    is_bool_dtype,
    is_float_dtype,
    is_int_dtype,
    iv_abs,
    iv_add,
    iv_div_float,
    iv_div_int,
    iv_max,
    iv_min,
    iv_mul,
    iv_neg,
    iv_rem,
    iv_sub,
)


class UnknownPrimitiveError(Exception):
    """A primitive without a registered transfer function was reached."""

    def __init__(self, primitive: str, stage: str = "?", where: str = "?"):
        self.primitive = primitive
        self.stage = stage
        self.where = where
        super().__init__(
            f"no transfer function for primitive {primitive!r} "
            f"(stage {stage}, at {where}); the registry hard-fails rather "
            f"than guess — add a sound transfer to analysis/flow/transfer.py"
        )


TRANSFERS = {}

#: higher-order primitives the interpreter sub-interprets itself.
HIGHER_ORDER = frozenset(
    {"jit", "closed_call", "core_call", "scan", "while", "cond", "shard_map",
     "custom_jvp_call", "custom_vjp_call", "remat", "checkpoint"}
)

#: primitives whose int results are accumulations — these also get the
#: integral-float exactness check (f32 tallies must stay < 2**24).
ACCUMULATING = frozenset(
    {"add", "sub", "mul", "dot_general", "reduce_sum", "cumsum", "cumprod",
     "scatter-add", "psum", "psum_invariant"}
)

#: primitives that run their own representability check (skip SW008 there).
SELF_CHECKED = frozenset({"convert_element_type"})


def register(*names):
    def deco(fn):
        for n in names:
            TRANSFERS[n] = fn
        return fn
    return deco


def registered_primitives():
    """Sorted names of all first-order primitives with transfers."""
    return sorted(TRANSFERS)


def _out(eqn, j, iv, integral):
    return AbsVal.from_aval(eqn.outvars[j].aval, iv, integral)


def _exact_float_limit(dtype) -> int:
    return 1 << (np.finfo(np.dtype(dtype)).nmant + 1)


def apply_transfer(ctx, eqn, args):
    """Dispatch one eqn; returns out AbsVals, emits findings via ctx."""
    name = eqn.primitive.name
    fn = TRANSFERS.get(name)
    if fn is None:
        raise UnknownPrimitiveError(name, getattr(ctx, "stage", "?"),
                                    ctx.where(eqn))
    ctx.exercised.add(name)
    outs = fn(ctx, eqn, args)
    checked = []
    for j, o in enumerate(outs):
        if o.iv.is_bottom:
            checked.append(o)
            continue
        if is_int_dtype(o.dtype) and name not in SELF_CHECKED:
            lo, hi = dtype_range(o.dtype)
            if o.iv.lo < lo or o.iv.hi > hi:
                ctx.report(
                    "SW008", eqn,
                    f"{name}: {np.dtype(o.dtype).name} result can reach "
                    f"{o.iv}, outside [{lo}, {hi}] — integer wraparound "
                    f"reachable at this envelope",
                )
                o = o.clamp_to_dtype()
        elif (is_float_dtype(o.dtype) and o.integral
              and name in ACCUMULATING):
            lim = _exact_float_limit(o.dtype)
            m = max(abs(o.iv.lo), abs(o.iv.hi))
            if m >= lim:
                ctx.report(
                    "SW008", eqn,
                    f"{name}: integer-valued {np.dtype(o.dtype).name} "
                    f"accumulation can reach {o.iv}, at or past the exact-"
                    f"integer limit 2**{lim.bit_length() - 1} — tally no "
                    f"longer exact",
                )
                o = o.with_iv(o.iv, integral=False)
        checked.append(o)
    return checked


# --------------------------------------------------------------------------
# elementwise arithmetic


@register("add")
def _t_add(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_add(a.iv, b.iv), a.integral and b.integral)]


@register("sub")
def _t_sub(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_sub(a.iv, b.iv), a.integral and b.integral)]


@register("mul")
def _t_mul(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_mul(a.iv, b.iv), a.integral and b.integral)]


@register("neg")
def _t_neg(ctx, eqn, args):
    (a,) = args
    return [_out(eqn, 0, iv_neg(a.iv), a.integral)]


@register("abs")
def _t_abs(ctx, eqn, args):
    (a,) = args
    return [_out(eqn, 0, iv_abs(a.iv), a.integral)]


@register("sign")
def _t_sign(ctx, eqn, args):
    (a,) = args
    lo = -1 if a.iv.lo < 0 else (0 if a.iv.lo == 0 else 1)
    hi = 1 if a.iv.hi > 0 else (0 if a.iv.hi == 0 else -1)
    return [_out(eqn, 0, Interval(lo, hi), True)]


@register("div")
def _t_div(ctx, eqn, args):
    a, b = args
    if is_int_dtype(eqn.outvars[0].aval.dtype):
        return [_out(eqn, 0, iv_div_int(a.iv, b.iv), True)]
    return [_out(eqn, 0, iv_div_float(a.iv, b.iv), False)]


@register("rem")
def _t_rem(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_rem(a.iv, b.iv), a.integral and b.integral)]


@register("max")
def _t_max(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_max(a.iv, b.iv), a.integral and b.integral)]


@register("min")
def _t_min(ctx, eqn, args):
    a, b = args
    return [_out(eqn, 0, iv_min(a.iv, b.iv), a.integral and b.integral)]


@register("clamp")
def _t_clamp(ctx, eqn, args):
    lo_v, x, hi_v = args
    iv = iv_min(iv_max(x.iv, lo_v.iv), hi_v.iv)
    return [_out(eqn, 0, iv, x.integral and lo_v.integral and hi_v.integral)]


@register("integer_pow")
def _t_integer_pow(ctx, eqn, args):
    (a,) = args
    y = int(eqn.params["y"])
    iv = Interval.point(1)
    for _ in range(abs(y)):
        iv = iv_mul(iv, a.iv)
    if y < 0:
        iv = iv_div_float(Interval.point(1.0), iv)
    return [_out(eqn, 0, iv, a.integral and y >= 0)]


# --------------------------------------------------------------------------
# boolean / bitwise


def _bitlen(v):
    if v in (POS_INF, NEG_INF):
        return None
    return int(v).bit_length()


@register("and")
def _t_and(ctx, eqn, args):
    a, b = args
    if is_bool_dtype(eqn.outvars[0].aval.dtype):
        return [_out(eqn, 0, iv_min(a.iv, b.iv).meet(Interval(0, 1)), True)]
    if a.iv.lo >= 0 and b.iv.lo >= 0:
        return [_out(eqn, 0, Interval(0, min(a.iv.hi, b.iv.hi)), True)]
    return [_out(eqn, 0, AbsVal.from_aval(eqn.outvars[0].aval).iv, True)]


@register("or")
def _t_or(ctx, eqn, args):
    a, b = args
    if is_bool_dtype(eqn.outvars[0].aval.dtype):
        return [_out(eqn, 0, iv_max(a.iv, b.iv).meet(Interval(0, 1)), True)]
    if a.iv.lo >= 0 and b.iv.lo >= 0:
        ba, bb = _bitlen(a.iv.hi), _bitlen(b.iv.hi)
        if ba is None or bb is None:
            return [_out(eqn, 0, AbsVal.from_aval(eqn.outvars[0].aval).iv, True)]
        hi = (1 << max(ba, bb)) - 1
        return [_out(eqn, 0, Interval(max(a.iv.lo, b.iv.lo), max(hi, 0)), True)]
    return [_out(eqn, 0, AbsVal.from_aval(eqn.outvars[0].aval).iv, True)]


@register("xor")
def _t_xor(ctx, eqn, args):
    a, b = args
    if is_bool_dtype(eqn.outvars[0].aval.dtype):
        return [_out(eqn, 0, Interval(0, 1), True)]
    if a.iv.lo >= 0 and b.iv.lo >= 0:
        ba, bb = _bitlen(a.iv.hi), _bitlen(b.iv.hi)
        if ba is not None and bb is not None:
            return [_out(eqn, 0, Interval(0, (1 << max(ba, bb)) - 1), True)]
    return [_out(eqn, 0, AbsVal.from_aval(eqn.outvars[0].aval).iv, True)]


@register("not")
def _t_not(ctx, eqn, args):
    (a,) = args
    if is_bool_dtype(eqn.outvars[0].aval.dtype):
        return [_out(eqn, 0, Interval(0, 1), True)]
    return [_out(eqn, 0, Interval(-a.iv.hi - 1, -a.iv.lo - 1), True)]


def _cmp_decide(op: str, a: Interval, b: Interval) -> Interval:
    """Fold a comparison to a point when the operand intervals decide it
    for every element (whole-array abstraction: a decided interval
    comparison is decided element-wise)."""
    if a.is_bottom or b.is_bottom:
        return Interval(0, 1)
    if op == "lt":
        if a.hi < b.lo:
            return Interval.point(1)
        if a.lo >= b.hi:
            return Interval.point(0)
    elif op == "le":
        if a.hi <= b.lo:
            return Interval.point(1)
        if a.lo > b.hi:
            return Interval.point(0)
    elif op == "gt":
        if a.lo > b.hi:
            return Interval.point(1)
        if a.hi <= b.lo:
            return Interval.point(0)
    elif op == "ge":
        if a.lo >= b.hi:
            return Interval.point(1)
        if a.hi < b.lo:
            return Interval.point(0)
    elif op == "eq":
        if a.is_point and b.is_point and a.lo == b.lo:
            return Interval.point(1)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval.point(0)
    elif op == "ne":
        if a.is_point and b.is_point and a.lo == b.lo:
            return Interval.point(0)
        if a.hi < b.lo or b.hi < a.lo:
            return Interval.point(1)
    return Interval(0, 1)


def _cmp(eqn, args, op):
    a, b = args
    return [_out(eqn, 0, _cmp_decide(op, a.iv, b.iv), True)]


@register("eq")
def _t_eq(ctx, eqn, args):
    return _cmp(eqn, args, "eq")


@register("ne")
def _t_ne(ctx, eqn, args):
    return _cmp(eqn, args, "ne")


@register("lt")
def _t_lt(ctx, eqn, args):
    return _cmp(eqn, args, "lt")


@register("le")
def _t_le(ctx, eqn, args):
    return _cmp(eqn, args, "le")


@register("gt")
def _t_gt(ctx, eqn, args):
    return _cmp(eqn, args, "gt")


@register("ge")
def _t_ge(ctx, eqn, args):
    return _cmp(eqn, args, "ge")


# --------------------------------------------------------------------------
# select_n with path refinement + sentinel-collision check (SW011)

_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}


def _refine_by_pred(v_iv: Interval, op: str, k_iv: Interval, branch: bool) -> Interval:
    """Interval of v inside the branch where ``op(v, k)`` is `branch`."""
    if op == "lt":
        cond_true = Interval(NEG_INF, k_iv.hi - 1 if isinstance(k_iv.hi, int) else k_iv.hi)
        cond_false = Interval(k_iv.lo, POS_INF)
    elif op == "le":
        cond_true = Interval(NEG_INF, k_iv.hi)
        cond_false = Interval(k_iv.lo + 1 if isinstance(k_iv.lo, int) else k_iv.lo, POS_INF)
    elif op == "gt":
        cond_true = Interval(k_iv.lo + 1 if isinstance(k_iv.lo, int) else k_iv.lo, POS_INF)
        cond_false = Interval(NEG_INF, k_iv.hi)
    elif op == "ge":
        cond_true = Interval(k_iv.lo, POS_INF)
        cond_false = Interval(NEG_INF, k_iv.hi - 1 if isinstance(k_iv.hi, int) else k_iv.hi)
    elif op == "eq":
        cond_true = k_iv
        cond_false = Interval(NEG_INF, POS_INF)
    else:
        return v_iv
    return v_iv.meet(cond_true if branch else cond_false)


def _peel(ctx, atom):
    """Follow value-preserving ``convert_element_type`` chains back to the
    underlying variable (jnp's index normalization converts to int64
    before adding the axis size)."""
    import jax.extend.core as jcore

    for _ in range(8):
        if isinstance(atom, jcore.Literal):
            break
        d = ctx.defs.get(atom)
        if d is None or d.primitive.name != "convert_element_type":
            break
        atom = d.invars[0]
    return atom


def _same_var(a, b):
    return a is b or (hasattr(a, "count") and a == b)


def _case_as_offset_of(ctx, case_atom, base_var):
    """If `case` is `base`, or add/sub of `base` and a constant, return the
    constant offset interval; else None.  Converts between int dtypes are
    peeled on both sides."""
    import jax.extend.core as jcore

    case_atom = _peel(ctx, case_atom)
    if isinstance(case_atom, jcore.Literal):
        return None
    if _same_var(case_atom, base_var):
        return Interval.point(0)
    d = ctx.defs.get(case_atom)
    if d is None or d.primitive.name not in ("add", "sub"):
        return None
    x, y = d.invars
    for var, const, sign in ((x, y, 1), (y, x, 1 if d.primitive.name == "add" else None)):
        if sign is None:
            continue
        if _same_var(_peel(ctx, var), base_var):
            k = ctx.const_interval(const)
            if k is None:
                return None
            return k if d.primitive.name == "add" else iv_neg(k)
    return None


@register("select_n")
def _t_select_n(ctx, eqn, args):
    import jax.extend.core as jcore

    pred, cases = args[0], args[1:]
    out_dt = eqn.outvars[0].aval.dtype

    # Decided predicate: only the selected arm is reachable, so the
    # unselected arms contribute nothing (and cannot collide with a
    # sentinel).  Covers jnp's negative-index normalization when the
    # index interval never crosses zero.
    p_iv = pred.iv
    if p_iv.is_point and isinstance(p_iv.lo, int):
        idx = int(p_iv.lo)
        if 0 <= idx < len(cases):
            sel = cases[idx]
            return [_out(eqn, 0, sel.iv, sel.integral)]

    # Path refinement for the 2-case boolean select where the predicate
    # compares a variable against a constant and an arm is an affine
    # offset of that same variable (jnp's negative-index normalization,
    # and guard patterns like where(i < cap, i, cap - 1)).
    refined = None
    if len(cases) == 2 and not isinstance(eqn.invars[0], jcore.Literal):
        pd = ctx.defs.get(eqn.invars[0])
        if pd is not None and pd.primitive.name in _FLIP:
            op = pd.primitive.name
            lhs, rhs = pd.invars
            k_iv = ctx.const_interval(rhs)
            base = _peel(ctx, lhs)
            if k_iv is None:
                k_iv = ctx.const_interval(lhs)
                base = _peel(ctx, rhs)
                op = _FLIP[op]
            if k_iv is not None and not isinstance(base, jcore.Literal):
                base_iv = ctx.env_lookup(base)
                if base_iv is not None:
                    parts = []
                    for which, case_atom, case_val in (
                        (False, eqn.invars[1], cases[0]),
                        (True, eqn.invars[2], cases[1]),
                    ):
                        off = _case_as_offset_of(ctx, case_atom, base)
                        if off is not None:
                            br = _refine_by_pred(base_iv.iv, op, k_iv, which)
                            parts.append(
                                Interval.bottom() if br.is_bottom
                                else iv_add(br, off))
                        else:
                            parts.append(case_val.iv)
                    iv = parts[0].join(parts[1])
                    refined = iv

    if refined is None:
        iv = Interval.bottom()
        for c in cases:
            iv = iv.join(c.iv)

    # SW011: one arm a constant sentinel, another arm's live range
    # containing that very value.
    if is_int_dtype(out_dt):
        for sval in ctx.sentinels:
            if not any(c.iv.is_point and c.iv.lo == sval for c in cases):
                continue
            for c in cases:
                if c.iv.is_point and c.iv.lo == sval:
                    continue
                if c.iv.contains(sval):
                    ctx.report(
                        "SW011", eqn,
                        f"select_n: one arm is the padding sentinel {sval} "
                        f"and another arm's range {c.iv} contains it — "
                        f"sentinel can collide with live data",
                    )
                    break

    integral = all(c.integral for c in cases)
    return [_out(eqn, 0, iv, integral)]


# --------------------------------------------------------------------------
# dtype conversion (SW010)


@register("convert_element_type")
def _t_convert(ctx, eqn, args):
    (a,) = args
    new_dt = np.dtype(eqn.params["new_dtype"])
    iv = a.iv
    integral = a.integral
    if is_int_dtype(new_dt) or is_bool_dtype(new_dt):
        lo, hi = dtype_range(new_dt)
        src_lo = a.iv.lo if a.integral else np.floor(a.iv.lo) if a.iv.lo not in (NEG_INF,) else NEG_INF
        src_hi = a.iv.hi if a.integral else np.ceil(a.iv.hi) if a.iv.hi not in (POS_INF,) else POS_INF
        if is_bool_dtype(new_dt):
            if a.iv.is_point and a.iv.lo == 0:
                iv = Interval(0, 0)
            elif not a.iv.contains(0):
                iv = Interval(1, 1)
            else:
                iv = Interval(0, 1)
            return [_out(eqn, 0, iv, True)]
        if src_lo < lo or src_hi > hi:
            ctx.report(
                "SW010", eqn,
                f"convert_element_type: narrowing to {new_dt.name} loses "
                f"values — operand range {a.iv} exceeds [{lo}, {hi}]",
            )
        iv = Interval(
            max(lo, int(src_lo) if src_lo not in (NEG_INF, POS_INF) else lo),
            min(hi, int(src_hi) if src_hi not in (NEG_INF, POS_INF) else hi),
        )
        if iv.is_bottom:
            iv = Interval(lo, hi)
        integral = True
    elif is_float_dtype(new_dt):
        if a.integral and is_int_dtype(np.dtype(a.dtype)):
            lim = _exact_float_limit(new_dt)
            m = max(abs(a.iv.lo), abs(a.iv.hi))
            if m >= lim:
                ctx.report(
                    "SW010", eqn,
                    f"convert_element_type: int→{new_dt.name} cast of range "
                    f"{a.iv} passes the exact-integer limit 2**"
                    f"{lim.bit_length() - 1} — values rounded",
                )
                integral = False
        iv = Interval(float(a.iv.lo) if a.iv.lo not in (NEG_INF, POS_INF) else a.iv.lo,
                      float(a.iv.hi) if a.iv.hi not in (NEG_INF, POS_INF) else a.iv.hi)
    return [_out(eqn, 0, iv, integral)]


# --------------------------------------------------------------------------
# shape-only / structural


def _passthrough(ctx, eqn, args):
    a = args[0]
    return [_out(eqn, 0, a.iv, a.integral)]


for _name in ("broadcast_in_dim", "reshape", "squeeze", "expand_dims",
              "transpose", "rev", "copy", "slice", "stop_gradient",
              "reduce_precision", "pbroadcast", "reshard"):
    register(_name)(_passthrough)


@register("pvary")
def _t_pvary(ctx, eqn, args):
    # marks each operand as varying over mesh axes: values are unchanged
    return [_out(eqn, j, a.iv, a.integral) for j, a in enumerate(args)]


@register("concatenate")
def _t_concat(ctx, eqn, args):
    iv = Interval.bottom()
    integral = True
    for a in args:
        if a.size:
            iv = iv.join(a.iv)
            integral = integral and a.integral
    return [_out(eqn, 0, iv, integral)]


@register("pad")
def _t_pad(ctx, eqn, args):
    a, pv = args
    return [_out(eqn, 0, a.iv.join(pv.iv), a.integral and pv.integral)]


@register("iota")
def _t_iota(ctx, eqn, args):
    dim = eqn.params["dimension"]
    n = eqn.outvars[0].aval.shape[dim]
    return [_out(eqn, 0, Interval(0, max(n - 1, 0)), True)]


@register("sort")
def _t_sort(ctx, eqn, args):
    return [_out(eqn, j, a.iv, a.integral) for j, a in enumerate(args)]


# --------------------------------------------------------------------------
# reductions


def _reduced_count(operand_shape, axes):
    n = 1
    for ax in axes:
        n *= operand_shape[ax]
    return max(n, 1)


@register("reduce_sum")
def _t_reduce_sum(ctx, eqn, args):
    (a,) = args
    n = _reduced_count(a.shape, eqn.params["axes"])
    # sum of exactly n elements, each in [lo, hi], is [n*lo, n*hi]
    return [_out(eqn, 0, Interval(a.iv.lo * n, a.iv.hi * n), a.integral)]


@register("reduce_max")
def _t_reduce_max(ctx, eqn, args):
    (a,) = args
    return [_out(eqn, 0, a.iv, a.integral)]


@register("reduce_min")
def _t_reduce_min(ctx, eqn, args):
    (a,) = args
    return [_out(eqn, 0, a.iv, a.integral)]


@register("reduce_and")
def _t_reduce_and(ctx, eqn, args):
    return [_out(eqn, 0, Interval(0, 1), True)]


@register("reduce_or")
def _t_reduce_or(ctx, eqn, args):
    return [_out(eqn, 0, Interval(0, 1), True)]


@register("argmax", "argmin")
def _t_argminmax(ctx, eqn, args):
    (a,) = args
    n = _reduced_count(a.shape, eqn.params["axes"])
    return [_out(eqn, 0, Interval(0, max(n - 1, 0)), True)]


@register("cumsum")
def _t_cumsum(ctx, eqn, args):
    (a,) = args
    n = a.shape[eqn.params["axis"]] if a.shape else 1
    lo = min(a.iv.lo, a.iv.lo * n)
    hi = max(a.iv.hi, a.iv.hi * n)
    return [_out(eqn, 0, Interval(lo, hi), a.integral)]


@register("cumprod")
def _t_cumprod(ctx, eqn, args):
    (a,) = args
    n = a.shape[eqn.params["axis"]] if a.shape else 1
    lo, hi = a.iv.lo, a.iv.hi
    if lo >= 0 and hi <= 1:
        iv = Interval(0 if lo < 1 else 1, hi)
    elif lo >= -1 and hi <= 1:
        m = max(abs(lo), abs(hi))
        iv = Interval(-m, m)
    else:
        m = max(abs(lo), abs(hi))
        try:
            big = m ** n if m not in (POS_INF,) else POS_INF
        except OverflowError:
            big = POS_INF
        iv = Interval(0 if lo >= 0 else -big, big)
    return [_out(eqn, 0, iv, a.integral)]


@register("dot_general")
def _t_dot_general(ctx, eqn, args):
    a, b = args
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    k = 1
    for d in lhs_c:
        k *= a.shape[d]
    k = max(k, 1)
    p = iv_mul(a.iv, b.iv)
    # sum of exactly k products, each in [p.lo, p.hi]
    return [_out(eqn, 0, Interval(p.lo * k, p.hi * k),
                 a.integral and b.integral)]


# --------------------------------------------------------------------------
# indexing (SW009)

_PROMISE = "PROMISE_IN_BOUNDS"


def _mode_name(mode) -> str:
    return getattr(mode, "name", str(mode) if mode is not None else "CLIP")


def _check_index_bounds(ctx, eqn, idx: AbsVal, allowed_hi: int, what: str):
    if idx.iv.is_bottom:
        return
    if idx.iv.lo < 0 or idx.iv.hi > allowed_hi:
        ctx.report(
            "SW009", eqn,
            f"{what}: index range {idx.iv} not provably within "
            f"[0, {allowed_hi}] — out-of-bounds access unproven at this "
            f"envelope",
        )


def _index_component_ivs(ctx, idx_atom, idx_val, n_comp):
    """Per-component intervals of a gather/scatter index array.

    jnp's advanced indexing stacks heterogeneous index vectors with a
    ``concatenate`` along the trailing (index-vector) dim; without this,
    the whole-array interval is the join of all components and a row
    index gets checked against the column bound."""
    import jax.extend.core as jcore

    atom = idx_atom
    d = None
    for _ in range(4):
        if isinstance(atom, jcore.Literal):
            break
        dd = ctx.defs.get(atom)
        if dd is None:
            break
        if dd.primitive.name in ("convert_element_type", "copy"):
            atom = dd.invars[0]
            continue
        d = dd
        break
    if (
        d is None
        or d.primitive.name != "concatenate"
        or d.params.get("dimension") != len(idx_val.shape) - 1
    ):
        return [idx_val.iv] * n_comp
    comps = []
    for piece in d.invars:
        pv = ctx.env_lookup(piece)
        if pv is None:
            return [idx_val.iv] * n_comp
        comps.extend([pv.iv] * piece.aval.shape[-1])
    if len(comps) != n_comp:
        return [idx_val.iv] * n_comp
    return comps


@register("gather")
def _t_gather(ctx, eqn, args):
    operand, idx = args
    dn = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    mode = _mode_name(eqn.params.get("mode"))
    n_comp = len(dn.start_index_map)
    if idx.shape and idx.shape[-1] == n_comp:
        comp_ivs = _index_component_ivs(ctx, eqn.invars[1], idx, n_comp)
    else:
        comp_ivs = [idx.iv] * n_comp
    in_bounds = True
    for j, d in enumerate(dn.start_index_map):
        a_hi = operand.shape[d] - slice_sizes[d]
        civ = comp_ivs[j]
        if civ.is_bottom or (civ.lo >= 0 and civ.hi <= a_hi):
            continue
        in_bounds = False
        if mode == _PROMISE:
            ctx.report(
                "SW009", eqn,
                f"gather(mode=promise_in_bounds): index range {civ} "
                f"(operand dim {d}) not provably within [0, {a_hi}] — "
                f"out-of-bounds access unproven at this envelope",
            )
    iv = operand.iv
    integral = operand.integral
    if mode == "FILL_OR_DROP" and not in_bounds:
        fv = eqn.params.get("fill_value")
        if fv is not None:
            iv = iv.join(Interval.point(
                int(fv) if is_int_dtype(operand.dtype) else float(fv)))
        else:
            lo, hi = dtype_range(operand.dtype)
            iv = iv.join(Interval(lo, hi))
    return [_out(eqn, 0, iv, integral)]


def _scatter_common(ctx, eqn, args, additive):
    operand, idx, upd = args
    dn = eqn.params["dimension_numbers"]
    mode = _mode_name(eqn.params.get("mode"))
    if mode == _PROMISE:
        dims = dn.scatter_dims_to_operand_dims
        if idx.shape and idx.shape[-1] == len(dims):
            comp_ivs = _index_component_ivs(ctx, eqn.invars[1], idx, len(dims))
        else:
            comp_ivs = [idx.iv] * len(dims)
        for j, d in enumerate(dims):
            a_hi = operand.shape[d] - 1
            civ = comp_ivs[j]
            if civ.is_bottom or (civ.lo >= 0 and civ.hi <= a_hi):
                continue
            ctx.report(
                "SW009", eqn,
                f"scatter(mode=promise_in_bounds): index range {civ} "
                f"(operand dim {d}) not provably within [0, {a_hi}] — "
                f"out-of-bounds access unproven at this envelope",
            )
    if additive:
        # worst case every update row lands on one slot
        n_upd = 1
        for i, d in enumerate(upd.shape):
            if i not in dn.update_window_dims:
                n_upd *= d
        if eqn.params.get("unique_indices"):
            n_upd = 1
        n_upd = max(n_upd, 1)
        delta = Interval(min(0, upd.iv.lo) * n_upd, max(0, upd.iv.hi) * n_upd)
        iv = iv_add(operand.iv, delta)
    else:
        iv = operand.iv.join(upd.iv)
    return [_out(eqn, 0, iv, operand.integral and upd.integral)]


@register("scatter")
def _t_scatter(ctx, eqn, args):
    return _scatter_common(ctx, eqn, args, additive=False)


@register("scatter-add")
def _t_scatter_add(ctx, eqn, args):
    return _scatter_common(ctx, eqn, args, additive=True)


@register("dynamic_slice")
def _t_dynamic_slice(ctx, eqn, args):
    operand, starts = args[0], args[1:]
    sizes = eqn.params["slice_sizes"]
    for i, s in enumerate(starts):
        allowed = operand.shape[i] - sizes[i]
        if not s.iv.is_bottom and (s.iv.lo < 0 or s.iv.hi > allowed):
            _check_index_bounds(
                ctx, eqn, s, allowed,
                f"dynamic_slice start (dim {i}, extent {operand.shape[i]}, "
                f"size {sizes[i]}; XLA clamps, so an unproven start reads a "
                f"silently shifted window)")
    return [_out(eqn, 0, operand.iv, operand.integral)]


@register("dynamic_update_slice")
def _t_dynamic_update_slice(ctx, eqn, args):
    operand, upd, starts = args[0], args[1], args[2:]
    for i, s in enumerate(starts):
        allowed = operand.shape[i] - upd.shape[i]
        if not s.iv.is_bottom and (s.iv.lo < 0 or s.iv.hi > allowed):
            _check_index_bounds(
                ctx, eqn, s, allowed,
                f"dynamic_update_slice start (dim {i}, extent "
                f"{operand.shape[i]}, update {upd.shape[i]}; XLA clamps, so "
                f"an unproven start writes a silently shifted window)")
    return [_out(eqn, 0, operand.iv.join(upd.iv),
                 operand.integral and upd.integral)]


# --------------------------------------------------------------------------
# mesh collectives


@register("psum", "psum_invariant")
def _t_psum(ctx, eqn, args):
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    n = 1
    for ax in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        if isinstance(ax, str):
            n *= ctx.axis_sizes.get(ax, 1)
        else:
            n *= int(ax)
    n = max(n, 1)
    outs = []
    for j, a in enumerate(args):
        outs.append(_out(eqn, j, Interval(a.iv.lo * n, a.iv.hi * n), a.integral))
    return outs


@register("axis_index")
def _t_axis_index(ctx, eqn, args):
    ax = eqn.params["axis_name"]
    n = ctx.axis_sizes.get(ax, 1)
    return [_out(eqn, 0, Interval(0, max(n - 1, 0)), True)]
