"""Jaxpr interpreter over the interval×dtype lattice.

:func:`interpret_jaxpr` walks a ``ClosedJaxpr`` produced by
``jax.make_jaxpr`` at envelope shapes and computes an :class:`AbsVal`
per variable, dispatching first-order primitives through
:mod:`.transfer` and sub-interpreting the higher-order ones itself:

``jit`` / ``closed_call`` / ``custom_jvp_call``
    straight sub-interpretation of the inner jaxpr.

``cond``
    join over the feasible branches; a constant-interval branch index
    prunes the rest (dead branches are not analyzed, so a guard like
    ``lax.cond(debug, ...)`` with a literal False never reports).

``while``
    join-to-fixpoint with **condition refinement**: when the cond jaxpr
    is a direct comparison between a carry component and a bound
    (``fori_loop`` lowers to exactly this), the component's interval is
    met with the branch condition at every body entry — that is the
    inductive bound for loop counters, so counter-indexed
    ``dynamic_slice`` starts are *proven* rather than widened away.
    Components still unstable after ``FIXPOINT_PASSES`` are widened
    per-endpoint to their dtype bound, then narrowed back through the
    refinement and re-verified by Park induction
    (``init ⊔ body(refine(C)) ⊆ C``).

``scan``
    the trip count is static, which buys more than ``while``: short
    loops (≤ ``UNROLL_LIMIT``) are unrolled exactly; longer ones run
    join-to-fixpoint, and carry components that keep growing (monotone
    counters — a round number bumped per event) get **length-aware
    extent extrapolation**: per-pass growth ``g`` is measured at the
    current carry, the candidate ``C = base ⊕ L·g`` is probed by
    re-running the body at ``C`` and accepting only if the growth there
    is no worse than ``g`` (translation-style steps; anything else
    falls back to the dtype bound).  This is how the audit proves
    ``rounds ≤ events ≪ 2**31`` instead of widening every counter to
    "might wrap".  A candidate escaping its dtype *is* the overflow
    proof and reports SW008 at the scan site.

``shard_map``
    sub-interpretation of the per-shard jaxpr with the mesh's axis
    sizes pushed into scope, so ``psum`` scales by the real axis extent
    and ``axis_index`` gets ``[0, axis-1]``.

Exploration passes (fixpoint/widening/probes) run *quiet*; once a loop
converges, one loud pass over the final abstract state emits findings.
Findings are deduplicated by (rule, site, primitive), so an unrolled
loop reports each offending site once.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from tpu_swirld.analysis.lint import Finding
from tpu_swirld.analysis.flow.lattice import (
    AbsVal,
    Interval,
    dtype_range,
    is_int_dtype,
)
from tpu_swirld.analysis.flow.transfer import (
    _FLIP,
    _refine_by_pred,
    HIGHER_ORDER,
    UnknownPrimitiveError,
    apply_transfer,
)

UNROLL_LIMIT = 64
FIXPOINT_PASSES = 12
SETTLE_PASSES = 4

RULE_NAMES = {
    "SW008": "overflow-reachable",
    "SW009": "unproven-bounds",
    "SW010": "lossy-narrowing",
    "SW011": "sentinel-collision",
}


def _src(eqn) -> Tuple[str, int]:
    """Best user-code (file, line) for an eqn from its source_info."""
    from jax._src import source_info_util

    frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    best = None
    for fr in frames:
        fn = getattr(fr, "file_name", "") or ""
        posix = fn.replace(os.sep, "/")
        if "tpu_swirld" in posix and "/analysis/" not in posix:
            best = fr
            break
    if best is None and frames:
        best = frames[0]
    if best is None:
        return "<jaxpr>", 0
    line = getattr(best, "start_line", None)
    if not line:
        line = getattr(best, "line_num", 0) or 0
    return best.file_name, int(line)


@dataclasses.dataclass
class FlowResult:
    outs: List[AbsVal]
    findings: List[Finding]
    exercised: set
    env_samples: Dict[str, AbsVal]


class _Analysis:
    """State shared across every (sub-)jaxpr walk of one interpretation."""

    def __init__(self, stage, sentinels, axis_sizes, findings, exercised):
        self.stage = stage
        self.sentinels = tuple(sentinels)
        self.axis_sizes = dict(axis_sizes or {})
        self.findings = findings if findings is not None else []
        self.exercised = exercised if exercised is not None else set()
        self.quiet = 0
        self._seen = set()

    def report(self, rule, eqn, msg):
        if self.quiet:
            return
        path, line = _src(eqn)
        key = (rule, path, line, eqn.primitive.name, msg.split(":")[0])
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(
            Finding(rule, RULE_NAMES.get(rule, rule), path, line, 0,
                    f"[{self.stage}] {msg}")
        )


class _Frame:
    """Per-jaxpr context handed to transfer functions."""

    def __init__(self, an: _Analysis):
        self.an = an
        self.env: Dict = {}
        self.defs: Dict = {}

    # --- interface used by transfer.py -----------------------------------
    @property
    def stage(self):
        return self.an.stage

    @property
    def sentinels(self):
        return self.an.sentinels

    @property
    def axis_sizes(self):
        return self.an.axis_sizes

    @property
    def exercised(self):
        return self.an.exercised

    def report(self, rule, eqn, msg):
        self.an.report(rule, eqn, msg)

    def where(self, eqn):
        path, line = _src(eqn)
        return f"{path}:{line}"

    def read(self, atom) -> AbsVal:
        import jax.extend.core as jcore

        if isinstance(atom, jcore.Literal):
            return _literal_absval(atom)
        return self.env[atom]

    def env_lookup(self, atom) -> Optional[AbsVal]:
        import jax.extend.core as jcore

        if isinstance(atom, jcore.Literal):
            return _literal_absval(atom)
        return self.env.get(atom)

    def const_interval(self, atom) -> Optional[Interval]:
        v = self.env_lookup(atom)
        return v.iv if v is not None else None


def _literal_absval(atom) -> AbsVal:
    """AbsVal for a jaxpr Literal, taking shape/dtype from the atom's
    aval (``np.asarray(0)`` would default a Python-int literal to int64
    and break joins against the jaxpr's declared int32)."""
    v = AbsVal.from_literal(atom.val)
    aval = atom.aval
    if hasattr(aval, "dtype"):
        v = dataclasses.replace(
            v, shape=tuple(aval.shape), dtype=np.dtype(aval.dtype))
    return v


def _bind_arg(invar, val: Optional[AbsVal]) -> AbsVal:
    aval = invar.aval
    if not hasattr(aval, "dtype"):
        return AbsVal((), np.dtype(np.int32), Interval(0, 0), True)
    if val is None:
        return AbsVal.from_aval(aval)
    return AbsVal.from_aval(aval, val.iv, val.integral).clamp_to_dtype()


def _eval_closed(an: _Analysis, closed, args: Sequence[AbsVal]):
    consts = []
    for c in closed.consts:
        try:
            consts.append(AbsVal.from_literal(np.asarray(c)))
        except Exception:
            consts.append(AbsVal((), np.dtype(np.int32), Interval(0, 0), True))
    return _eval_jaxpr(an, closed.jaxpr, consts, args)


def _eval_jaxpr(an: _Analysis, jaxpr, consts: Sequence[AbsVal],
                args: Sequence[AbsVal]):
    frame = _Frame(an)
    for v, c in zip(jaxpr.constvars, consts):
        frame.env[v] = c
    for v, a in zip(jaxpr.invars, args):
        frame.env[v] = _bind_arg(v, a)
    for eqn in jaxpr.eqns:
        in_vals = [frame.read(x) for x in eqn.invars]
        name = eqn.primitive.name
        if name in HIGHER_ORDER:
            outs = _eval_higher_order(an, frame, eqn, in_vals)
            an.exercised.add(name)
        else:
            outs = apply_transfer(frame, eqn, in_vals)
        for ov, o in zip(eqn.outvars, outs):
            frame.env[ov] = o
            frame.defs[ov] = eqn
    return [frame.read(x) for x in jaxpr.outvars], frame


# --------------------------------------------------------------------------
# higher-order primitives


def _remainder_summary(a: Interval, b: Interval) -> Optional[Interval]:
    """Closed-form interval of ``jnp.remainder(a, b)`` (floored mod) when
    the divisor interval has a definite sign; None when it spans zero."""
    if a.is_bottom or b.is_bottom:
        return None
    if b.lo > 0:
        if a.lo >= 0 and a.hi < b.lo:
            return a          # already reduced
        return Interval(0, b.hi - 1)
    if b.hi < 0:
        return Interval(b.lo + 1, 0)
    return None


def _eval_higher_order(an, frame, eqn, args):
    name = eqn.primitive.name
    if name in ("jit", "closed_call", "core_call"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        outs, _ = _eval_closed(an, inner, args)
        if (
            eqn.params.get("name") == "remainder"
            and len(args) == 2
            and len(outs) == 1
            and is_int_dtype(outs[0].dtype)
        ):
            # Known-function summary: jnp.remainder is floored mod (result
            # sign follows the divisor).  The sign-fix select inside uses a
            # compound predicate that defeats path refinement, so meet the
            # descended result with the closed form.
            s = _remainder_summary(args[0].iv, args[1].iv)
            if s is not None:
                outs[0] = dataclasses.replace(outs[0], iv=outs[0].iv.meet(s))
        return outs
    if name in ("custom_jvp_call", "custom_vjp_call"):
        inner = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
        outs, _ = _eval_closed(an, inner, args)
        return outs
    if name in ("remat", "checkpoint"):
        inner = eqn.params["jaxpr"]
        outs, _ = _eval_jaxpr(an, inner, [], args)
        return outs
    if name == "cond":
        return _eval_cond(an, eqn, args)
    if name == "while":
        return _eval_while(an, eqn, args)
    if name == "scan":
        return _eval_scan(an, eqn, args)
    if name == "shard_map":
        return _eval_shard_map(an, eqn, args)
    raise UnknownPrimitiveError(name, an.stage, frame.where(eqn))


def _eval_cond(an, eqn, args):
    branches = eqn.params["branches"]
    index, ops = args[0], args[1:]
    lo = 0 if index.iv.is_bottom else max(0, int(index.iv.lo))
    hi = len(branches) - 1 if index.iv.is_bottom else min(
        len(branches) - 1, int(index.iv.hi))
    if lo > hi:
        lo, hi = 0, len(branches) - 1
    outs = None
    for b in branches[lo:hi + 1]:
        b_outs, _ = _eval_closed(an, b, ops)
        if outs is None:
            outs = b_outs
        else:
            outs = [o.join(n) for o, n in zip(outs, b_outs)]
    return outs


def _cond_info(an, cond_closed, cc, carry):
    """Refine carry under "condition is True"; also return the
    ``(carry_index, op, bound_interval)`` constraints found, so the
    while handler can derive a trip-count bound for counters."""
    carry = list(carry)
    constraints = []
    an.quiet += 1
    try:
        try:
            _, fr = _eval_closed(an, cond_closed, list(cc) + carry)
        except UnknownPrimitiveError:
            return carry, constraints
    finally:
        an.quiet -= 1
    jx = cond_closed.jaxpr
    out = jx.outvars[0]
    prod = fr.defs.get(out)
    if prod is None or prod.primitive.name not in _FLIP:
        return carry, constraints
    lhs, rhs = prod.invars
    for var, bound, op in (
        (lhs, rhs, prod.primitive.name),
        (rhs, lhs, _FLIP[prod.primitive.name]),
    ):
        try:
            pos = jx.invars.index(var)
        except (ValueError, TypeError):
            continue
        ci = pos - len(cc)
        if ci < 0 or ci >= len(carry):
            continue
        b_iv = fr.const_interval(bound)
        if b_iv is None or b_iv.is_bottom:
            continue
        refined = _refine_by_pred(carry[ci].iv, op, b_iv, True)
        if not refined.is_bottom:
            carry[ci] = carry[ci].with_iv(refined)
        constraints.append((ci, op, b_iv))
    return carry, constraints


def _cond_refine(an, cond_closed, cc, carry):
    return _cond_info(an, cond_closed, cc, carry)[0]


def _widen_unstable(carry, prev):
    """Per-endpoint widening: any endpoint still moving goes to its
    dtype bound; the stable endpoint is kept."""
    out = []
    for c, p in zip(carry, prev):
        lo_d, hi_d = dtype_range(c.dtype)
        lo = c.iv.lo if c.iv.lo == p.iv.lo else lo_d
        hi = c.iv.hi if c.iv.hi == p.iv.hi else hi_d
        out.append(c.with_iv(Interval(lo, hi)))
    return out


def _literal_step(jx, out_atom, in_var):
    """Constant k when the body computes ``out = in_var + k`` at top
    level (the fori_loop counter pattern); None otherwise."""
    import jax.extend.core as jcore

    if isinstance(out_atom, jcore.Literal):
        return None
    prod = None
    for e in jx.eqns:
        if out_atom in e.outvars:
            prod = e
    if prod is None or prod.primitive.name != "add":
        return None
    a, b = prod.invars
    for x, y in ((a, b), (b, a)):
        if x is in_var and isinstance(y, jcore.Literal):
            try:
                return int(np.asarray(y.val))
            except Exception:
                return None
    return None


def _while_trip_bound(body_closed, nbc, constraints, init):
    """Trip-count bound for a while loop whose condition is
    ``counter < bound`` and whose body bumps the counter by a literal
    ``k >= 1`` — the only pattern where interval data gives a *sound*
    bound (a conditionally-advancing counter would not)."""
    from tpu_swirld.analysis.flow.lattice import NEG_INF, POS_INF

    jx = body_closed.jaxpr
    for ci, op, b_iv in constraints:
        if op not in ("lt", "le") or b_iv.hi == POS_INF:
            continue
        if init[ci].iv.is_bottom or init[ci].iv.lo in (NEG_INF, POS_INF):
            continue
        step = _literal_step(jx, jx.outvars[ci], jx.invars[nbc + ci])
        if step is None or step < 1:
            continue
        span = b_iv.hi - init[ci].iv.lo + (1 if op == "le" else 0)
        return max(0, -(-int(span) // step))
    return None


def _eval_while(an, eqn, args):
    ncc = eqn.params["cond_nconsts"]
    nbc = eqn.params["body_nconsts"]
    cond_jaxpr = eqn.params["cond_jaxpr"]
    body_jaxpr = eqn.params["body_jaxpr"]
    cc = args[:ncc]
    bc = args[ncc:ncc + nbc]
    init = list(args[ncc + nbc:])
    carry = list(init)
    an.quiet += 1
    try:
        prev = carry
        stable = False
        constraints = []
        for _ in range(FIXPOINT_PASSES):
            entry, constraints = _cond_info(an, cond_jaxpr, cc, carry)
            outs, _ = _eval_closed(an, body_jaxpr, list(bc) + entry)
            new = [c.join(o) for c, o in zip(carry, outs)]
            if all(c.covers(n) for c, n in zip(carry, new)):
                stable = True
                break
            prev, carry = carry, new
        if not stable:
            # a ``counter < bound`` condition on a strictly-growing carry
            # component bounds the trip count — extent-extrapolate the
            # other movers like a fixed-length scan.
            trip = _while_trip_bound(body_jaxpr, nbc, constraints, init)
            if trip is not None:
                def run(c):
                    e = _cond_refine(an, cond_jaxpr, cc, c)
                    outs, _ = _eval_closed(an, body_jaxpr, list(bc) + e)
                    return outs, ()

                carry = _extrapolate_scan(
                    an, eqn, run, init, carry, prev, trip)
                stable = True
        if not stable:
            wide = _widen_unstable(carry, prev)
            # narrow back through the refinement; verify by Park induction
            entry = _cond_refine(an, cond_jaxpr, cc, wide)
            outs, _ = _eval_closed(an, body_jaxpr, list(bc) + entry)
            cand = [i.join(e).join(o) for i, e, o in zip(init, entry, outs)]
            ok = False
            for _ in range(SETTLE_PASSES):
                entry = _cond_refine(an, cond_jaxpr, cc, cand)
                outs, _ = _eval_closed(an, body_jaxpr, list(bc) + entry)
                nxt = [i.join(e).join(o)
                       for i, e, o in zip(init, entry, outs)]
                if all(c.covers(n) for c, n in zip(cand, nxt)):
                    ok = True
                    break
                cand = [c.join(n) for c, n in zip(cand, nxt)]
            carry = cand if ok else wide
    finally:
        an.quiet -= 1
    # loud pass over the converged state (cond + body findings)
    entry = _cond_refine(an, cond_jaxpr, cc, carry)
    _eval_closed(an, cond_jaxpr, list(cc) + carry)
    outs, _ = _eval_closed(an, body_jaxpr, list(bc) + entry)
    return [c.join(o) for c, o in zip(carry, outs)]


def _eval_scan(an, eqn, args):
    p = eqn.params
    body = p["jaxpr"]
    length = int(p["length"])
    n_consts = p["num_consts"]
    n_carry = p["num_carry"]
    consts = args[:n_consts]
    init = list(args[n_consts:n_consts + n_carry])
    xs = args[n_consts + n_carry:]
    x_slices = [AbsVal(x.shape[1:] if x.shape else (), x.dtype, x.iv,
                       x.integral) for x in xs]

    def run(carry):
        outs, _ = _eval_closed(an, body, list(consts) + list(carry)
                               + list(x_slices))
        return outs[:n_carry], outs[n_carry:]

    if length <= UNROLL_LIMIT:
        carry = init
        ys = None
        for _ in range(max(length, 1)):
            carry, y = run(carry)
            ys = y if ys is None else [a.join(b) for a, b in zip(ys, y)]
        return _scan_outs(eqn, n_carry, carry, ys)

    an.quiet += 1
    try:
        carry, prev = list(init), list(init)
        stable = False
        for _ in range(FIXPOINT_PASSES):
            outs, _ = run(carry)
            new = [c.join(o) for c, o in zip(carry, outs)]
            if all(c.covers(n) for c, n in zip(carry, new)):
                stable = True
                break
            prev, carry = carry, new
        if not stable:
            carry = _extrapolate_scan(an, eqn, run, init, carry, prev, length)
    finally:
        an.quiet -= 1
    outs, ys = run(carry)  # loud final pass
    carry = [c.join(o) for c, o in zip(carry, outs)]
    return _scan_outs(eqn, n_carry, carry, ys)


def _scan_outs(eqn, n_carry, carry, ys):
    out_vals = list(carry)
    for j, y in enumerate(ys or []):
        ov = eqn.outvars[n_carry + j]
        out_vals.append(AbsVal.from_aval(ov.aval, y.iv, y.integral))
    return out_vals


def _extrapolate_scan(an, eqn, run, init, carry, prev, length):
    """Length-aware extent extrapolation for monotone scan carries.

    Growth per pass ``g`` is measured between the last two joined
    carries; the candidate ``C = carry ⊕ length·g`` is accepted for a
    component only if re-running the body *at C* grows no faster than
    ``g`` (translation-style step).  A candidate past the dtype range is
    a proven overflow: SW008 at the scan site, then clamp.  Components
    that fail the probe widen to their dtype bound.
    """
    grow = []
    for c, pr in zip(carry, prev):
        g_lo = min(0, c.iv.lo - pr.iv.lo)
        g_hi = max(0, c.iv.hi - pr.iv.hi)
        grow.append((g_lo, g_hi))
    # The body of iteration k sees the carry *input*, i.e. at most
    # init + (length-1)·g for a translation-style step — basing the
    # candidate on the fixpoint-observed carry would overshoot by the
    # passes already run (a counter would read [0, length+passes] and
    # fail its own in-bounds gather at exactly the envelope extent).
    ext = max(length - 1, 0)
    cand = []
    for i, (c, (g_lo, g_hi)) in enumerate(zip(carry, grow)):
        if g_lo == 0 and g_hi == 0:
            cand.append(c)
            continue
        ini = init[i]
        base = ini if not ini.iv.is_bottom else c
        cand.append(c.join(c.with_iv(Interval(base.iv.lo + ext * g_lo,
                                              base.iv.hi + ext * g_hi))))
    probe, _ = run(cand)
    final = []
    frozen = []
    for i, (c, cd, (g_lo, g_hi), pb) in enumerate(
            zip(carry, cand, grow, probe)):
        if g_lo == 0 and g_hi == 0:
            # stable component: keep, folding in any probe drift
            final.append(c if c.covers(pb) else c.join(pb))
            frozen.append(False)
            continue
        ok = (pb.iv.lo >= cd.iv.lo + g_lo - abs(g_lo)
              and pb.iv.hi <= cd.iv.hi + g_hi + abs(g_hi))
        v = cd if ok else cd.top_like()
        if is_int_dtype(v.dtype):
            lo_d, hi_d = dtype_range(v.dtype)
            if v.iv.lo < lo_d or v.iv.hi > hi_d:
                an.report(
                    "SW008", eqn,
                    f"scan: carry component {i} grows ~[{g_lo}, {g_hi}] per "
                    f"step over {length} steps, reaching {v.iv} — outside "
                    f"{np.dtype(v.dtype).name} range [{lo_d}, {hi_d}]",
                )
                v = v.clamp_to_dtype()
                ok = False
        final.append(v)
        # A translation-verified component's in-body *input* never exceeds
        # init + (length-1)·g; joining its own +g output back in while
        # settling the others would inflate a loop counter past the trip
        # count (and fail in-bounds gathers at exactly the extent).
        frozen.append(ok)
    # settle the rest against the extrapolated components
    carry = final
    new = carry
    for _ in range(SETTLE_PASSES):
        outs, _ = run(carry)
        # re-verify frozen components against the (possibly widened)
        # rest; a faster-growing step voids the translation argument
        for i, (g_lo, g_hi) in enumerate(grow):
            if frozen[i] and not (
                outs[i].iv.lo >= carry[i].iv.lo + g_lo - abs(g_lo)
                and outs[i].iv.hi <= carry[i].iv.hi + g_hi + abs(g_hi)
            ):
                frozen[i] = False
        new = [c if fz else c.join(o)
               for c, o, fz in zip(carry, outs, frozen)]
        if all(fz or c.covers(n)
               for c, n, fz in zip(carry, new, frozen)):
            return new
        carry = new
    # still moving: dtype-bound the movers and finish
    return [c if fz else (c.top_like() if not c.covers(n) else c)
            for c, n, fz in zip(carry, new, frozen)]


def _eval_shard_map(an, eqn, args):
    mesh = eqn.params.get("mesh")
    inner = eqn.params.get("jaxpr")
    saved = dict(an.axis_sizes)
    try:
        if mesh is not None:
            # caller-declared axis sizes (the envelope's mesh_devices) win
            # over the traced mesh — the audit traces shard_map under
            # whatever mesh the host has (often 1 CPU device) while
            # proving the envelope's device count.
            try:
                for k, v in dict(mesh.shape).items():
                    an.axis_sizes.setdefault(str(k), int(v))
            except Exception:
                pass
        if hasattr(inner, "jaxpr"):  # ClosedJaxpr
            outs, _ = _eval_closed(an, inner, args)
        else:
            outs, _ = _eval_jaxpr(an, inner, [], args)
        # shard_map outvars carry the *global* shape; rebuild on out avals
        return [AbsVal.from_aval(ov.aval, o.iv, o.integral)
                for ov, o in zip(eqn.outvars, outs)]
    finally:
        an.axis_sizes = saved


# --------------------------------------------------------------------------
# entry point


def interpret_jaxpr(
    closed,
    arg_vals: Optional[Sequence] = None,
    *,
    stage: str = "<fn>",
    sentinels: Sequence[int] = (),
    axis_sizes: Optional[Dict[str, int]] = None,
    findings: Optional[List[Finding]] = None,
    exercised: Optional[set] = None,
) -> FlowResult:
    """Interpret a ``ClosedJaxpr`` abstractly.

    ``arg_vals`` aligns with the jaxpr invars; each entry is an
    :class:`AbsVal`, an :class:`Interval`, a ``(lo, hi)`` tuple, or
    ``None`` (= full dtype range).  Returns the abstract outputs plus
    all findings and the set of primitive names exercised.
    """
    an = _Analysis(stage, sentinels, axis_sizes, findings, exercised)
    invars = closed.jaxpr.invars
    vals: List[Optional[AbsVal]] = []
    for i, v in enumerate(invars):
        raw = arg_vals[i] if arg_vals is not None and i < len(arg_vals) else None
        if raw is None:
            vals.append(None)
        elif isinstance(raw, AbsVal):
            vals.append(raw)
        elif isinstance(raw, Interval):
            vals.append(AbsVal.from_aval(v.aval, raw))
        else:
            lo, hi = raw
            vals.append(AbsVal.from_aval(v.aval, Interval(lo, hi)))
    outs, frame = _eval_closed(an, closed, vals)
    samples = {}
    return FlowResult(outs=outs, findings=an.findings,
                      exercised=an.exercised, env_samples=samples)
