"""Interval analysis over limb-arithmetic jaxprs: ``jaxpr-limb-overflow``.

The limb format (ops/limbs.py) does exact multi-precision integer
arithmetic in f32 digits; every op contract is a *digit-magnitude bound*
(strict < 2^8, products < 2^16, anti-diagonal sums < 2^22, everything
< 2^24 = the largest range where f32 represents every integer exactly).
A bound violation does not crash — it silently rounds, and the fused
pairing kernels can only hit it at scale (a 256-lane batch on real
hardware), long after tier-1 passed.  BENCH_r05's Mosaic splice bug and
the round-3 Kogge-Stone miscompile were both caught by *structural*
jaxpr rules; this rule closes the remaining class: arithmetic whose
*values* leave the exactly-representable range.

The auditor abstract-interprets a traced jaxpr over the interval domain
[lo, hi] (one interval per array — digit bounds are uniform across the
limb axis by construction):

- elementwise arithmetic, shape ops, reductions, ``dot_general``,
  scatter/gather and ``select_n`` propagate intervals directly;
- ``dot_general`` against a KNOWN CONSTANT operand (the MXU limb-multiply
  mapping: one-hot REP/TIL/ACC contractions, the RED fold matrix) is
  bounded per output column from the constant's actual positive/negative
  column sums — max_c(hi * P_c - lo * N_c) — instead of the generic
  interval-product times contraction-size rule, which over-approximates a
  one-hot contraction by the full contraction width (2500x for the flat
  outer product) and would falsely flag the MXU path;
- ``scan``/``while`` bodies run to an inductive fixpoint (the carry
  interval is widened to TOP if it fails to stabilize, so the analysis
  always terminates and never *under*-approximates);
- the ``d - floor(d * 2^-8) * 2^8`` split idiom (``limbs._split``, the
  heart of every carry) is pattern-matched so the modulo's [0, 255]
  range survives — naive interval subtraction would lose the correlation
  between ``d`` and its own floor and the carry chain would never
  converge;
- unknown primitives go to TOP: the rule only reports *proven*
  may-overflows (a finite interval exceeding the dtype bound), never
  "I could not prove safety" — plus a coverage ratio so the tests can
  assert the core entries are FULLY proven, not just unflagged.

``audit_limb_overflow()`` runs the registry of ops/limbs.py entries at
their documented input contracts (strict digits, the fp_sub loose
bounds, the carry_exact 2^24 ceiling) and returns ``Violation``s whose
path/line point at the offending *source line* via the jaxpr's
source_info — which is how the known-bad fixture fires exactly on its
``# VIOLATION`` marks.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .report import Violation

RULE = "jaxpr-limb-overflow"

INF = math.inf
TOP = (-INF, INF)

# largest integer ranges represented exactly per float dtype
_EXACT_BOUNDS = {
    "float32": float(1 << 24),
    "float64": float(1 << 53),
    "bfloat16": 256.0,
    "float16": 2048.0,
}

_SCAN_FIXPOINT_ITERS = 12


def _union(a, b):
    return (min(a[0], b[0]), max(a[1], b[1]))


def _is_finite(iv) -> bool:
    return math.isfinite(iv[0]) and math.isfinite(iv[1])


@dataclass
class Finding:
    file: str
    line: int
    prim: str
    lo: float
    hi: float
    bound: float
    entry: str = ""


@dataclass
class LimbReport:
    findings: List[Finding]
    float_outputs: int
    bounded_outputs: int

    @property
    def coverage(self) -> float:
        if not self.float_outputs:
            return 1.0
        return self.bounded_outputs / self.float_outputs


class _Analyzer:
    # constants above this size are not retained for the const-aware
    # dot_general rule (memory bound; far above the 2500x99 MXU one-hots)
    _CONST_VAL_MAX_SIZE = 1 << 22

    def __init__(self):
        self.findings: List[Finding] = []
        self.float_outputs = 0
        self.bounded_outputs = 0
        self._flagged_lines: set = set()
        # constvar -> actual numpy array, for const-aware dot bounds
        self._const_vals: Dict = {}

    # -- source mapping ---------------------------------------------------
    @staticmethod
    def _eqn_site(eqn) -> Tuple[str, int]:
        from .jaxpr_audit import _eqn_site

        return _eqn_site(eqn)

    # -- env --------------------------------------------------------------
    @staticmethod
    def _read(env, defs, v):
        from jax._src import core as jcore

        if isinstance(v, jcore.Literal):
            import numpy as np

            arr = np.asarray(v.val)
            if arr.size == 0:
                return (0.0, 0.0)
            return (float(arr.min()), float(arr.max()))
        return env.get(v, TOP)

    def _record(self, eqn, outvals, env, defs):
        for var, iv in zip(eqn.outvars, outvals):
            env[var] = iv
            defs[var] = eqn
            dtype = getattr(getattr(var, "aval", None), "dtype", None)
            bound = _EXACT_BOUNDS.get(str(dtype)) if dtype is not None else None
            if bound is None:
                continue
            self.float_outputs += 1
            if _is_finite(iv):
                self.bounded_outputs += 1
                if iv[1] > bound or iv[0] < -bound:
                    fname, line = self._eqn_site(eqn)
                    key = (fname, line)
                    if key not in self._flagged_lines:
                        self._flagged_lines.add(key)
                        self.findings.append(Finding(
                            file=fname, line=line,
                            prim=eqn.primitive.name,
                            lo=iv[0], hi=iv[1], bound=bound,
                        ))

    # -- the split idiom --------------------------------------------------
    @staticmethod
    def _const_of(env, defs, v) -> Optional[float]:
        from jax._src import core as jcore

        if isinstance(v, jcore.Literal):
            import numpy as np

            arr = np.asarray(v.val)
            if arr.size and float(arr.min()) == float(arr.max()):
                return float(arr.min())
        iv = env.get(v)
        if iv is not None and iv[0] == iv[1]:
            return iv[0]
        return None

    def _match_mod_split(self, eqn, env, defs):
        """sub(x, mul(floor(mul(x, c)), c')) with c*c' ~= 1 and x in
        [0, exact-bound] is exactly ``x mod c'`` -> [0, c' - 1]."""
        from jax._src import core as jcore

        x, y = eqn.invars
        if isinstance(y, jcore.Literal) or isinstance(x, jcore.Literal):
            return None
        mul_out = defs.get(y)
        if mul_out is None or mul_out.primitive.name != "mul":
            return None
        floor_v, c2 = None, None
        for a, b in (mul_out.invars, reversed(mul_out.invars)):
            cv = self._const_of(env, defs, b)
            if cv is not None and not isinstance(a, jcore.Literal):
                floor_v, c2 = a, cv
                break
        if floor_v is None:
            return None
        floor_eqn = defs.get(floor_v)
        if floor_eqn is None or floor_eqn.primitive.name != "floor":
            return None
        inner = defs.get(floor_eqn.invars[0])
        if inner is None or inner.primitive.name != "mul":
            return None
        c1, matches_x = None, False
        for a, b in (inner.invars, reversed(inner.invars)):
            cv = self._const_of(env, defs, b)
            if cv is not None and a is x:
                c1, matches_x = cv, True
                break
        if not matches_x or c1 is None or c2 <= 0:
            return None
        if abs(c1 * c2 - 1.0) > 1e-9:
            return None
        xiv = self._read(env, defs, x)
        dtype = str(getattr(getattr(x, "aval", None), "dtype", ""))
        bound = _EXACT_BOUNDS.get(dtype, float(1 << 24))
        if xiv[0] < 0 or xiv[1] > bound:
            return None
        return (0.0, c2 - 1.0)

    # -- jaxpr walk -------------------------------------------------------
    def run(self, jaxpr, consts, in_intervals) -> List[Tuple[float, float]]:
        import numpy as np

        env: Dict = {}
        defs: Dict = {}
        for var, c in zip(jaxpr.constvars, consts):
            try:
                arr = np.asarray(c)
                env[var] = (float(arr.min()), float(arr.max())) if arr.size \
                    else (0.0, 0.0)
                if 0 < arr.size <= self._CONST_VAL_MAX_SIZE:
                    self._const_vals[var] = arr
            except Exception:
                env[var] = TOP
        for var, iv in zip(jaxpr.invars, in_intervals):
            env[var] = tuple(iv)
        for eqn in jaxpr.eqns:
            outvals = self._eval_eqn(eqn, env, defs)
            self._record(eqn, outvals, env, defs)
            self._fwd_const(eqn)
        return [self._read(env, defs, v) for v in jaxpr.outvars]

    def _fwd_const(self, eqn):
        """Keep the const-aware dot rule's view of a constant alive across
        value-preserving plumbing (jnp.asarray of a host constant traces as
        device_put; casts and layout moves likewise)."""
        import numpy as np

        if len(eqn.outvars) != 1 or not eqn.invars:
            return
        name = eqn.primitive.name
        arr = self._const_arr(eqn.invars[0])
        if arr is None:
            return
        try:
            if name in ("device_put", "copy", "stop_gradient"):
                self._const_vals[eqn.outvars[0]] = arr
            elif name == "convert_element_type":
                # bound the CONVERTED values (a narrowing cast may round)
                self._const_vals[eqn.outvars[0]] = np.asarray(arr).astype(
                    eqn.params["new_dtype"]
                )
            elif name == "transpose":
                self._const_vals[eqn.outvars[0]] = np.transpose(
                    arr, eqn.params.get("permutation")
                )
            elif name == "reshape":
                self._const_vals[eqn.outvars[0]] = np.reshape(
                    arr, eqn.params["new_sizes"]
                )
        except Exception:
            pass

    def _seed_consts(self, analyzer, outer_atoms, inner_vars):
        """Forward statically-known arrays across a call/control-flow
        boundary (pjit consts are lifted into invars; scan/cond/while pass
        their closure constants positionally)."""
        for outer, inner in zip(outer_atoms, inner_vars):
            arr = self._const_arr(outer)
            if arr is not None:
                analyzer._const_vals[inner] = arr

    def _subjaxpr(self, closed, in_ivs):
        return self.run(closed.jaxpr, closed.consts, in_ivs)

    def _eval_eqn(self, eqn, env, defs) -> List[Tuple[float, float]]:
        name = eqn.primitive.name
        ins = [self._read(env, defs, v) for v in eqn.invars]
        n_out = len(eqn.outvars)

        def mulspan(a, b):
            cands = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
            cands = [c if not math.isnan(c) else 0.0 for c in cands]
            return (min(cands), max(cands))

        if name == "add" or name == "add_any":
            return [(ins[0][0] + ins[1][0], ins[0][1] + ins[1][1])]
        if name == "sub":
            m = self._match_mod_split(eqn, env, defs)
            if m is not None:
                return [m]
            return [(ins[0][0] - ins[1][1], ins[0][1] - ins[1][0])]
        if name == "mul":
            return [mulspan(ins[0], ins[1])]
        if name == "div":
            lo, hi = ins[1]
            if lo > 0 or hi < 0:
                inv = (1.0 / hi, 1.0 / lo)
                return [mulspan(ins[0], inv)]
            return [TOP]
        if name == "neg":
            return [(-ins[0][1], -ins[0][0])]
        if name == "abs":
            lo, hi = ins[0]
            alo = 0.0 if lo <= 0 <= hi else min(abs(lo), abs(hi))
            return [(alo, max(abs(lo), abs(hi)))]
        if name == "sign":
            return [(-1.0, 1.0)]
        if name == "floor":
            return [(math.floor(ins[0][0]) if math.isfinite(ins[0][0]) else -INF,
                     math.floor(ins[0][1]) if math.isfinite(ins[0][1]) else INF)]
        if name in ("ceil", "round", "round_nearest_even"):
            lo, hi = ins[0]
            return [(lo - 1 if math.isfinite(lo) else -INF,
                     hi + 1 if math.isfinite(hi) else INF)]
        if name == "max":
            return [(max(ins[0][0], ins[1][0]), max(ins[0][1], ins[1][1]))]
        if name == "min":
            return [(min(ins[0][0], ins[1][0]), min(ins[0][1], ins[1][1]))]
        if name == "clamp":
            lo = max(ins[0][0], min(ins[1][0], ins[0][1]))
            hi = min(ins[2][1], max(ins[1][1], ins[2][0]))
            return [(min(lo, hi), max(lo, hi))]
        if name == "integer_pow":
            p = eqn.params.get("y", 1)
            cands = [ins[0][0] ** p, ins[0][1] ** p]
            if ins[0][0] <= 0 <= ins[0][1]:
                cands.append(0.0)
            return [(min(cands), max(cands))]
        if name in ("square",):
            return [self._eval_pow2(ins[0])]
        if name == "sqrt":
            lo, hi = ins[0]
            return [(math.sqrt(max(lo, 0.0)),
                     math.sqrt(hi) if math.isfinite(hi) and hi >= 0 else INF)]
        if name in (
            "reshape", "squeeze", "expand_dims", "broadcast_in_dim",
            "transpose", "rev", "copy", "stop_gradient", "slice",
            "dynamic_slice", "gather", "device_put",
        ):
            return [ins[0]] * n_out
        if name == "convert_element_type":
            return [ins[0]]
        if name == "concatenate":
            out = ins[0]
            for iv in ins[1:]:
                out = _union(out, iv)
            return [out]
        if name == "pad":
            return [_union(ins[0], ins[1])]
        if name in ("dynamic_update_slice",):
            return [_union(ins[0], ins[1])]
        if name in ("scatter", "scatter-update"):
            return [_union(ins[0], ins[-1])]
        if name in ("scatter-add", "scatter_add"):
            op, upd = ins[0], ins[-1]
            return [(op[0] + min(0.0, upd[0]), op[1] + max(0.0, upd[1]))]
        if name == "select_n":
            out = ins[1]
            for iv in ins[2:]:
                out = _union(out, iv)
            return [out]
        if name in ("eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not",
                    "xor", "is_finite", "reduce_and", "reduce_or"):
            return [(0.0, 1.0)] * n_out
        if name == "iota":
            size = 1
            try:
                shape = eqn.params.get("shape") or ()
                dim = eqn.params.get("dimension", 0)
                size = shape[dim] if shape else 1
            except Exception:
                pass
            return [(0.0, float(max(size - 1, 0)))]
        if name in ("reduce_sum", "cumsum"):
            k = self._reduced_size(eqn)
            lo, hi = ins[0]
            return [(min(lo * k, 0.0) if lo < 0 else lo,
                     hi * k if hi > 0 else max(hi * k, hi))]
        if name in ("reduce_max", "cummax", "reduce_min", "cummin"):
            return [ins[0]]
        if name == "reduce_prod":
            return [TOP]
        if name == "dot_general":
            return [self._dot_interval(eqn, ins)]
        if name in ("jit", "closed_call", "core_call", "remat",
                    "remat_call", "custom_jvp_call", "custom_vjp_call",
                    "custom_jvp_call_jaxpr", "checkpoint"):
            closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if closed is None:
                return [TOP] * n_out
            if hasattr(closed, "jaxpr"):
                self._seed_consts(self, eqn.invars, closed.jaxpr.invars)
                return self._subjaxpr(closed, ins)
            self._seed_consts(self, eqn.invars, closed.invars)
            return self.run(closed, [], ins)
        if name == "cond":
            branches = eqn.params.get("branches") or ()
            outs = None
            for br in branches:
                self._seed_consts(self, eqn.invars[1:], br.jaxpr.invars)
                o = self._subjaxpr(br, ins[1:])
                outs = o if outs is None else [
                    _union(a, b) for a, b in zip(outs, o)
                ]
            return outs if outs is not None else [TOP] * n_out
        if name == "scan":
            return self._eval_scan(eqn, ins)
        if name == "while":
            return self._eval_while(eqn, ins)
        return [TOP] * n_out

    @staticmethod
    def _eval_pow2(iv):
        cands = [iv[0] * iv[0], iv[1] * iv[1]]
        lo = 0.0 if iv[0] <= 0 <= iv[1] else min(cands)
        return (lo, max(cands))

    @staticmethod
    def _eval_mul_for_dot(a, b):
        cands = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
        cands = [c if not math.isnan(c) else 0.0 for c in cands]
        return (min(cands), max(cands))

    def _const_arr(self, v):
        """The actual array behind a jaxpr atom, if statically known."""
        from jax._src import core as jcore

        if isinstance(v, jcore.Literal):
            import numpy as np

            try:
                arr = np.asarray(v.val)
                return arr if 0 < arr.size <= self._CONST_VAL_MAX_SIZE else None
            except Exception:
                return None
        return self._const_vals.get(v)

    def _dot_interval(self, eqn, ins):
        """dot_general bounds.

        When one operand is a known constant W (the MXU mapping's one-hot
        REP/TIL/ACC and placement matrices, the RED fold rows), each output
        column c is sum_j W[j, c] * x_j with x_j in [lo, hi], so the exact
        interval hull is
            [ min_c(lo * P_c - hi * N_c),  max_c(hi * P_c - lo * N_c) ]
        with P_c / N_c the positive/negative parts of W summed over the
        contracted axes.  For a one-hot column this is just [lo, hi] —
        whereas the generic fallback (interval product x contraction size)
        multiplies by the full contraction width and cannot prove the MXU
        path.  Fallback keeps the old sound over-approximation when
        neither operand is statically known.
        """
        import numpy as np

        dn = eqn.params.get("dimension_numbers")
        if dn is not None:
            (lcd, rcd), (lbd, rbd) = dn
            for cidx, vidx, caxes in ((1, 0, tuple(rcd)), (0, 1, tuple(lcd))):
                arr = self._const_arr(eqn.invars[cidx])
                if arr is None:
                    continue
                lo, hi = ins[vidx]
                if not (math.isfinite(lo) and math.isfinite(hi)):
                    break  # unknown operand range: no better than fallback
                w = np.asarray(arr, dtype=np.float64)
                pos = np.maximum(w, 0.0)
                neg = np.maximum(-w, 0.0)
                if caxes:
                    pos = pos.sum(axis=caxes)
                    neg = neg.sum(axis=caxes)
                out_lo = float(np.min(lo * pos - hi * neg)) if pos.size else 0.0
                out_hi = float(np.max(hi * pos - lo * neg)) if pos.size else 0.0
                return (min(out_lo, out_hi), max(out_lo, out_hi))
        k = self._contract_size(eqn)
        span = self._eval_mul_for_dot(ins[0], ins[1])
        return (span[0] * k if span[0] < 0 else span[0],
                span[1] * k if span[1] > 0 else span[1])

    @staticmethod
    def _reduced_size(eqn) -> int:
        try:
            shape = eqn.invars[0].aval.shape
            axes = eqn.params.get("axes")
            if axes is None:  # cumsum: params axis
                axis = eqn.params.get("axis")
                return int(shape[axis]) if axis is not None else 1
            k = 1
            for ax in axes:
                k *= int(shape[ax])
            return max(k, 1)
        except Exception:
            return 1

    @staticmethod
    def _contract_size(eqn) -> int:
        try:
            ((lc, _rc), _batch) = eqn.params["dimension_numbers"]
            shape = eqn.invars[0].aval.shape
            k = 1
            for ax in lc:
                k *= int(shape[ax])
            return max(k, 1)
        except Exception:
            return 1

    def _eval_scan(self, eqn, ins):
        closed = eqn.params["jaxpr"]
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        length = eqn.params.get("length", 1) or 1
        consts = ins[:n_consts]
        carry = list(ins[n_consts:n_consts + n_carry])
        xs = ins[n_consts + n_carry:]
        ys_acc: Optional[List[Tuple[float, float]]] = None
        # fixpoint on the carry: silent sub-analyzer (findings only from
        # the final stabilized pass, so lines are not double-reported and
        # pre-widening transients don't fire)
        for _ in range(_SCAN_FIXPOINT_ITERS):
            sub = _Analyzer()
            self._seed_consts(sub, eqn.invars[:n_consts], closed.jaxpr.invars)
            outs = sub.run(closed.jaxpr, closed.consts, consts + carry + xs)
            new_carry = [
                _union(c, o) for c, o in zip(carry, outs[:n_carry])
            ]
            if new_carry == carry:
                break
            carry = new_carry
        else:
            carry = [TOP] * n_carry
        self._seed_consts(self, eqn.invars[:n_consts], closed.jaxpr.invars)
        final = self._subjaxpr(closed, consts + carry + xs)
        carry_out = [_union(c, o) for c, o in zip(carry, final[:n_carry])]
        ys = final[n_carry:]
        if ys_acc is None:
            ys_acc = ys
        return carry_out + ys_acc

    def _eval_while(self, eqn, ins):
        closed = eqn.params["body_jaxpr"]
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _ in range(_SCAN_FIXPOINT_ITERS):
            sub = _Analyzer()
            self._seed_consts(
                sub, eqn.invars[cn:cn + bn], closed.jaxpr.invars
            )
            outs = sub.run(closed.jaxpr, closed.consts, consts + carry)
            new_carry = [_union(c, o) for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        else:
            carry = [TOP] * len(carry)
        self._seed_consts(self, eqn.invars[cn:cn + bn], closed.jaxpr.invars)
        final = self._subjaxpr(closed, consts + carry)
        return [_union(c, o) for c, o in zip(carry, final)]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def analyze_callable(
    fn: Callable,
    in_shapes: Sequence[Tuple[int, ...]],
    in_intervals: Sequence[Tuple[float, float]],
    dtype=None,
) -> LimbReport:
    """Trace ``fn`` abstractly (make_jaxpr — no backend compile, compile-
    guard-safe) and interval-analyze the result."""
    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float32
    args = [jax.ShapeDtypeStruct(s, dtype) for s in in_shapes]
    closed = jax.make_jaxpr(fn)(*args)
    a = _Analyzer()
    a.run(closed.jaxpr, closed.consts, list(in_intervals))
    return LimbReport(
        findings=a.findings,
        float_outputs=a.float_outputs,
        bounded_outputs=a.bounded_outputs,
    )


@dataclass
class LimbEntry:
    name: str
    fn: Callable
    in_shapes: Sequence[Tuple[int, ...]]
    in_intervals: Sequence[Tuple[float, float]]
    # the documented contract the intervals encode, for the report
    contract: str = ""


def limb_entries() -> List[LimbEntry]:
    """The ops/limbs.py arithmetic core at its documented input
    contracts.  Strict digits are <= 2^8 (carry_exact's fixed point is
    256, not 255 — see its docstring), loose inputs go to the 2^24
    f32-exact ceiling."""
    from lodestar_tpu.ops import limbs as fl

    N = fl.NLIMBS
    STRICT = (0.0, 256.0)
    LOOSE = (0.0, float((1 << fl.LOOSE_BITS) - 1))
    SUB_A = (0.0, float((1 << 23) - 1))
    SUB_B = (0.0, float((1 << 12) - 1))
    return [
        LimbEntry("fp_strict", fl.fp_strict, [(N,)], [LOOSE],
                  contract="loose digits < 2^24 -> strict"),
        LimbEntry("fp_add", fl.fp_add, [(N,), (N,)], [STRICT, STRICT],
                  contract="lazy digitwise sum of two strict elements"),
        LimbEntry("fp_sub", fl.fp_sub, [(N,), (N,)], [SUB_A, SUB_B],
                  contract="a digits < 2^23, b digits < 2^12"),
        LimbEntry("fp_mul", lambda a, b: fl.fp_mul(a, b),
                  [(N,), (N,)], [STRICT, STRICT],
                  contract="strict x strict schoolbook (env-selected mode)"),
        # every LODESTAR_TPU_LIMB_MUL mode is proven individually — the
        # env default must never be the only path with a digit proof
        LimbEntry("fp_mul@ladder", lambda a, b: fl.fp_mul(a, b, mode="ladder"),
                  [(N,), (N,)], [STRICT, STRICT],
                  contract="strict x strict, VPU pad+add ladder"),
        LimbEntry("fp_mul@mxu", lambda a, b: fl.fp_mul(a, b, mode="mxu"),
                  [(N,), (N,)], [STRICT, STRICT],
                  contract="strict x strict, one-hot MXU contraction"),
        LimbEntry("fp_mul@mxu9", lambda a, b: fl.fp_mul(a, b, mode="mxu9"),
                  [(N,), (N,)], [STRICT, STRICT],
                  contract="strict x strict, 9-bit re-packed contraction"),
        LimbEntry("pack9", fl._pack9, [(N,)], [STRICT],
                  contract="strict 8-bit digits -> 45 x 9-bit digits"),
        LimbEntry("carry_base512",
                  lambda x: fl._carry_base(x, fl.LOOSE_BITS, fl.PACK9_BITS),
                  [(2 * fl.PACK9_NLIMBS - 1,)],
                  [(0.0, float(fl.PACK9_NLIMBS * (1 << 18)))],
                  contract="base-512 carry at the mxu9 product bound"),
        LimbEntry("fp_sqr", lambda a: fl.fp_sqr(a), [(N,)], [STRICT],
                  contract="strict square"),
        LimbEntry("fp_mul_small", lambda a: fl.fp_mul_small(a, (1 << 14) - 1),
                  [(N,)], [STRICT],
                  contract="strict x largest small multiplier"),
        LimbEntry("carry_exact", lambda x: fl.carry_exact(x), [(N,)], [LOOSE],
                  contract="loose -> semi-strict fold ladder"),
        LimbEntry("fp_reduce_full", fl.fp_reduce_full, [(N,)], [STRICT],
                  contract="semi-strict -> canonical (scan ripple + Barrett)"),
    ]


def audit_limb_overflow(
    entries: Optional[Sequence[LimbEntry]] = None,
    repo: Optional[str] = None,
) -> List[Violation]:
    """The jaxpr-limb-overflow rule over the limb entry registry."""
    if repo is None:
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    if entries is None:
        entries = limb_entries()
    out: List[Violation] = []
    for entry in entries:
        report = analyze_callable(entry.fn, entry.in_shapes, entry.in_intervals)
        for f in report.findings:
            path = f.file
            if path.startswith(repo + os.sep):
                path = os.path.relpath(path, repo)
            out.append(Violation(
                rule=RULE,
                path=path or entry.name,
                line=f.line,
                message=(
                    f"{entry.name}: `{f.prim}` result proven to reach "
                    f"[{f.lo:.4g}, {f.hi:.4g}] under the entry's input "
                    f"contract ({entry.contract}) — exceeds the "
                    f"exactly-representable +/-{f.bound:.4g}; f32 limb "
                    "arithmetic silently rounds past this bound "
                    "(docs/static_analysis.md#jaxpr-limb-overflow)"
                ),
            ))
    return out
