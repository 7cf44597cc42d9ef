"""Pallas kernel verifier — the fifth static-analysis layer.

Walks every ``pallas_call`` primitive in traced entry jaxprs and audits
the kernel body for the four failure classes ROADMAP item 3 (remote-DMA
sharded pairing v2) will live or die by:

* ``pallas-dma-unbalanced`` — every ``make_async_copy`` /
  ``make_async_remote_copy`` start has a matching wait on the same
  semaphore (slot) along every control path; no wait-without-start; no
  semaphore count leaked across grid steps / loop iterations.
* ``pallas-ref-race`` — read-after-write / write-after-write /
  write-after-read on overlapping Ref slices while a DMA touching them
  is still in flight (no intervening wait) — the double-buffer
  slot-aliasing bug class.  Two in-flight DMAs sharing one semaphore
  slot are flagged directly.
* ``pallas-ring-neighbor`` — remote device ids derived from
  ``axis_index`` must be congruent mod the axis size and never
  self-send.
* ``pallas-block-misaligned`` — gridded block shapes must divide the
  operand shape on every split dim, split trailing dims must meet the
  per-dtype (sublane, 128) Mosaic tile rules (the BENCH_r05 rc=124
  class, caught here before a TPU ever sees the kernel), and
  memory-space sanity: DMA semaphore slots must be semaphore-space
  refs, semaphore refs must never be used as data.

Everything is decided from the jaxpr alone — no TPU, no interpreter
run.  The extraction distills each ``pallas_call`` into a JSON-native
record (blocks, refs, a nested region tree of DMA/access events with
slice indices evaluated per ``axis_index`` value) so the rules replay
from the jaxpr_audit artifact cache exactly like the layer-4 rules:
records ride ``.jax_cache/jaxpr_audit_artifacts.json`` under the same
ops-content fingerprint (``_CACHE_VERSION`` v4 folds this module's
source in), keyed ``pallas:<entry>`` for the kernel-library entries
below and embedded as the ``"pallas"`` artifact field for the layer-4
entry points (so the fused dispatch graphs are swept for free).

Slice arithmetic: index expressions inside kernels are evaluated by a
tiny abstract interpreter over scalar ints, tracking one value PER
axis_index (a length-n vector when the kernel sits under shard_map over
an n-way mesh).  Remote-DMA incoming writes are modelled SPMD-
symmetrically: the write landing on shard r is the one the sender s
with device_id(s) == r issued, so its destination slice is the sender's
expression evaluated at s.  Anything the interpreter cannot evaluate
degrades to "?" — treated as overlapping-everything (conservative), a
non-issue for the live tree whose only DMA kernel (ops/pallas_ring.py)
evaluates exactly.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Optional, Tuple

from .report import Violation

RULE_DMA = "pallas-dma-unbalanced"
RULE_RACE = "pallas-ref-race"
RULE_RING = "pallas-ring-neighbor"
RULE_TILE = "pallas-block-misaligned"

# mesh width the ring-combine entry traces at (>= 2 devices required;
# gated on jaxpr_audit.sharded_audit_available())
PALLAS_AUDIT_MESH = 2

# Mosaic vreg second-minor (sublane) tile per dtype; the minor (lane)
# tile is 128 for every dtype
_SUBLANE = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16, "int16": 16, "uint16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}
_LANE = 128

_DMA_PRIMS = frozenset({
    "dma_start", "dma_wait", "semaphore_signal", "semaphore_wait",
    "get_barrier_semaphore",
})

# scalar-int primitives the mini-interpreter evaluates (per axis_index)
_EVAL_PRIMS = frozenset({
    "add", "sub", "mul", "rem", "div", "neg", "max", "min",
    "convert_element_type", "broadcast_in_dim", "squeeze", "reshape",
    "stop_gradient", "axis_index",
})


def _site(eqn) -> List:
    from . import jaxpr_audit as ja

    f, ln = ja._eqn_site(eqn)
    return [f, ln]


# ---------------------------------------------------------------------------
# mini-interpreter values: int (uniform) | [int]*n (per axis_index) | "?"
# ---------------------------------------------------------------------------


def _lift(v, n):
    if isinstance(v, int) and n:
        return [v] * n
    return v


def _binop(op, a, b, n):
    if a == "?" or b == "?":
        return "?"
    if isinstance(a, int) and isinstance(b, int):
        return op(a, b)
    a, b = _lift(a, n), _lift(b, n)
    if not (isinstance(a, list) and isinstance(b, list) and len(a) == len(b)):
        return "?"
    return [op(x, y) for x, y in zip(a, b)]


def _trunc_rem(a, b):
    # lax.rem is the TRUNCATED remainder (sign of the dividend) — the
    # reason kernels must bias (x - k + n) % n positive before the rem
    if b == 0:
        return 0
    r = abs(a) % abs(b)
    return r if a >= 0 else -r


def _trunc_div(a, b):
    if b == 0:
        return 0
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


_BINOPS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "rem": _trunc_rem,
    "div": _trunc_div,
    "max": max,
    "min": min,
}


class _KernelExtractor:
    """One pallas_call kernel body -> JSON-native record."""

    def __init__(self, axis_sizes: Dict[str, int]):
        self.axis_sizes = dict(axis_sizes)
        # the per-axis_index vector model only makes sense for a single
        # mapped axis — the live mesh (and item 3's plan) is 1-D
        self.n: Optional[int] = (
            next(iter(self.axis_sizes.values()))
            if len(self.axis_sizes) == 1 else None
        )
        self.refs: Dict[str, dict] = {}
        self._fresh = 0

    # -- env plumbing ------------------------------------------------------

    def _reg_ref(self, var, origin: str) -> str:
        rid = f"r{self._fresh}"
        self._fresh += 1
        av = getattr(var, "aval", None)
        dt = getattr(av, "dtype", None)
        dt_name = str(getattr(dt, "name", "") or dt or "")
        space = str(getattr(av, "memory_space", None) or "")
        self.refs[rid] = {
            "shape": [int(d) for d in getattr(av, "shape", ())],
            "dtype": dt_name,
            "space": space,
            "sem": "sem" in dt_name or "sem" in space,
            "origin": origin,
        }
        return rid

    def _is_ref(self, var) -> bool:
        av = getattr(var, "aval", None)
        return "Ref" in type(av).__name__ if av is not None else False

    def _val_of(self, x, env):
        """Value of an invar/leaf: Literal, raw int, or env lookup."""
        if x is None:
            return None
        if isinstance(x, int):
            return x
        if hasattr(x, "val") and not hasattr(x, "aval"):
            try:
                return int(x.val)
            except (TypeError, ValueError):
                return "?"
        if type(x).__name__ == "Literal":
            try:
                return int(x.val)
            except (TypeError, ValueError):
                return "?"
        try:
            got = env.get(x, "?") if not isinstance(x, (list, tuple)) else "?"
        except TypeError:  # unhashable leaf (array-valued Literal)
            return "?"
        if isinstance(got, tuple) and got and got[0] == "ref":
            return "?"
        return got

    def _map_env(self, sub_invars, operands, env):
        sub = {}
        for i, v in enumerate(sub_invars):
            if i < len(operands):
                op = operands[i]
                try:
                    known = env.get(op)
                except TypeError:
                    known = None
                if known is not None:
                    sub[v] = known
                else:
                    sub[v] = self._val_of(op, env)
            elif self._is_ref(v):
                sub[v] = ("ref", self._reg_ref(v, "scoped"))
            else:
                sub[v] = "?"
        return sub

    # -- slice decoding ----------------------------------------------------

    def _decode_transforms(self, transforms, shape, env) -> List[dict]:
        """tuple of NDIndexer -> [{"start": v, "size": s}, ...] ("?" on
        anything beyond one plain strided indexer)."""
        full = [{"start": 0, "size": int(d)} for d in shape]
        try:
            if not transforms:
                return full
            if len(transforms) != 1:
                return [{"start": "?", "size": 1} for _ in shape]
            idx = transforms[0]
            out = []
            for d, el in enumerate(getattr(idx, "indices", ())):
                if isinstance(el, int):
                    out.append({"start": el, "size": 1})
                elif hasattr(el, "start") and hasattr(el, "size"):
                    if getattr(el, "stride", 1) not in (1, None):
                        out.append({"start": "?", "size": 1})
                        continue
                    out.append({
                        "start": self._val_of(el.start, env),
                        "size": int(el.size),
                    })
                else:
                    out.append({"start": self._val_of(el, env), "size": 1})
            return out or full
        except Exception:
            return [{"start": "?", "size": 1} for _ in shape]

    def _ref_slices(self, var, transforms, env) -> Optional[dict]:
        if var is None:
            return None
        tag = env.get(var)
        rid = tag[1] if isinstance(tag, tuple) and tag[0] == "ref" else "?"
        shape = getattr(getattr(var, "aval", None), "shape", ())
        return {"ref": rid,
                "slices": self._decode_transforms(transforms, shape, env)}

    # -- events ------------------------------------------------------------

    def _dma_event(self, pname, eqn, env) -> dict:
        try:
            import jax

            args = jax.tree_util.tree_unflatten(eqn.params["tree"], eqn.invars)
            (src, src_t, dst, dst_t, dst_sem, dst_sem_t,
             src_sem, src_sem_t, dev) = args
            dev_v = None if dev is None else self._val_of(dev, env)
            return {
                "op": pname, "site": _site(eqn),
                "src": self._ref_slices(src, src_t, env),
                "dst": self._ref_slices(dst, dst_t, env),
                "dst_sem": self._ref_slices(dst_sem, dst_sem_t, env),
                "src_sem": self._ref_slices(src_sem, src_sem_t, env),
                "device_id": dev_v,
            }
        except Exception:
            return {"op": pname, "site": _site(eqn), "src": None, "dst": None,
                    "dst_sem": None, "src_sem": None, "device_id": "?"}

    def _access_event(self, pname, eqn, env) -> dict:
        ref_var = eqn.invars[0] if eqn.invars else None
        try:
            import jax

            tree = eqn.params.get("tree")
            transforms = ()
            if tree is not None:
                flat = eqn.invars[1:] if pname == "get" else eqn.invars[2:]
                transforms = jax.tree_util.tree_unflatten(tree, flat)
        except Exception:
            transforms = None  # forces "?" slices below
        target = (self._ref_slices(ref_var, transforms, env)
                  if transforms is not None else
                  {"ref": "?", "slices": [{"start": "?", "size": 1}]})
        return {"op": pname, "site": _site(eqn), "target": target}

    def _sem_event(self, pname, eqn, env) -> dict:
        ref_var = eqn.invars[0] if eqn.invars else None
        tag = env.get(ref_var)
        rid = tag[1] if isinstance(tag, tuple) and tag[0] == "ref" else "?"
        return {"op": pname, "site": _site(eqn), "ref": rid}

    # -- region walk -------------------------------------------------------

    def _eval(self, eqn, env) -> None:
        p = eqn.primitive.name
        outv = eqn.outvars[0] if eqn.outvars else None
        if outv is None:
            return
        if getattr(getattr(outv, "aval", None), "shape", None) not in ((), None):
            env[outv] = "?"
            return
        if p == "axis_index":
            name = eqn.params.get("axis_name")
            if isinstance(name, (tuple, list)):
                name = name[0] if len(name) == 1 else None
            if self.n is not None and (
                name is None or str(name) in self.axis_sizes
            ):
                env[outv] = list(range(self.n))
            else:
                env[outv] = "?"
            return
        vals = [self._val_of(v, env) for v in eqn.invars]
        if p in _BINOPS and len(vals) == 2:
            env[outv] = _binop(_BINOPS[p], vals[0], vals[1], self.n)
        elif p == "neg" and vals:
            env[outv] = _binop(_BINOPS["sub"], 0, vals[0], self.n)
        elif vals:  # convert/broadcast/squeeze/reshape on a scalar
            env[outv] = vals[0]
        else:
            env[outv] = "?"

    def region(self, jaxpr, env) -> List:
        events: List = []
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            if p in ("dma_start", "dma_wait"):
                events.append(self._dma_event(p, eqn, env))
            elif p in ("get", "swap"):
                events.append(self._access_event(p, eqn, env))
            elif p in ("semaphore_signal", "semaphore_wait"):
                events.append(self._sem_event(p, eqn, env))
            elif p == "get_barrier_semaphore":
                if eqn.outvars:
                    env[eqn.outvars[0]] = ("ref", self._reg_ref(
                        eqn.outvars[0], "barrier"))
            elif p == "scan":
                body = eqn.params["jaxpr"]
                sub = self._map_env(body.jaxpr.invars, eqn.invars, env)
                ev = self.region(body.jaxpr, sub)
                if ev:
                    events.append({"op": "loop", "site": _site(eqn),
                                   "body": ev})
            elif p == "while":
                cn = eqn.params.get("cond_nconsts", 0)
                body = eqn.params["body_jaxpr"]
                sub = self._map_env(body.jaxpr.invars, eqn.invars[cn:], env)
                ev = self.region(body.jaxpr, sub)
                cond = eqn.params.get("cond_jaxpr")
                if cond is not None:
                    cond_ops = (list(eqn.invars[:cn])
                                + list(eqn.invars[cn + eqn.params.get(
                                    "body_nconsts", 0):]))
                    ev += self.region(
                        cond.jaxpr,
                        self._map_env(cond.jaxpr.invars, cond_ops, env))
                if ev:
                    events.append({"op": "loop", "site": _site(eqn),
                                   "body": ev})
            elif p == "cond":
                branches = []
                for br in eqn.params.get("branches", ()):
                    sub = self._map_env(br.jaxpr.invars, eqn.invars[1:], env)
                    branches.append(self.region(br.jaxpr, sub))
                if any(branches):
                    events.append({"op": "cond", "site": _site(eqn),
                                   "branches": branches})
            elif p in _EVAL_PRIMS:
                self._eval(eqn, env)
            else:
                inlined = False
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    sub_j = eqn.params.get(key)
                    if sub_j is None:
                        continue
                    inner = getattr(sub_j, "jaxpr", sub_j)
                    if not hasattr(inner, "eqns"):
                        continue
                    sub = self._map_env(inner.invars, eqn.invars, env)
                    for cv, c in zip(inner.constvars,
                                     getattr(sub_j, "consts", ())):
                        try:
                            sub[cv] = int(c) if getattr(
                                c, "shape", None) == () else "?"
                        except (TypeError, ValueError):
                            sub[cv] = "?"
                    events.extend(self.region(inner, sub))
                    inlined = True
                    break
                if not inlined and eqn.outvars:
                    for v in eqn.outvars:
                        env[v] = "?"
        return events


def _subtree_has_dma(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _DMA_PRIMS:
            return True
        for v in eqn.params.values():
            cands = v if isinstance(v, (list, tuple)) else (v,)
            for c in cands:
                inner = c if hasattr(c, "eqns") else getattr(c, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns") and \
                        _subtree_has_dma(inner):
                    return True
    return False


def _block_dim(b) -> int:
    """One block-shape entry as an int: a plain int, a ``Blocked`` /
    ``Element`` wrapper (its ``block_size``), or None / ``Squeezed`` (1)."""
    size = getattr(b, "block_size", b)
    return size if isinstance(size, int) else 1


def _pallas_record(eqn, axis_sizes: Dict[str, int]) -> dict:
    gm = eqn.params.get("grid_mapping")
    kj = eqn.params.get("jaxpr")
    name = str(eqn.params.get("name_and_src_info", "") or "pallas_call")
    name = name.split(" at ")[0]
    grid: List = []
    blocks: List = []
    if gm is not None:
        try:
            grid = [int(g) for g in gm.grid]
        except (TypeError, ValueError):
            grid = [str(g) for g in gm.grid]
        for bm in getattr(gm, "block_mappings", ()):
            try:
                sds = bm.array_aval
                blocks.append({
                    "block": [_block_dim(b) for b in bm.block_shape],
                    "array": [int(d) for d in sds.shape],
                    "dtype": str(getattr(sds.dtype, "name", sds.dtype)),
                    "space": str(getattr(bm.transformed_block_aval,
                                         "memory_space", None) or ""),
                    "origin": str(getattr(bm, "origin", "")),
                })
            except Exception:
                pass
    ex = _KernelExtractor(axis_sizes)
    env: Dict = {}
    for v in getattr(kj, "invars", ()):
        if ex._is_ref(v):
            env[v] = ("ref", ex._reg_ref(v, "operand"))
        else:
            env[v] = "?"
    events: List = []
    if kj is not None and _subtree_has_dma(kj):
        try:
            events = ex.region(kj, env)
        except Exception:
            events = []
    return {
        "name": name,
        "site": _site(eqn),
        "grid": grid,
        "axis_size": ex.n,
        "blocks": blocks,
        "refs": ex.refs,
        "events": events,
    }


def _walk(jaxpr, axis_sizes: Dict[str, int], out: List) -> None:
    for eqn in jaxpr.eqns:
        pname = eqn.primitive.name
        if pname == "pallas_call":
            out.append(_pallas_record(eqn, axis_sizes))
            continue
        sizes = axis_sizes
        if pname == "shard_map":
            mesh = eqn.params.get("mesh")
            try:
                sizes = dict(axis_sizes)
                sizes.update({str(k): int(v)
                              for k, v in dict(mesh.shape).items()})
            except Exception:
                sizes = axis_sizes
        for v in eqn.params.values():
            cands = v if isinstance(v, (list, tuple)) else (v,)
            for c in cands:
                inner = c if hasattr(c, "eqns") else getattr(c, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk(inner, sizes, out)


def extract_pallas_records(closed_jaxpr) -> List[dict]:
    """Every pallas_call in a traced graph -> JSON-native audit records
    (canonicalized through JSON so cold and cache-loaded copies compare
    equal, matching the layer-4 artifact contract)."""
    out: List = []
    _walk(closed_jaxpr.jaxpr, {}, out)
    return json.loads(json.dumps(out))


# ---------------------------------------------------------------------------
# slice / overlap helpers shared by the rules
# ---------------------------------------------------------------------------


def _slot_key(slices) -> str:
    return json.dumps(slices, sort_keys=True)


def _dim_overlap(a: dict, b: dict, n: Optional[int]) -> bool:
    sa, sb = a.get("start"), b.get("start")
    za, zb = a.get("size", 1), b.get("size", 1)
    if sa == "?" or sb == "?":
        return True
    la = _lift(sa, n) if isinstance(sa, int) else sa
    lb = _lift(sb, n) if isinstance(sb, int) else sb
    if isinstance(la, int) and isinstance(lb, int):
        la, lb = [la], [lb]
    if not (isinstance(la, list) and isinstance(lb, list)):
        return True
    if len(la) != len(lb):
        return True
    return any(x < y + zb and y < x + za for x, y in zip(la, lb))


def _slices_overlap(a: Optional[dict], b: Optional[dict],
                    n: Optional[int]) -> bool:
    """Do two {"ref", "slices"} access descriptors overlap on any device?"""
    if a is None or b is None:
        return False
    if a["ref"] != b["ref"] or a["ref"] == "?":
        return a["ref"] == "?" and b["ref"] == "?"
    xs, ys = a["slices"], b["slices"]
    if len(xs) != len(ys):
        return True
    return all(_dim_overlap(x, y, n) for x, y in zip(xs, ys))


def _incoming(dst: Optional[dict], dev, n: Optional[int]) -> Optional[dict]:
    """The SPMD-symmetric incoming remote write: shard r receives the
    write whose slice expression the sender s (device_id(s) == r)
    evaluated at s.  Unknown / non-bijective mappings degrade to "?"."""
    if dst is None:
        return None
    if n is None or dev in (None, "?"):
        return {"ref": dst["ref"],
                "slices": [{"start": "?", "size": s.get("size", 1)}
                           for s in dst["slices"]]}
    dv = _lift(dev, n) if isinstance(dev, int) else dev
    perm: Dict[int, int] = {}
    ok = isinstance(dv, list) and len(dv) == n
    if ok:
        for s, tgt in enumerate(dv):
            if not isinstance(tgt, int) or not 0 <= tgt < n or tgt in perm:
                ok = False
                break
            perm[tgt] = s
    out_slices = []
    for sl in dst["slices"]:
        st = sl.get("start")
        if not ok or st == "?":
            out_slices.append({"start": "?", "size": sl.get("size", 1)})
            continue
        vec = _lift(st, n) if isinstance(st, int) else st
        if not (isinstance(vec, list) and len(vec) == n):
            out_slices.append({"start": "?", "size": sl.get("size", 1)})
            continue
        out_slices.append({"start": [vec[perm[r]] for r in range(n)],
                           "size": sl.get("size", 1)})
    return {"ref": dst["ref"], "slices": out_slices}


def _where(rec: dict, ev: Optional[dict], fallback: str) -> Tuple[str, int]:
    site = (ev or rec).get("site") or ["", 0]
    if site[0]:
        return site[0], int(site[1])
    rsite = rec.get("site") or ["", 0]
    return (rsite[0] or fallback), int(rsite[1])


# ---------------------------------------------------------------------------
# rule (a): DMA/semaphore balance
# ---------------------------------------------------------------------------


def _sem_ledger(events, ledger: Dict[str, dict], viol: List[Violation],
                rec: dict, fallback: str) -> None:
    for ev in events:
        op = ev.get("op")
        if op == "dma_start":
            for part in ("dst_sem", "src_sem"):
                s = ev.get(part)
                if s is None:
                    continue
                k = f"{s['ref']}|{_slot_key(s['slices'])}"
                e = ledger.setdefault(
                    k, {"net": 0, "start": None, "wait": None})
                e["net"] += 1
                e["start"] = e["start"] or ev.get("site")
        elif op == "dma_wait":
            s = ev.get("dst_sem")
            if s is None:
                continue
            k = f"{s['ref']}|{_slot_key(s['slices'])}"
            e = ledger.setdefault(k, {"net": 0, "start": None, "wait": None})
            e["net"] -= 1
            e["wait"] = e["wait"] or ev.get("site")
        elif op == "loop":
            sub: Dict[str, dict] = {}
            _sem_ledger(ev["body"], sub, viol, rec, fallback)
            for k, e in sub.items():
                if e["net"] != 0:
                    f, ln = _where(rec, {"site": e["start"] or e["wait"]
                                         or ev.get("site")}, fallback)
                    viol.append(Violation(
                        RULE_DMA, f, ln,
                        f"DMA semaphore {k.split('|')[0]} nets "
                        f"{e['net']:+d} per loop iteration in kernel "
                        f"'{rec['name']}' — counts leak across iterations "
                        f"(and across grid steps)"))
        elif op == "cond":
            nets = []
            for br in ev["branches"]:
                sub = {}
                _sem_ledger(br, sub, viol, rec, fallback)
                nets.append({k: e["net"] for k, e in sub.items()
                             if e["net"] != 0})
                for k, e in sub.items():
                    ledger.setdefault(
                        k, {"net": 0, "start": None, "wait": None})
                    ledger[k]["start"] = ledger[k]["start"] or e["start"]
                    ledger[k]["wait"] = ledger[k]["wait"] or e["wait"]
            if any(nz != nets[0] for nz in nets[1:]):
                f, ln = _where(rec, ev, fallback)
                viol.append(Violation(
                    RULE_DMA, f, ln,
                    f"DMA semaphore balance differs between cond branches "
                    f"in kernel '{rec['name']}' — some control path leaves "
                    f"a start without its wait"))
            elif nets and nets[0]:
                for k, d in nets[0].items():
                    ledger.setdefault(
                        k, {"net": 0, "start": None, "wait": None})
                    ledger[k]["net"] += d


def _check_dma_balance(rec: dict, fallback: str) -> List[Violation]:
    viol: List[Violation] = []
    ledger: Dict[str, dict] = {}
    _sem_ledger(rec.get("events", ()), ledger, viol, rec, fallback)
    # collapse per-slot entries into one per-ref bucket when any slot on
    # that ref failed to decode ("?" starts) — avoids phantom imbalance
    # from a start and its wait landing in different keys
    unknown = {k.split("|")[0] for k in ledger if '"?"' in k}
    merged: Dict[str, dict] = {}
    for k, e in ledger.items():
        rid = k.split("|")[0]
        mk = rid if rid in unknown else k
        m = merged.setdefault(mk, {"net": 0, "start": None, "wait": None})
        m["net"] += e["net"]
        m["start"] = m["start"] or e["start"]
        m["wait"] = m["wait"] or e["wait"]
    for k, e in merged.items():
        if e["net"] > 0:
            f, ln = _where(rec, {"site": e["start"]}, fallback)
            viol.append(Violation(
                RULE_DMA, f, ln,
                f"{e['net']} DMA start(s) on semaphore {k.split('|')[0]} "
                f"without a matching wait in kernel '{rec['name']}' — the "
                f"semaphore count leaks across grid steps"))
        elif e["net"] < 0:
            f, ln = _where(rec, {"site": e["wait"]}, fallback)
            viol.append(Violation(
                RULE_DMA, f, ln,
                f"{-e['net']} DMA wait(s) on semaphore {k.split('|')[0]} "
                f"with no matching start in kernel '{rec['name']}' — "
                f"deadlocks at the first grid step"))
    return viol


# ---------------------------------------------------------------------------
# rule (b): ref races / double-buffer slot aliasing
# ---------------------------------------------------------------------------


def _race_replay(events, state: List[dict], rec: dict, fallback: str,
                 seen, viol: List[Violation]) -> List[dict]:
    n = rec.get("axis_size")

    def emit(ev, msg):
        f, ln = _where(rec, ev, fallback)
        key = (RULE_RACE, f, ln, msg[:40])
        if key not in seen:
            seen.add(key)
            viol.append(Violation(RULE_RACE, f, ln, msg))

    def check_access(ev, acc, is_write, what):
        if acc is None:
            return
        for rec_if in state:
            for w in rec_if["writes"]:
                if _slices_overlap(acc, w, n):
                    emit(ev, f"{what} of ref {acc['ref']} slice overlaps an "
                             f"in-flight DMA write with no intervening "
                             f"semaphore wait in kernel '{rec['name']}' "
                             f"(double-buffer slot reuse hazard)")
                    return
            if is_write:
                for r in rec_if["reads"]:
                    if _slices_overlap(acc, r, n):
                        emit(ev, f"write to ref {acc['ref']} slice still "
                                 f"being read by an in-flight DMA in kernel "
                                 f"'{rec['name']}'")
                        return

    for ev in events:
        op = ev.get("op")
        if op == "dma_start":
            for part in ("dst_sem", "src_sem"):
                s = ev.get(part)
                if s is None:
                    continue
                for rec_if in state:
                    sp = rec_if["sem"]
                    if sp and s["ref"] == sp["ref"] and s["ref"] != "?" and \
                            _slices_overlap(s, sp, n):
                        emit(ev, f"DMA started on semaphore {s['ref']} slot "
                                 f"already guarding an in-flight transfer "
                                 f"in kernel '{rec['name']}' — slot "
                                 f"aliasing, waits become ambiguous")
            src, dst = ev.get("src"), ev.get("dst")
            remote = ev.get("device_id") is not None
            check_access(ev, src, False, "DMA source read")
            wr = _incoming(dst, ev.get("device_id"), n) if remote else dst
            check_access(ev, wr, True, "DMA destination write")
            if remote:
                state.append({"sem": ev.get("src_sem"),
                              "reads": [src] if src else [], "writes": []})
                state.append({"sem": ev.get("dst_sem"), "reads": [],
                              "writes": [wr] if wr else []})
            else:
                state.append({"sem": ev.get("dst_sem"),
                              "reads": [src] if src else [],
                              "writes": [dst] if dst else []})
        elif op == "dma_wait":
            s = ev.get("dst_sem")
            if s is None:
                state.clear()
            else:
                state[:] = [r for r in state
                            if not (r["sem"] and r["sem"]["ref"] == s["ref"]
                                    and _slices_overlap(r["sem"], s, n))]
        elif op == "semaphore_wait":
            state.clear()  # generous: any explicit wait orders everything
        elif op == "get":
            check_access(ev, ev.get("target"), False, "read")
        elif op == "swap":
            check_access(ev, ev.get("target"), True, "write")
        elif op == "loop":
            # second pass catches hazards that only appear once iteration
            # k+1's accesses meet iteration k's still-in-flight DMAs
            state = _race_replay(ev["body"], state, rec, fallback, seen, viol)
            state = _race_replay(ev["body"], state, rec, fallback, seen, viol)
        elif op == "cond":
            outs: List[dict] = []
            for br in ev["branches"]:
                outs.extend(_race_replay(list(br), list(state), rec,
                                         fallback, seen, viol))
            state = outs
    return state


def _check_ref_races(rec: dict, fallback: str) -> List[Violation]:
    viol: List[Violation] = []
    _race_replay(rec.get("events", ()), [], rec, fallback, set(), viol)
    return viol


# ---------------------------------------------------------------------------
# rule (c): ring neighbor topology
# ---------------------------------------------------------------------------


def _ring_events(events):
    for ev in events:
        op = ev.get("op")
        if op == "dma_start":
            yield ev
        elif op == "loop":
            yield from _ring_events(ev["body"])
        elif op == "cond":
            for br in ev["branches"]:
                yield from _ring_events(br)


def _check_ring(rec: dict, fallback: str) -> List[Violation]:
    n = rec.get("axis_size")
    out: List[Violation] = []
    for ev in _ring_events(rec.get("events", ())):
        dev = ev.get("device_id")
        if dev is None or dev == "?" or n is None:
            continue
        vec = _lift(dev, n) if isinstance(dev, int) else dev
        if not (isinstance(vec, list) and len(vec) == n):
            continue
        bad_range = [(i, d) for i, d in enumerate(vec)
                     if not (isinstance(d, int) and 0 <= d < n)]
        self_send = [i for i, d in enumerate(vec) if d == i]
        f, ln = _where(rec, ev, fallback)
        if bad_range:
            i, d = bad_range[0]
            out.append(Violation(
                RULE_RING, f, ln,
                f"remote DMA device_id not congruent mod the axis size in "
                f"kernel '{rec['name']}': axis_index {i} targets device "
                f"{d} outside [0, {n}) — wrap with rem(x + {n}, {n})"))
        if self_send:
            out.append(Violation(
                RULE_RING, f, ln,
                f"remote DMA self-send in kernel '{rec['name']}': "
                f"axis_index {self_send[0]} targets itself — the ring "
                f"neighbor expression must never be the identity"))
    return out


# ---------------------------------------------------------------------------
# rule (d): Mosaic tiling / memory-space sanity
# ---------------------------------------------------------------------------


def _check_tiling(rec: dict, fallback: str) -> List[Violation]:
    out: List[Violation] = []
    grid = rec.get("grid") or []
    gridded = bool(grid) and all(isinstance(g, int) for g in grid)
    f, ln = _where(rec, None, fallback)
    if gridded:
        for b in rec.get("blocks", ()):
            blk, arr = b["block"], b["array"]
            if blk == arr or len(blk) != len(arr):
                continue
            if "sem" in b.get("space", ""):
                continue
            sub = _SUBLANE.get(b.get("dtype", ""), 8)
            rank = len(blk)
            for d, (bd, ad) in enumerate(zip(blk, arr)):
                if bd == ad:
                    continue
                if bd <= 0 or ad % bd != 0:
                    out.append(Violation(
                        RULE_TILE, f, ln,
                        f"block shape {blk} does not divide operand shape "
                        f"{arr} on dim {d} of '{b.get('origin', '?')}' in "
                        f"kernel '{rec['name']}' — partial edge blocks are "
                        f"the BENCH_r05 Mosaic rc=124 class"))
                    continue
                tile = sub if d == rank - 2 else (
                    _LANE if d == rank - 1 else None)
                if tile and bd % tile != 0:
                    out.append(Violation(
                        RULE_TILE, f, ln,
                        f"block dim {d} of '{b.get('origin', '?')}' splits "
                        f"a tiled axis into {bd}-wide pieces in kernel "
                        f"'{rec['name']}' — {b.get('dtype', '?')} needs "
                        f"({sub}, {_LANE}) alignment on the trailing dims"))
    refs = rec.get("refs", {})

    def ref_is_sem(acc):
        r = refs.get((acc or {}).get("ref"))
        return None if r is None else r.get("sem", False)

    for ev in _ring_events(rec.get("events", ())):
        for part in ("dst_sem", "src_sem"):
            if ev.get(part) is not None and ref_is_sem(ev[part]) is False:
                ef, eln = _where(rec, ev, fallback)
                out.append(Violation(
                    RULE_TILE, ef, eln,
                    f"DMA semaphore position holds non-semaphore ref "
                    f"{ev[part]['ref']} ({refs.get(ev[part]['ref'], {}).get('space', '?')}) "
                    f"in kernel '{rec['name']}'"))
        for part in ("src", "dst"):
            if ev.get(part) is not None and ref_is_sem(ev[part]) is True:
                ef, eln = _where(rec, ev, fallback)
                out.append(Violation(
                    RULE_TILE, ef, eln,
                    f"semaphore-space ref {ev[part]['ref']} used as DMA "
                    f"data in kernel '{rec['name']}'"))
    return out


# ---------------------------------------------------------------------------
# record -> violations driver
# ---------------------------------------------------------------------------


def check_pallas_records(where: str, records) -> List[Violation]:
    """All four rules over a list of extracted pallas_call records."""
    out: List[Violation] = []
    for rec in records or ():
        out.extend(_check_dma_balance(rec, where))
        out.extend(_check_ref_races(rec, where))
        out.extend(_check_ring(rec, where))
        out.extend(_check_tiling(rec, where))
    return out


# ---------------------------------------------------------------------------
# kernel-library entry registry (the pallas_calls NOT reachable from the
# layer-4 dispatch entries) + cache-riding driver
# ---------------------------------------------------------------------------


def pallas_entry_points() -> Dict[str, dict]:
    """name -> {fn, args}: every Pallas kernel the library exposes that
    the layer-4 entry sweep cannot reach.  All trace with interpret=True
    (lowering-only difference; tracing must not need a TPU).  The ring
    entry needs a >= PALLAS_AUDIT_MESH-device mesh and is skipped when
    unavailable (tier-1 and tools/lint.py both force 8 virtual
    devices)."""
    import jax
    import jax.numpy as jnp

    from ..ops import pallas_fuse as pf
    from ..ops import pallas_tower as pt
    from ..ops import tower as tw

    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    B = 4
    out = {
        "pallas_tower.fq2_mul": {
            "fn": lambda a, b: pt.fq2_mul(a, b, interpret=True),
            "args": (S((B, 2, 50), f32), S((B, 2, 50), f32)),
        },
        "pallas_tower.fq2_sqr": {
            "fn": lambda a: pt.fq2_sqr(a, interpret=True),
            "args": (S((B, 2, 50), f32),),
        },
        "pallas_tower.fq6_mul": {
            "fn": lambda a, b: pt.fq6_mul(a, b, interpret=True),
            "args": (S((B, 3, 2, 50), f32), S((B, 3, 2, 50), f32)),
        },
        "pallas_tower.fq12_mul": {
            "fn": lambda a, b: pt.fq12_mul(a, b, interpret=True),
            "args": (S((B, 6, 2, 50), f32), S((B, 6, 2, 50), f32)),
        },
        "pallas_fuse.fq2_mul": {
            "fn": pf.pallas_fuse(
                tw.fq2_mul, S((B, 2, 50), f32), S((B, 2, 50), f32),
                interpret=True),
            "args": (S((B, 2, 50), f32), S((B, 2, 50), f32)),
        },
    }
    from . import jaxpr_audit as ja

    if ja.sharded_audit_available():
        from ..ops import pallas_ring as pr
        from ..ops import sharded_verify as sv

        mesh = sv.make_mesh(n_devices=PALLAS_AUDIT_MESH)
        out["pallas_ring.ring_combine"] = {
            "fn": pr.ring_combine_fn(mesh, interpret=True),
            "args": (S((PALLAS_AUDIT_MESH, 6, 2, 50), f32),),
        }
    return out


@functools.lru_cache(maxsize=None)
def trace_pallas_entry(name: str):
    import jax

    meta = pallas_entry_points()[name]
    return jax.make_jaxpr(meta["fn"])(*meta["args"])


@functools.lru_cache(maxsize=None)
def pallas_entry_artifacts(name: str, use_cache: bool = True) -> dict:
    """Extracted records for one kernel-library entry — rides the
    layer-4 disk cache (same fingerprint, "pallas:"-prefixed keys)."""
    from . import jaxpr_audit as ja

    key = f"pallas:{name}"
    if use_cache:
        cached = ja._load_disk_cache().get(key)
        if cached is not None:
            return cached
    art = {"pallas": extract_pallas_records(trace_pallas_entry(name))}
    if use_cache:
        ja._store_disk_cache(key, art)
    return art


def audit_pallas_entry(name: str, use_cache: bool = True) -> List[Violation]:
    art = pallas_entry_artifacts(name, use_cache)
    return check_pallas_records(name, art.get("pallas"))


def audit_all_pallas(use_cache: bool = True) -> List[Violation]:
    """All four rules over every kernel-library entry.  The layer-4
    dispatch entries are swept separately by jaxpr_audit.audit_entry via
    the "pallas" artifact field."""
    out: List[Violation] = []
    for name in pallas_entry_points():
        out.extend(audit_pallas_entry(name, use_cache))
    return out
