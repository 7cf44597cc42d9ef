#!/usr/bin/env python3
"""Validate a trace dump against the Chrome trace-event schema.

Usage:
    python tools/check_trace.py TRACE.json [--require-pipeline [N]]
                                [--require-device [TOL_US]]

Checks (the subset of the Trace Event Format spec that chrome://tracing
and Perfetto actually require to load a file):

- top level is an object with a ``traceEvents`` list (or a bare list);
- every event is an object with a string ``name`` and a string ``ph``;
- ``X``/``B``/``E``/``i``/``I`` events carry a numeric ``ts``;
- complete events (``ph == "X"``) carry a numeric non-negative ``dur``;
- ``pid``/``tid``, when present, are integers;
- ``args``, when present, is an object;
- object-form dumps note their drop count
  (``otherData.dropped_spans``) — a dump that cannot say how much
  history the ring evicted under it is silently lying about coverage.

``--require-pipeline [N]`` additionally asserts the dump contains the
full BLS span taxonomy — ``bls.queue_wait`` / ``bls.pack`` /
``bls.dispatch`` / ``bls.readback`` — with non-zero durations, batch-
correlated (same ``args.cid``) for at least N distinct merged batches
(default 2).  On the split path (a ``bls.readback`` span carrying
``args.ok``) a batch whose device checks passed (``ok`` true) must also
carry its host ``bls.final_exp``; the fused path and a batch the device
already rejected have none.  When the dump comes from a multi-device executor pool
(any ``bls.dispatch`` span carries ``args.devices_total > 1``) it also
asserts the dispatches landed on >= 2 distinct ``args.device`` ids — a
pool that funnels every batch to one chip is a scheduler bug, not a
pipeline.  Mesh dispatch (the sharded tier, docs/multichip.md): a
dispatch span carrying ``args.sharded`` must also carry
``args.mesh_devices >= 2`` and a ``devices_total > 1`` — a "sharded"
batch that reports one device never left a single chip; conversely one
sharded span with ``mesh_devices >= 2`` satisfies the distinct-device
requirement by itself (the mesh program spans every chip).  ``bls.shed`` spans (overload policy) exclude their cid from
the pipeline requirement; ``bls.requeue`` spans (self-healing pool,
docs/chaos.md) do NOT — a requeued cid must still complete its pipeline
via the replay, and must show >= 2 ``bls.dispatch`` attempts.  This is
the acceptance gate for a ``--trace-dump`` dev-chain run;
tests/test_tracing.py drives it in-process.

``--require-device [TOL_US]`` validates a MERGED host+device dump (the
mesh observatory's xprof output, docs/observability.md §Mesh
observatory): device events must live in renumbered processes at
``pid >= 1000`` (one ``process_name`` metadata event each — the
profiler pid/tid convention after the merge), host spans must remain at
pid 0, the dump must carry its clock mapping
(``otherData.device_clock``: numeric ``offset_us``/``skew_us``/
``tolerance_us``), the remapped device events must share the host
clock (their window overlaps the host span window), and a recorded
skew beyond tolerance (TOL_US overrides the dump's own) fails — a
merge whose clocks drifted is two timelines glued together, not one.

Exit 0 on success; exit 1 with one error per line on failure.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List

PIPELINE_SPANS = ("bls.queue_wait", "bls.pack", "bls.dispatch", "bls.readback")
#: the split path's host final exponentiation: required of a batch whose
#: ``bls.readback`` says the device checks passed (``args.ok`` true)
FINAL_EXP_SPAN = "bls.final_exp"
#: spans that legitimately END a batch early: a cid whose jobs were shed
#: by the overload policy (chain/bls_pool deadline shedding) never reaches
#: pack/dispatch — --require-pipeline must not count it as a broken
#: pipeline, and its presence is reported, not errored
SHED_SPAN = "bls.shed"
#: a failed in-flight batch re-dispatched onto a surviving executor
#: (self-healing pool, docs/chaos.md).  A requeued cid must STILL satisfy
#: --require-pipeline — the replay emits fresh dispatch/readback spans —
#: and additionally must show >= 2 dispatch attempts (a requeue span with
#: no re-dispatch means the recovery path lost the batch)
REQUEUE_SPAN = "bls.requeue"
_TS_PHASES = {"X", "B", "E", "i", "I"}
#: merged-trace device processes start here (the
#: lodestar_tpu/observatory/xprof.py DEVICE_PID_BASE convention; the
#: value is duplicated so this tool stays runnable with no package
#: on the path)
DEVICE_PID_BASE = 1000


def validate(trace: Any) -> List[str]:
    """Schema errors for a parsed trace object (empty list = valid)."""
    errors: List[str] = []
    if isinstance(trace, dict):
        events = trace.get("traceEvents")
        if not isinstance(events, list):
            return ["top-level object has no traceEvents list"]
        if not isinstance(
            (trace.get("otherData") or {}).get("dropped_spans"), int
        ):
            errors.append(
                "otherData.dropped_spans missing: the dump must note how "
                "many spans the ring evicted"
            )
    elif isinstance(trace, list):
        events = trace
    else:
        return [f"trace must be an object or array, got {type(trace).__name__}"]
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: event is not an object")
            continue
        if not isinstance(ev.get("name"), str):
            errors.append(f"{where}: missing string 'name'")
        ph = ev.get("ph")
        if not isinstance(ph, str) or not ph:
            errors.append(f"{where}: missing string 'ph'")
            continue
        if ph in _TS_PHASES and not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"{where} ({ev.get('name')}): ph={ph} requires numeric 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(
                    f"{where} ({ev.get('name')}): complete event requires "
                    f"non-negative numeric 'dur'"
                )
        for key in ("pid", "tid"):
            if key in ev and not isinstance(ev[key], int):
                errors.append(f"{where}: '{key}' must be an integer")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"{where}: 'args' must be an object")
    return errors


def validate_pipeline(trace: Any, min_batches: int = 2) -> List[str]:
    """BLS-pipeline errors: every PIPELINE_SPANS stage present with dur>0
    under the same cid, for >= min_batches distinct cids; and, for a
    multi-device dump (dispatch spans carrying ``devices_total > 1``),
    dispatches spread over >= 2 distinct device ids."""
    events = trace.get("traceEvents", trace) if isinstance(trace, dict) else trace
    by_cid: Dict[Any, Dict[str, float]] = {}
    shed_cids = set()
    requeued_cids = set()
    dispatches_by_cid: Dict[Any, int] = {}
    final_exp_due = set()  # split-path cids whose device checks passed
    devices_seen = set()
    devices_total = 1
    mesh_covered = False  # a sharded span with mesh_devices >= 2 seen
    mesh_errors: List[str] = []
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        name = ev.get("name")
        if name == SHED_SPAN:
            cid = (ev.get("args") or {}).get("cid", ev.get("id"))
            if cid is not None:
                shed_cids.add(cid)
            continue
        if name == REQUEUE_SPAN:
            cid = (ev.get("args") or {}).get("cid", ev.get("id"))
            if cid is not None:
                requeued_cids.add(cid)
            continue
        if name not in PIPELINE_SPANS and name != FINAL_EXP_SPAN:
            continue
        args = ev.get("args") or {}
        if name == "bls.dispatch":
            devices_total = max(devices_total, int(args.get("devices_total", 1)))
            if args.get("sharded"):
                # mesh dispatch contract: the span must say how many
                # chips the batch actually spanned, and a sharded batch
                # on a 1-device "mesh" is the scheduler lying
                mesh_n = args.get("mesh_devices")
                if not isinstance(mesh_n, int) or mesh_n < 2:
                    mesh_errors.append(
                        f"pipeline: sharded bls.dispatch span (cid "
                        f"{args.get('cid')}) must carry integer "
                        f"args.mesh_devices >= 2, got {mesh_n!r}"
                    )
                elif int(args.get("devices_total", 1)) <= 1:
                    mesh_errors.append(
                        f"pipeline: sharded bls.dispatch span (cid "
                        f"{args.get('cid')}) reports devices_total == 1 — "
                        f"a mesh-spanning batch on a single-device pool "
                        f"is not sharded"
                    )
                else:
                    mesh_covered = True
            elif args.get("device") is not None:
                devices_seen.add(args["device"])
        cid = args.get("cid", ev.get("id"))
        if cid is None:
            continue
        if name == "bls.dispatch":
            dispatches_by_cid[cid] = dispatches_by_cid.get(cid, 0) + 1
        if name == "bls.readback" and args.get("ok") is True:
            final_exp_due.add(cid)
        stages = by_cid.setdefault(cid, {})
        stages[name] = max(stages.get(name, 0.0), float(ev.get("dur", 0)))
    complete = [
        cid
        for cid, stages in by_cid.items()
        if all(stages.get(s, 0.0) > 0.0 for s in PIPELINE_SPANS)
        and (cid not in final_exp_due or stages.get(FINAL_EXP_SPAN, 0.0) > 0.0)
    ]
    errors: List[str] = []
    if len(complete) < min_batches:
        # a cid whose jobs were entirely shed (bls.shed) is an overload
        # decision, not a broken pipeline — exclude it from the partials
        partial = {
            cid: sorted(st)
            for cid, st in by_cid.items()
            if cid not in shed_cids
        }
        errors.append(
            f"pipeline: need >= {min_batches} batches with correlated non-zero "
            f"{'/'.join(PIPELINE_SPANS)} spans, found {len(complete)} "
            f"({len(shed_cids)} shed batches excluded; "
            f"partial batches: {partial})"
        )
    errors.extend(mesh_errors)
    # one valid mesh-spanning dispatch covers every chip by construction
    if devices_total > 1 and len(devices_seen) < 2 and not mesh_covered:
        errors.append(
            f"pipeline: multi-device dump (devices_total={devices_total}) but "
            f"dispatches landed on {sorted(devices_seen)} — expected >= 2 "
            f"distinct device ids (or a sharded mesh dispatch)"
        )
    # a requeued batch (bls.requeue) must show its replay: >= 2 dispatch
    # attempts under the same cid, else the recovery path lost the batch
    for cid in sorted(requeued_cids, key=str):
        if dispatches_by_cid.get(cid, 0) < 2:
            errors.append(
                f"pipeline: cid {cid} carries a {REQUEUE_SPAN} span but only "
                f"{dispatches_by_cid.get(cid, 0)} bls.dispatch attempt(s) — "
                f"a requeue must re-dispatch on a surviving executor"
            )
    return errors


def validate_device_merge(trace: Any, tolerance_us: float = None) -> List[str]:
    """Merged host+device dump errors (empty list = valid merge).

    Requires: object form with ``otherData.device_clock`` (numeric
    offset/skew/tolerance), >= 1 complete device event at
    ``pid >= DEVICE_PID_BASE`` with a ``process_name`` metadata event
    per device process, host spans still at pid 0, the remapped device
    window overlapping the host window (shared clock), and
    ``|skew_us| <= tolerance`` (``tolerance_us`` overrides the dump's)."""
    errors: List[str] = []
    if not isinstance(trace, dict):
        return ["device-merge: merged dumps must use the object form "
                "(otherData carries the clock mapping)"]
    clock = (trace.get("otherData") or {}).get("device_clock")
    if not isinstance(clock, dict):
        return ["device-merge: otherData.device_clock missing — a merged "
                "dump must record how the profiler timebase was mapped"]
    for key in ("offset_us", "skew_us", "tolerance_us"):
        if not isinstance(clock.get(key), (int, float)):
            errors.append(
                f"device-merge: device_clock.{key} must be numeric, "
                f"got {clock.get(key)!r}"
            )
    if errors:
        return errors
    tol = float(tolerance_us) if tolerance_us is not None else float(
        clock["tolerance_us"]
    )
    if abs(float(clock["skew_us"])) > tol:
        errors.append(
            f"device-merge: clock skew {clock['skew_us']:.1f}us exceeds "
            f"tolerance {tol:.1f}us — the device timeline cannot be "
            f"trusted against the host spans"
        )
    events = trace.get("traceEvents") or []
    named_pids = set()
    device_windows: List[tuple] = []
    host_windows: List[tuple] = []
    device_pids = set()
    for ev in events:
        if not isinstance(ev, dict):
            continue
        pid = ev.get("pid")
        if not isinstance(pid, int):
            continue
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            named_pids.add(pid)
            continue
        if ev.get("ph") != "X":
            continue
        ts, dur = ev.get("ts"), ev.get("dur", 0)
        if not isinstance(ts, (int, float)):
            continue
        window = (float(ts), float(ts) + float(dur or 0))
        if pid >= DEVICE_PID_BASE:
            device_pids.add(pid)
            device_windows.append(window)
        elif pid == 0:
            host_windows.append(window)
    if not device_windows:
        errors.append(
            f"device-merge: no complete device events at "
            f"pid >= {DEVICE_PID_BASE} — the merge carried no profile"
        )
    if not host_windows:
        errors.append(
            "device-merge: no host spans at pid 0 — the merge lost the "
            "span-tracer timeline"
        )
    for pid in sorted(device_pids):
        if pid not in named_pids:
            errors.append(
                f"device-merge: device process {pid} has no process_name "
                f"metadata event (the profiler pid convention)"
            )
    if device_windows and host_windows:
        d0 = min(a for a, _ in device_windows)
        d1 = max(b for _, b in device_windows)
        h0 = min(a for a, _ in host_windows)
        h1 = max(b for _, b in host_windows)
        if d1 < h0 - tol or d0 > h1 + tol:
            errors.append(
                f"device-merge: remapped device window "
                f"[{d0:.1f}, {d1:.1f}]us does not overlap the host window "
                f"[{h0:.1f}, {h1:.1f}]us (±{tol:.1f}us) — the clocks were "
                f"not actually shared"
            )
    return errors


def _optional_float(argv: List[str], flag: str):
    """(present, value|None) for a flag with an optional numeric arg."""
    if flag not in argv:
        return False, None
    idx = argv.index(flag)
    if idx + 1 < len(argv):
        try:
            return True, float(argv[idx + 1])
        except ValueError:
            pass
    return True, None


def main(argv: List[str]) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0 if argv else 1
    path = argv[0]
    min_batches = 2
    require_pipeline = "--require-pipeline" in argv
    if require_pipeline:
        idx = argv.index("--require-pipeline")
        if idx + 1 < len(argv) and argv[idx + 1].isdigit():
            min_batches = int(argv[idx + 1])
    require_device, device_tol = _optional_float(argv, "--require-device")
    try:
        with open(path) as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        print(f"{path}: unreadable trace: {e}", file=sys.stderr)
        return 1
    errors = validate(trace)
    if not errors and require_pipeline:
        errors = validate_pipeline(trace, min_batches)
    if not errors and require_device:
        errors = validate_device_merge(trace, tolerance_us=device_tol)
    for err in errors:
        print(f"{path}: {err}", file=sys.stderr)
    if not errors:
        n_events = len(trace.get("traceEvents", trace) if isinstance(trace, dict) else trace)
        print(f"{path}: OK ({n_events} events)")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
