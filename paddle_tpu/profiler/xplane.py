"""Cross-stack trace analysis: parse profiler xplane.pb captures into
per-op / per-category time summaries.

Reference parity: the reference's cross-stack profiler tooling
(tools/CrossStackProfiler — merges trainer/device timelines into op-level
statistics) and profiler/profiler_statistic.py's op summary tables.

TPU-native design: `paddle_tpu.profiler.Profiler` (and raw
`jax.profiler.trace`) emit xplane protobuf captures. This module reads them
back WITHOUT TensorFlow/TensorBoard (their converter wheels drift), using a
vendored minimal xplane schema (`_xplane/xplane.proto`, compiled once with
protoc and checked in). `summarize()` is what turned up the r4 perf wins:
the flash-kernel half-utilization and the BN-reduction domination were both
read straight off its category table.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple


def _load_space(path):
    from ._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def find_xplane_files(logdir):
    """All xplane.pb captures under a jax.profiler/Profiler logdir."""
    return sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))


def _capture_paths(logdir_or_file):
    """One capture file, or every capture under a logdir."""
    if logdir_or_file.endswith(".pb"):
        return [logdir_or_file]
    return find_xplane_files(logdir_or_file)


# an HLO instruction's name, alone or in front of its text: `fusion.123`,
# `%copy.31.remat2 = bf16[...] copy(...)`
_HLO_NAME = re.compile(r"%?([A-Za-z_][\w-]*)(?:\.\w+)*(?: = .*)?$", re.S)


def _category(op_name):
    """An operation's name up to its first dot (`fusion.123`,
    `%copy.31.remat2 = ...` and `ragged_paged_attention.7` fold to
    `fusion`, `copy` and `ragged_paged_attention`), so a kernel or the
    copies are one row; a host event that is no HLO name stays whole."""
    m = _HLO_NAME.match(op_name)
    return m.group(1) if m else op_name


def summarize(logdir_or_file, device_only=True, top=30):
    """Per-op-category busy-time summary across all planes of a capture.

    Returns {plane_name: {"total_ms", "lines", "by_category": [(name, ms)],
    "by_op": [(name, ms)]}} — the op-profile table the reference's
    cross-stack tool renders, as plain data."""
    paths = _capture_paths(logdir_or_file)
    out = {}
    for path in paths:
        xs = _load_space(path)
        for plane in xs.planes:
            is_device = plane.name.startswith("/device:")
            if device_only and not is_device:
                continue
            em = plane.event_metadata
            cat = defaultdict(float)
            ops = defaultdict(float)
            total = 0.0
            n_lines = []
            for line in plane.lines:
                n_lines.append(line.name)
                if is_device and line.name not in ("XLA Ops",):
                    continue  # Steps/Modules double-count the op time
                for ev in line.events:
                    name = em[ev.metadata_id].name
                    ms = ev.duration_ps / 1e9
                    ops[name] += ms
                    cat[_category(name)] += ms
                    total += ms
            if not ops:
                continue
            entry = out.setdefault(
                plane.name,
                {"total_ms": 0.0, "lines": n_lines,
                 "by_category": defaultdict(float), "by_op": defaultdict(float)},
            )
            entry["total_ms"] += total
            for k, v in cat.items():
                entry["by_category"][k] += v
            for k, v in ops.items():
                entry["by_op"][k] += v
    for entry in out.values():
        entry["by_category"] = sorted(
            entry["by_category"].items(), key=lambda kv: -kv[1]
        )[:top]
        entry["by_op"] = sorted(entry["by_op"].items(), key=lambda kv: -kv[1])[:top]
    return out


def interval_union_stats(intervals, to_ms=1.0, top_gaps=10, min_span=1e-12,
                         name_limit=None):
    """Merge (start, end, name) intervals into the per-plane schedule-stats
    dict `schedule_analysis` emits: overlaps union into busy time, the gaps
    between merged runs become top_gaps. Units are whatever the caller uses
    (ps for xplane captures, seconds for serving.ServingMetrics); `to_ms`
    converts them to milliseconds and `min_span` floors the utilization
    denominator in native units. An empty interval list (e.g. a metrics
    scrape before the first engine step) yields a zeroed record rather
    than an error."""
    iv = sorted(intervals)
    if not iv:
        return {"span_ms": 0.0, "busy_ms": 0.0, "idle_ms": 0.0,
                "utilization": 0.0, "n_ops": 0, "top_gaps": []}
    span_start = iv[0][0]
    span_end = max(e for _, e, _ in iv)
    busy = 0
    gaps = []
    cur_s, cur_e, last_name = iv[0]
    for s, e, name in iv[1:]:
        if s <= cur_e:
            if e >= cur_e:
                cur_e, last_name = e, name
        else:
            busy += cur_e - cur_s
            gaps.append((s - cur_e, last_name, name))
            cur_s, cur_e, last_name = s, e, name
    busy += cur_e - cur_s
    span = max(span_end - span_start, min_span)
    gaps.sort(key=lambda g: -g[0])
    trim = (lambda n: n[:name_limit]) if name_limit else (lambda n: n)
    return {
        "span_ms": span * to_ms,
        "busy_ms": busy * to_ms,
        "idle_ms": (span - busy) * to_ms,
        "utilization": busy / span,
        "n_ops": len(iv),
        "top_gaps": [
            {"gap_ms": g * to_ms, "after_op": trim(a), "before_op": trim(b)}
            for g, a, b in gaps[:top_gaps]
        ],
    }


def schedule_analysis(logdir_or_file, top_gaps=10):
    """Executor-schedule statistics (reference
    paddle/fluid/framework/new_executor/executor_statistics.cc: per-run
    timeline analysis — device busy vs idle, the gaps where the executor
    starved the device, and the op stream's utilization ratio).

    For each device plane: wall span (first event start -> last event end),
    busy time (union of op intervals, overlaps merged), idle = span - busy,
    utilization = busy/span, and the largest idle gaps with the ops that
    bracket them — the direct answer to "where is the schedule losing
    time" that the reference derives from interpreter run records."""
    out = {}
    planes = []
    for path in _capture_paths(logdir_or_file):
        xs = _load_space(path)
        planes.extend((path, p) for p in xs.planes)
    device_planes = [(f, p) for f, p in planes if p.name.startswith("/device:")]
    host_fallback = not device_planes
    if host_fallback:
        # CPU-only captures carry no device plane; analyze the host
        # compute threads instead (still a real schedule view)
        device_planes = [(f, p) for f, p in planes if p.name == "/host:CPU"]
    # same-named planes WITHIN one capture (multi-line traces) merge their
    # intervals; the same plane across DIFFERENT capture files has an
    # unrelated clock base, so unioning would report the dead time between
    # captures as one giant idle gap — key by (path, plane_name) and report
    # per-capture instead
    by_key = defaultdict(list)
    for path, plane in device_planes:
        em = plane.event_metadata
        for line in plane.lines:
            if not host_fallback and line.name not in ("XLA Ops",):
                continue
            base = line.timestamp_ns * 1000
            for ev in line.events:
                s = base + ev.offset_ps
                by_key[(path, plane.name)].append(
                    (s, s + ev.duration_ps, em[ev.metadata_id].name)
                )
    name_counts = defaultdict(int)
    for _, plane_name in by_key:
        name_counts[plane_name] += 1
    for (path, plane_name), intervals in sorted(by_key.items()):
        if name_counts[plane_name] > 1:  # disambiguate multi-capture runs
            base = f"{plane_name} [{os.path.basename(path)}]"
            plane_name, i = base, 2
            while plane_name in out:
                plane_name = f"{base}#{i}"
                i += 1
        if not intervals:
            continue
        out[plane_name] = interval_union_stats(
            intervals, to_ms=1e-9, top_gaps=top_gaps, min_span=1,
            name_limit=80,
        )
    return out


def print_schedule_analysis(logdir_or_file, top_gaps=10, file=None):
    """Also accepts pre-computed per-plane stats (a dict in
    schedule_analysis's output shape, e.g. serving.ServingMetrics
    .schedule_view()) and renders them identically."""
    import sys

    f = file or sys.stdout
    stats = (
        logdir_or_file
        if isinstance(logdir_or_file, dict)
        else schedule_analysis(logdir_or_file, top_gaps)
    )
    for plane, st in stats.items():
        print(
            f"== {plane}: span {st['span_ms']:.2f} ms, busy {st['busy_ms']:.2f} ms "
            f"({st['utilization']*100:.1f}% util, {st['n_ops']} ops)", file=f
        )
        for g in st["top_gaps"]:
            print(f"  idle {g['gap_ms']:8.3f} ms  after {g['after_op']}"
                  f"  before {g['before_op']}", file=f)


_STEP_ANNOTATION_RE = re.compile(r"^paddle_tpu\.step (\d+)$")
# a device plane's line of whole program runs: one event a run, named
# `jit_step(<hash>)`, stats `run_id`, `device_offset_ps`, `device_duration_ps`
_MODULES_LINE = "XLA Modules"
# the runtime's own host events around a run (TPU: `tpu::System::Execute=>..`)
_ISSUE_SUFFIX = "Execute=>IssueSequencedEvent"
_DONE_SUFFIX = "Execute=>Done"


def engine_step_spans(logdir_or_file):
    """Serving-engine step annotations in a capture: {step_id ->
    {"start_us", "end_us", "dur_us", "plane"}}.

    While `serving.trace.EngineTracer` is on, the engine wraps every
    device dispatch in a `jax.profiler.TraceAnnotation` named
    ``paddle_tpu.step <id>`` with the SAME id the host trace's ``step``
    span carries. A `jax.profiler.trace` capture taken during a traced
    serve therefore contains one annotation event per engine step; this
    walks every plane for them. Duplicate ids (an annotation mirrored on
    several lines) merge to their union span. The span is the launch CALL
    on the host, not the program's run on the device: `join_engine_steps`
    finds that."""
    merged = {}
    for path in _capture_paths(logdir_or_file):
        for sid, (s, e, plane) in _step_annotations(_load_space(path)).items():
            if sid in merged:
                s, e = min(s, merged[sid][0]), max(e, merged[sid][1])
            merged[sid] = (s, e, plane)
    return {sid: {"start_us": s / 1e3, "end_us": e / 1e3,
                  "dur_us": (e - s) / 1e3, "plane": plane}
            for sid, (s, e, plane) in merged.items()}


def _events_named(plane, match):
    """`(start_ns, end_ns, match(name))` of the plane's events whose name
    `match` accepts (returns something true for), by start. Names are
    looked up in the plane's metadata first, so a device line of a million
    operations costs a dict lookup an event and a plane that holds no such
    name nothing."""
    ids = {mid: got for mid, md in plane.event_metadata.items()
           if (got := match(md.name))}
    out = []
    if ids:
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                got = ids.get(ev.metadata_id)
                if got is not None:
                    s = base + ev.offset_ps
                    out.append((s / 1e3, (s + ev.duration_ps) / 1e3, got))
        out.sort(key=lambda r: r[:2])
    return out


def _step_annotations(xs):
    """`{step id: (start_ns, end_ns, plane)}`; an annotation mirrored on
    several lines merges to its union."""
    out = {}
    for plane in xs.planes:
        for s, e, m in _events_named(plane, _STEP_ANNOTATION_RE.match):
            sid = int(m.group(1))
            if sid in out:
                s, e = min(s, out[sid][0]), max(e, out[sid][1])
            out[sid] = (s, e, plane.name)
    return out


def _stat(plane, ev, name):
    for st in ev.stats:
        if plane.stat_metadata[st.metadata_id].name == name:
            return getattr(st, st.WhichOneof("value"))
    return None


def _program_runs(xs):
    """The runs of whole programs on the device planes' `XLA Modules`
    lines, by start: `[{"start", "end" (ns, the capture's clock), "program",
    "run_id"}]`. Several planes (a mesh) give a run each; the join reads
    the first plane, on which every program of one process runs."""
    runs = []
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != _MODULES_LINE:
                continue
            base = line.timestamp_ns * 1000
            for ev in line.events:
                s = base + ev.offset_ps
                runs.append({
                    "start": s / 1e3, "end": (s + ev.duration_ps) / 1e3,
                    "program": plane.event_metadata[ev.metadata_id].name,
                    "run_id": _stat(plane, ev, "run_id")})
        if runs:
            break
    runs.sort(key=lambda r: r["start"])
    return runs


def _host_runtime_events(xs):
    """What the runtime says on the host planes, in ns: `said` `{run_id:
    [start, ..]}` by start (every host event with a `run_id` stat: on a TPU
    `DoEnqueueProgram` on the runtime's thread before the run, which is the
    FIRST, and `CompleteCallbacks` on its completion thread after it),
    `issues` and `dones` `[start]` (`..Execute=>IssueSequencedEvent`,
    `..Execute=>Done`)."""
    said, issues, dones = defaultdict(list), [], []
    for plane in xs.planes:
        if plane.name.startswith("/device:"):
            continue
        if any(m.name == "run_id" for m in plane.stat_metadata.values()):
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    rid = _stat(plane, ev, "run_id") if ev.stats else None
                    if rid is not None:
                        said[rid].append((base + ev.offset_ps) / 1e3)
        issues += [r[0] for r in _events_named(
            plane, lambda n: n.endswith(_ISSUE_SUFFIX))]
        dones += [r[0] for r in _events_named(
            plane, lambda n: n.endswith(_DONE_SUFFIX))]
    return ({rid: sorted(ts) for rid, ts in said.items()}, sorted(issues),
            sorted(dones))


def step_span_records(chrome_trace):
    """The step spans of a host trace as plain records on
    ``time.monotonic()`` seconds, by step id: ``{"id", "kind", "start",
    "end", "phases": {name: (start, end)}, "args"}`` (``kind`` None for a
    ``train_step``). `chrome_trace` is a tracer's ``chrome_trace()`` dict or
    the path of its dump; its ``otherData.epoch_ns`` puts ``ts`` on the
    clock of the spans' ``launch_ns`` (0 where an older dump lacks it)."""
    import json as _json

    if isinstance(chrome_trace, str):
        with open(chrome_trace) as f:
            chrome_trace = _json.load(f)
    epoch = (chrome_trace.get("otherData") or {}).get("epoch_ns", 0) / 1e9
    by_id = {}
    for ev in chrome_trace.get("traceEvents", ()):
        args = ev.get("args") or {}
        if ev.get("ph") != "X" or "step" not in args:
            continue
        name = ev.get("name", "")
        t0 = epoch + ev["ts"] / 1e6
        span = (t0, t0 + ev["dur"] / 1e6)
        if name.startswith("step[") or name == "train_step":
            by_id.setdefault(args["step"], {"phases": {}}).update(
                id=args["step"], kind=args.get("kind"), start=span[0],
                end=span[1], args=args)
        elif "request_id" not in args:
            by_id.setdefault(args["step"], {"phases": {}})["phases"][name] \
                = span
    return [r for _, r in sorted(by_id.items()) if "id" in r]


class StepJoin(NamedTuple):
    """What `join_engine_steps` returns: `steps`, one record a host span by
    step id, and `clocks`, one record a capture file that says how the
    tracer's clock was fitted to it, or `why` it gave no device run."""

    steps: list
    clocks: list


def _quartile_distance(values):
    import statistics

    if len(values) < 2:
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def _match_runs(annotated, runs, said):
    """`({step id: run}, why)`: the device run each annotated step launched.
    `annotated` is `[(start_ns, step id)]` by start, `said` the host's words
    by `run_id`. A run's enqueue is the first host event that carries its
    id, and it belongs to the annotation that began last before it; a step
    owns the first enqueue after its annotation. Only enqueues are keyed
    on, so a completion of step N that lands after annotation N+1 began
    (an overlapped loop) names nobody, and one enqueue names one step: no
    two steps share a run. Steps at the capture's two ends may lack their
    run (the profiler's host and device halves do not start and stop in
    one instant) and are left out; a step between two joined ones that
    lacks it, or a capture whose host events carry no `run_id` (every TPU
    capture of jax 0.9.0 does; there is no matching by order), gives `why`
    and no run at all."""
    import bisect

    if not said:
        return {}, ("no host event of the capture carries a run_id to match "
                    "a step's annotation to its run by")
    starts = [s for s, _ in annotated]
    ids = {}
    for t, rid in sorted((ts[0], rid) for rid, ts in said.items()):
        at = bisect.bisect_right(starts, t) - 1
        if at >= 0:
            ids.setdefault(annotated[at][1], rid)
    by_id = {r["run_id"]: r for r in runs}
    found = [by_id.get(ids.get(sid)) for _, sid in annotated]
    have = [i for i, r in enumerate(found) if r is not None]
    if not have:
        return {}, "no step's run_id is on the device's line"
    missing = [annotated[i][1] for i in range(have[0], have[-1] + 1)
               if found[i] is None]
    if missing:
        return {}, (
            f"step {missing[0]} lies between joined steps and its run "
            f"(run_id {ids.get(missing[0])}) is not on the device's "
            f"`{_MODULES_LINE}` line ({len(missing)} such)")
    return ({sid: r for (_, sid), r in zip(annotated, found)
             if r is not None}, None)


def _one_program_a_width(matched, by_id):
    """None, or why the match cannot stand: the engine compiles ONE step
    program a width bucket (the span's ``width``), so two steps of one
    width that ran programs of different names were not both matched to
    their own run (another program's enqueue, a copy's or a swap's, fell
    between an annotation and its own)."""
    ran = {}
    for sid, r in sorted(matched.items()):
        w = by_id[sid]["args"].get("width") if sid in by_id else None
        if w is not None and ran.setdefault(w, (r["program"], sid))[0] \
                != r["program"]:
            return (f"step {sid} of width {w} is matched to a run of "
                    f"`{r['program']}` where step {ran[w][1]} of that width "
                    f"ran `{ran[w][0]}`: one of the two is another "
                    "program's run")
    return None


def _runtime_stamps(order, notes, matched, said, issues, dones):
    """`{step id: {"enqueue", "issue", "done"}}`, ns on the capture's host
    clock: the enqueue is the first host event that carries the run's id,
    a later one with it the first word of the run's end. The issue and the
    Done carry no id and go by name: the first issue after the step's
    annotation began and before the next step's (else None), and the first
    Done after that (a Done belongs to one run, so they are handed out one
    a step); ``done`` is the earlier of the two words of the end. Where
    the runtime names runs' ends itself (completion events that carry the
    id, as a TPU's does), those alone are taken: a loop that launches a
    step before its predecessor's run ends issues it before that run's
    Done, which the hand-out would then give it."""
    import bisect

    named = any(len(said[matched[sid]["run_id"]]) > 1 for sid in order)
    out, d = {}, 0
    for sid, nxt in zip(order, order[1:] + [None]):
        s0 = notes[sid][0]
        mine = said[matched[sid]["run_id"]]
        at = bisect.bisect_left(issues, s0)
        issue = issues[at] if at < len(issues) and (
            nxt is None or issues[at] < notes[nxt][0]) else None
        while d < len(dones) and dones[d] < (s0 if issue is None else issue):
            d += 1
        ends = mine[1:] if named else mine[1:] + dones[d:d + 1]
        d += d < len(dones)
        out[sid] = {"enqueue": mine[0], "issue": issue,
                    "done": min(ends, default=None)}
    return out


def _causal_shift(bounds):
    """The check of the profiler's own alignment of its host and device
    planes. `bounds`: a `(before, start, end, after)` a step, ns: the run
    cannot start before the last host stamp known to precede it nor end
    after the first known to follow it (either may be None). Returns
    `(shift_ns, width_ns, violations, worst_ns)`: with no violation the
    planes stand as aligned (shift 0, width None). Else a constant skew is
    assumed: every shift in [max(before - start), min(after - end)] makes
    every step causal; the middle is taken and the interval's width is the
    doubt that leaves. A width of zero or less means no one shift mends
    them all, and a capture that bounds one side only gives no interval to
    take the middle of: shift None."""
    lo = [b - s for b, s, _, _ in bounds if b is not None]
    hi = [a - e for _, _, e, a in bounds if a is not None]
    violations = sum((b is not None and b > s) or (a is not None and e > a)
                     for b, s, e, a in bounds)
    worst = max([0.0] + lo + [-h for h in hi])
    if not violations:
        return 0.0, None, 0, worst
    if not (lo and hi):
        return None, None, violations, worst
    width = min(hi) - max(lo)
    shift = (min(hi) + max(lo)) / 2 if width > 0 else None
    return shift, width, violations, worst


def _join_capture(records, path):
    """`({step id: fields}, clock)` for one capture file."""
    import statistics

    xs = _load_space(path)
    notes = _step_annotations(xs)
    clock = {"path": path, "why": None, "steps": 0, "pairs": 0,
             "offset_ns": None, "fit_us": None, "violations": None,
             "worst_us": None, "shift_us": None, "causal_width_us": None,
             "error_us": None, "period_us": None, "error_points": None}
    out = {sid: {"launch_call_us": (e - s) / 1e3, "capture_plane": plane}
           for sid, (s, e, plane) in notes.items()}
    runs = _program_runs(xs)
    if not notes or not runs:
        clock["why"] = ("the capture holds no step annotation" if not notes
                        else "the capture holds no device plane with an "
                        f"`{_MODULES_LINE}` line")
        return out, clock
    annotated = sorted((s, sid) for sid, (s, _, _) in notes.items())
    said, issues, dones = _host_runtime_events(xs)
    matched, clock["why"] = _match_runs(annotated, runs, said)
    by_id = {r["id"]: r for r in records}
    if clock["why"] is None:
        clock["why"] = _one_program_a_width(matched, by_id)
    if clock["why"] is not None:
        return out, clock
    for sid, r in matched.items():
        out[sid].update(program=r["program"], run_id=r["run_id"],
                        device_us=(r["end"] - r["start"]) / 1e3)
    order = [sid for _, sid in annotated if sid in matched]
    if len(order) > 1:
        clock["period_us"] = (matched[order[-1]]["start"]
                              - matched[order[0]]["start"]) \
            / 1e3 / (len(order) - 1)
    # ONE clock: this capture's host clock minus the tracer's, from every
    # step's pair (annotation's start, the `launch_ns` stamped before it)
    launched = {sid: r for sid, r in by_id.items() if "launch_ns" in r["args"]}
    pairs = [notes[sid][0] - launched[sid]["args"]["launch_ns"]
             for sid in matched if sid in launched]
    clock["steps"], clock["pairs"] = len(matched), len(pairs)
    if not pairs:
        clock["why"] = ("the step spans carry no `launch_ns` (a trace from "
                        "before it was recorded): runs are matched, not "
                        "placed")
        return out, clock
    offset = clock["offset_ns"] = statistics.median(pairs)
    clock["fit_us"] = _quartile_distance(pairs) / 1e3
    stamps = _runtime_stamps(order, notes, matched, said, issues, dones)
    bounds = []
    for sid in order:
        f, rec = stamps[sid], launched.get(sid)
        f["launch"] = f["read"] = None
        if rec is not None:
            f["launch"] = rec["args"]["launch_ns"] + offset
            if "sync" in rec["phases"]:
                f["read"] = rec["phases"]["sync"][1] * 1e9 + offset
        bounds.append((
            max(f[k] for k in ("launch", "issue", "enqueue")
                if f[k] is not None),
            matched[sid]["start"], matched[sid]["end"],
            min((f[k] for k in ("done", "read") if f[k] is not None),
                default=None)))
    shift, width, clock["violations"], worst = _causal_shift(bounds)
    clock["worst_us"] = worst / 1e3
    clock["causal_width_us"] = None if width is None else width / 1e3
    if shift is None:
        clock["why"] = (
            f"{clock['violations']} of {len(order)} steps are not causal "
            f"(worst by {clock['worst_us']:.1f} us) and "
            + ("no one shift of the device's plane mends them all"
               if width is not None else
               "the capture bounds the device's plane on one side only, so "
               "no shift can be chosen")
            + ": runs are matched, not placed")
        return out, clock
    clock["shift_us"] = shift / 1e3
    clock["error_us"] = clock["fit_us"] + (clock["causal_width_us"] or 0.0)
    if clock["period_us"]:
        clock["error_points"] = 100 * clock["error_us"] / clock["period_us"]
    # every stamp on the tracer's monotonic clock, microseconds
    for sid in order:
        f = stamps[sid]
        f["device_start"] = matched[sid]["start"] + shift
        f["device_end"] = matched[sid]["end"] + shift
        out[sid].update({k + "_us": None if t is None else (t - offset) / 1e3
                         for k, t in f.items()})
    return out, clock


_PLACED = ("launch_us", "enqueue_us", "issue_us", "device_start_us",
           "device_end_us", "done_us", "read_us")


def join_engine_steps(spans, logdir_or_file):
    """Join host step spans to a device capture by step id, and lay each
    step's PROGRAM RUN on the device on the tracer's own clock.

    `spans`: an `EngineTracer`/`TrainTracer` ``chrome_trace()`` dict, the
    path of its dumped JSON, or the records `step_span_records` makes of
    either; serving ``step[kind]`` spans and training ``train_step`` spans
    alike, both launched under the ``paddle_tpu.step <id>`` annotation
    (`Tracer.launch`). Returns `StepJoin(steps, clocks)`.

    ``steps``: one record a host span, by step id. Always ``step``,
    ``kind`` (None for training), ``host_start_us``, ``host_dur_us``. Where
    a capture holds the step's annotation: ``launch_call_us``, the
    annotation's own length (the launch CALL on the host, which returns
    long before the device is done), and ``capture_plane``. Where the
    capture's device plane holds the run this step launched (the event of
    its `XLA Modules` line matched by ``run_id`` through the host's enqueue
    event, else in order): ``program``, ``run_id``, ``device_us`` (the run's
    length by the device's own clock: it needs no join of clocks). And
    where the clocks could be joined, in microseconds on the tracer's
    ``time.monotonic()``: ``launch_us`` (the span's ``launch_ns``),
    ``enqueue_us`` and ``issue_us`` (the runtime's enqueue that carries the
    run's id and its ``..Execute=>IssueSequencedEvent``, first after the
    annotation began), ``device_start_us``, ``device_end_us``, ``done_us``
    (the runtime's first word of the run's end: its completion event that
    carries the run's id, else ``..Execute=>Done``) and ``read_us`` (the
    end of the span's ``sync`` phase: the read of the result returned).
    Fields a capture does not give are None.

    ``clocks``: a record a capture file. ``offset_ns`` is the capture's
    host clock minus ``time.monotonic()``, the median over the steps of
    (annotation's start - ``launch_ns``), and ``fit_us`` the quartile
    distance of those differences. ``violations`` counts the steps whose
    run, as the profiler aligned its device plane, starts before the last
    host stamp known to precede it (launch, issue, enqueue) or ends after
    the first known to follow it (the completion event, Done, the read's
    return), ``worst_us`` the widest such margin. Where there are any, the
    device's plane is moved by ``shift_us``, the MIDDLE of the interval of
    shifts that make EVERY step causal, and that interval's width
    (``causal_width_us``) is added to ``error_us`` (else ``fit_us``
    alone). The middle is a convention, not a measurement: the truth lies
    anywhere in the interval, so ``device_start_us`` and ``device_end_us``
    can both be off by half the width, the same way. ``launch`` grows by
    what ``readback`` loses (`split_idle`); their SUM, ``host`` and the
    run's length do not move with the shift and have ``fit_us`` alone.
    ``period_us`` is the mean step period (device start to next start, by
    the device's own clock) and ``error_points`` is ``error_us`` over it in
    percent: the doubt in the unit of the shares of `split_idle`; the
    ``launch`` and the ``readback`` share each lie within half of it of
    what is reported. ``why`` says why a capture placed no run: no device
    plane (a CPU capture), no ``run_id`` on the host's events, a step's
    run missing between joined steps, two steps of one ``width`` on
    programs of different names (then no ``device_us`` either: the match
    cannot stand), spans without ``launch_ns``, violations no one shift
    mends or a capture that bounds the shift on one side only (runs are
    then matched and ``device_us`` given, not placed).

    Measured on a TPU v5e, jax 0.9.0 (chip runs of PR 38, PERF.md section
    6; 19 traced runs of three serving cells, 96-146 steps each, and a tiny
    engine's 20): the tracer's clock and the capture's host clock are ONE
    clock up to a constant, ``fit_us`` 0.05-0.2. The profiler's own
    alignment of its device plane is not: it stamps every run 0.8-2.1 ms
    EARLY (before the host enqueued it), by a constant within one capture
    that differs from capture to capture, so every step of every capture
    violates causality as aligned (``violations`` = steps, ``worst_us``
    561-2,015) and every capture is shifted; every run's enqueue carried
    its ``run_id``; ``causal_width_us`` read 177-445 (enqueue ->
    start and end -> the completion event at their shortest over the
    capture's steps), which is 1.9-4.6 points of rollout's 10 ms period,
    0.8-1.5 of turns' 26 ms and 0.4-1.1 of think's 41 ms. A run's length
    and every host stamp are known to the microsecond."""
    records = spans if isinstance(spans, list) else step_span_records(spans)
    fields, clocks = {}, []
    for path in _capture_paths(logdir_or_file):
        got, clock = _join_capture(records, path)
        for sid, f in got.items():
            if "device_us" in f or sid not in fields:
                fields[sid] = f
        clocks.append(clock)
    rows = []
    for r in records:
        f = fields.get(r["id"], {})
        row = {"step": r["id"], "kind": r["kind"],
               "host_start_us": r["start"] * 1e6,
               "host_dur_us": (r["end"] - r["start"]) * 1e6}
        for k in ("launch_call_us", "capture_plane", "program", "run_id",
                  "device_us") + _PLACED:
            row[k] = f.get(k)
        rows.append(row)
    return StepJoin(rows, clocks)


def clock_line(clock):
    """One of `join_engine_steps`' clock records in a line of words."""
    def us(key):
        return f"{clock[key]:.1f} us"

    line = f"{clock['steps']} steps matched, {clock['pairs']} clock pairs"
    if clock["fit_us"] is not None:
        line += (f", fit {us('fit_us')}, {clock['violations']} not causal "
                 f"as aligned (worst {us('worst_us')})")
    if clock["why"] is not None:
        return f"{line}; not placed: {clock['why']}"
    line += (f", device plane shifted {us('shift_us')}, error "
             f"{us('error_us')}")
    if clock["error_points"] is not None:
        line += (f" = {clock['error_points']:.2f} points of a "
                 f"{clock['period_us'] / 1e3:.2f} ms period between launch "
                 "and readback")
    return line


def print_step_timeline(spans, logdir_or_file, file=None):
    """Where the device's time went, step period by step period (device
    start to next device start): the run, then the idle time by the host's
    stamps on the fitted clock: ``readback`` (device end -> the read
    returns), ``host`` (-> the next launch) and ``launch`` (-> the next
    device start). What `benchmark/reduce/step_timeline.py` reports a cell
    by, for a capture of a live server and its ``/debug/trace``."""
    import statistics
    import sys

    f = file or sys.stdout
    join = join_engine_steps(spans, logdir_or_file)
    for c in join.clocks:
        print(f"== {c['path']}: {clock_line(c)}", file=f)
    placed = [r for r in join.steps if r["device_start_us"] is not None]
    parts = split_idle(placed)
    if parts:
        span = placed[-1]["device_start_us"] - placed[0]["device_start_us"]
        # the device plane's place in its causal interval moves time between
        # these two and nothing else
        doubt = max((c["error_points"] or 0.0) / 2 for c in join.clocks)
        for name in ("run", "readback", "host", "launch"):
            us = [p[name] for p in parts]
            print(f"  {name:9s} {100 * sum(us) / span:5.1f} %  median "
                  f"{statistics.median(us) / 1e3:7.3f} ms a step"
                  + (f"  (+-{doubt:.1f} points)"
                     if name in ("readback", "launch") else ""), file=f)
        both = sum(p["readback"] + p["launch"] for p in parts)
        print(f"  readback + launch {100 * both / span:5.1f} %", file=f)


def split_idle(placed):
    """Cut consecutive placed steps (records of `join_engine_steps` with a
    ``device_start_us``) into periods, device start to next device start:
    ``[{"step", "start_us", "run", "readback", "host", "launch"}]``, lengths
    in microseconds, the three idle parts summing to the gap between this
    run's end and the next run's start. The gap is cut at the read's return
    and at the next launch, each clamped into it, so the parts keep their
    meaning where a loop launches the next step before it has read this
    one."""
    out = []
    for a, b in zip(placed, placed[1:]):
        g0, g1 = a["device_end_us"], max(b["device_start_us"],
                                         a["device_end_us"])
        read = g0 if a["read_us"] is None else a["read_us"]
        p1 = min(max(read, g0), g1)
        p2 = p1 if b["launch_us"] is None else min(max(b["launch_us"], p1), g1)
        out.append({"step": a["step"], "start_us": a["device_start_us"],
                    "run": g0 - a["device_start_us"], "readback": p1 - g0,
                    "host": p2 - p1, "launch": g1 - p2})
    return out


def print_summary(logdir_or_file, device_only=True, top=20, file=None):
    """Human-readable rendering of summarize() (the reference tool's
    console table)."""
    import sys

    f = file or sys.stdout
    for plane, entry in summarize(logdir_or_file, device_only, top).items():
        print(f"== {plane}: busy {entry['total_ms']:.2f} ms "
              f"(lines: {', '.join(entry['lines'])})", file=f)
        for name, ms in entry["by_category"]:
            print(f"  {ms:10.3f} ms  {name[:100]}", file=f)


def main(argv=None):
    """``python -m paddle_tpu.profiler.xplane <logdir-or-file>`` — render
    the per-op-category busy-time summary and the executor-schedule
    analysis for a capture, straight from the shell (the functions have
    existed since round 1; this is their entry point); with ``--steps
    <trace.json>`` also the step timeline joined to the host trace."""
    import argparse
    import sys

    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.profiler.xplane",
        description="Summarize a jax.profiler xplane capture: per-category "
                    "op busy time (print_summary) + device busy/idle/gap "
                    "schedule analysis (print_schedule_analysis).",
    )
    p.add_argument("logdir_or_file",
                   help="a profiler logdir (globbed for **/*.xplane.pb) "
                        "or one .xplane.pb capture file")
    p.add_argument("--top", type=int, default=20,
                   help="op/category rows per plane (default 20)")
    p.add_argument("--top-gaps", type=int, default=10,
                   help="largest idle gaps per plane (default 10)")
    p.add_argument("--host", action="store_true",
                   help="include host planes in the op summary "
                        "(device_only=False; CPU captures need this)")
    p.add_argument("--steps", metavar="TRACE_JSON",
                   help="the host trace taken with the capture (GET "
                        "/debug/trace, or tracer.dump()): also lay each "
                        "step's run on the tracer's clock and split the "
                        "device's idle time (print_step_timeline)")
    args = p.parse_args(argv)
    if not _capture_paths(args.logdir_or_file):
        print(f"no *.xplane.pb captures under {args.logdir_or_file}",
              file=sys.stderr)
        return 1
    print_summary(args.logdir_or_file, device_only=not args.host,
                  top=args.top)
    print_schedule_analysis(args.logdir_or_file, top_gaps=args.top_gaps)
    if args.steps:
        print_step_timeline(args.steps, args.logdir_or_file)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
