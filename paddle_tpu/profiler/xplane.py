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


def engine_step_spans(logdir_or_file):
    """Serving-engine step annotations in a capture: {step_id ->
    {"start_us", "end_us", "dur_us", "plane"}}.

    While `serving.trace.EngineTracer` is on, the engine wraps every
    device dispatch in a `jax.profiler.TraceAnnotation` named
    ``paddle_tpu.step <id>`` with the SAME id the host trace's ``step``
    span carries. A `jax.profiler.trace` capture taken during a traced
    serve therefore contains one annotation event per engine step; this
    walks every plane for them. Duplicate ids (an annotation mirrored on
    several lines) merge to their union span."""
    out = {}
    for path in _capture_paths(logdir_or_file):
        xs = _load_space(path)
        for plane in xs.planes:
            em = plane.event_metadata
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    m = _STEP_ANNOTATION_RE.match(em[ev.metadata_id].name)
                    if not m:
                        continue
                    sid = int(m.group(1))
                    s = (base + ev.offset_ps) / 1e6      # ps -> us
                    e = s + ev.duration_ps / 1e6
                    if sid in out:
                        s = min(s, out[sid]["start_us"])
                        e = max(e, out[sid]["end_us"])
                    out[sid] = {"start_us": s, "end_us": e,
                                "dur_us": e - s, "plane": plane.name}
    return out


def join_engine_steps(chrome_trace, logdir_or_file):
    """Join a host trace (`EngineTracer`/`TrainTracer` ``chrome_trace()``
    dict, or a path to its dumped JSON) to a device capture by step id.

    Accepts the serving step timeline's ``step[kind]`` spans AND the
    training stack's ``train_step`` spans (profiler/tracing.py) — both
    wrap their device dispatch in the same ``paddle_tpu.step <id>``
    annotation. Returns one record per host span, sorted by step id:
    ``{"step", "kind", "host_ts_us", "host_dur_us", "capture_dur_us",
    "capture_plane"}`` — ``kind`` is None for training spans; capture
    fields are None for steps the capture did not cover (the two
    recorders have independent lifetimes). The two clocks are unrelated,
    so only DURATIONS are comparable across the join, never absolute
    timestamps."""
    import json as _json

    if isinstance(chrome_trace, str):
        with open(chrome_trace) as f:
            chrome_trace = _json.load(f)
    device = engine_step_spans(logdir_or_file)
    rows = []
    for ev in chrome_trace.get("traceEvents", ()):
        args = ev.get("args") or {}
        name = ev.get("name", "")
        if ev.get("ph") != "X" or "step" not in args \
                or not (name.startswith("step[") or name == "train_step"):
            continue
        sid = args["step"]
        d = device.get(sid)
        rows.append({
            "step": sid,
            "kind": args.get("kind"),
            "host_ts_us": ev["ts"],
            "host_dur_us": ev["dur"],
            "capture_dur_us": None if d is None else d["dur_us"],
            "capture_plane": None if d is None else d["plane"],
        })
    rows.sort(key=lambda r: r["step"])
    return rows


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
    existed since round 1; this is their entry point)."""
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
    args = p.parse_args(argv)
    if not _capture_paths(args.logdir_or_file):
        print(f"no *.xplane.pb captures under {args.logdir_or_file}",
              file=sys.stderr)
        return 1
    print_summary(args.logdir_or_file, device_only=not args.host,
                  top=args.top)
    print_schedule_analysis(args.logdir_or_file, top_gaps=args.top_gaps)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
