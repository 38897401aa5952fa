"""Each step's run on the device laid on the engine tracer's clock, and the
device's idle time by what it waited for. The join is the program's
(`paddle_tpu/profiler/xplane.py join_engine_steps`: a step's run is the event
of the device plane's `XLA Modules` line that it launched; the tracer's clock
is fitted to the capture's from every step's `launch_ns` against its
`paddle_tpu.step <id>` annotation); this file hands it the run's capture and
step spans and cuts the traced window into step periods, device start to next
device start. A period is the run plus the idle time after it, and that falls
into `readback` (device end -> the read of the result returns: completion
signal, the copy, the thread's wake), `host` (-> the next step's `launch_ns`:
emit, the time outside any step, plan, build, the transfer) and `launch`
(-> the next device start: `jit`'s call path, the runtime's issue, the
program's start). Nothing is read where the program has no such join (a commit
before PR 38), without a device capture, where the join matched no runs, or
where it could not place them; `run.notes` then says which."""
from __future__ import annotations

PARTS = ("readback", "host", "launch")


def joined(run):
    """The program's `StepJoin` of this run, made once; None with a note
    where there is nothing to join."""
    if not hasattr(run, "_step_join"):
        run._step_join = _join(run)
    return run._step_join


def _join(run):
    from paddle_tpu.profiler import xplane

    why = None
    if run.capture is None or not run.capture.devices:
        why = "no device capture"
    elif not run.engine_steps:
        why = "no step spans"
    elif not hasattr(xplane, "StepJoin"):
        why = "the program's join_engine_steps does not find a step's run"
    if why is None:
        join = xplane.join_engine_steps(run.engine_steps, run.capture.path)
        clock = join.clocks[0]
        if any(s["device_us"] is not None for s in join.steps):
            run.notes.append("step timeline: " + xplane.clock_line(clock))
            return join
        why = clock["why"]
    run.notes.append(f"step timeline: nothing read: {why}")
    return None


def device_ms(run, kind="decode"):
    """Lengths in ms of the runs of the traced steps of one kind, by the
    device's own clock: it needs the match, and no join of clocks."""
    join = joined(run)
    if join is None:
        return []
    return [s["device_us"] / 1e3 for s in join.steps
            if s["kind"] == kind and s["device_us"] is not None]


def clock(run):
    """The fitted clock's record, or None where no run was placed."""
    join = joined(run)
    if join is None or join.clocks[0]["why"] is not None:
        return None
    return join.clocks[0]


def idle_parts(run):
    """`({"run", "readback", "host", "launch": seconds}, window seconds)` of
    the traced window, or None where no run was placed. The window is the
    `bench.window` annotation on the fitted clock, as `device.idle_share`
    reads it; the periods are clipped to it, so the three idle parts sum to
    that share's idle time up to the window's two ends (before the first
    placed run, after the last) and to the gaps between the operations of
    one run, which count as the run here."""
    from paddle_tpu.profiler import xplane

    c = clock(run)
    if c is None:
        return None
    w0, w1 = ((t - c["offset_ns"]) / 1e3 for t in run.capture.window())
    placed = [s for s in joined(run).steps
              if s["device_start_us"] is not None]
    total = dict.fromkeys(("run",) + PARTS, 0.0)
    for p in xplane.split_idle(placed):
        at = p["start_us"]
        for name in total:
            total[name] += max(0.0, min(at + p[name], w1) - max(at, w0))
            at += p[name]
    return {k: v * 1e-6 for k, v in total.items()}, (w1 - w0) * 1e-6


def idle_share(run, part):
    """One idle part over the traced window, in percent, with the number
    of step periods it was summed over."""
    got = idle_parts(run)
    if got is None:
        return None
    parts, window = got
    return 100.0 * parts[part] / window, clock(run)["steps"] - 1
