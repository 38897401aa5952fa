"""How far the step timeline's clock can be off, in microseconds: the quartile
distance of the steps' (annotation's start - `launch_ns`) around the fitted
offset (under a microsecond on a v5e), plus, where the profiler's device plane
had to be shifted to make every step causal, the width of the interval of
shifts that do (the program's `join_engine_steps`, `error_us`). The plane is
put in the MIDDLE of that interval by convention, so the width is what
`idle.launch_share` and `idle.readback_share` can have traded with each other:
each lies within (50 x this reading / the step's period) points of the truth,
which the run's notes give beside it ("= 2.2 points of a 9.7 ms period"). Two
runs whose launch or readback shares differ by less than that have not been
told apart; their SUM, `idle.host_share` and `step.device_ms_p50` do not move
with the shift and have the fit's error alone. Nothing is read where the
capture bounds the shift on one side only: no run is placed then."""
from benchmark.reduce import step_timeline


def read(run):
    c = step_timeline.clock(run)
    return None if c is None else (c["error_us"], c["pairs"])
