"""What one run measured. The runner fills it in; each per-layer reader
(`layer_metrics/<name>.py`, `read(run)`) takes its metric from it and returns
None where there is nothing to read."""
from __future__ import annotations


class Run:
    def __init__(self, bench, cell, config, mix, args, device, clock,
                 process_start):
        self.bench, self.cell, self.config, self.mix = bench, cell, config, mix
        self.args = args
        self.device = device            # platform, kind, count
        self.clock = clock              # CompileClock
        self.process_start = process_start
        self.on_chip = device["platform"] == "tpu"
        # the window, on time.monotonic()
        self.t0 = self.t1 = None
        self.compile_setup = self.compile_window = None
        self.memory_peak_bytes = None
        self.attempted = self.failed = 0
        self.correct = False
        self.compared = {}              # what `correct` compared: name ->
                                        # {"value", "limit"} (correct.py)
        self.notes = []                 # lines for the log, not the result
        # the traced part of the window (a traced run only)
        self.capture = None             # reduce.xplane.Capture
        self.traced = None              # (t0, t1) on time.monotonic()
        self.trace_clock_ns = None      # capture ns at monotonic 0
        # serving. The load starts before the window (the ramp is set-up):
        # end-to-end metrics are of the window; per-layer readers may look at
        # the whole load where the window alone holds too little, and say so
        self.load_start = None
        self.tokens_short = None        # a window that closes on a token
                                        # count: tokens missing at the cap
        self.samples = None             # loadgen.Sample, every request sent
        self.counters = None            # the program's counters, window only
        self.load_counters = None       # the same, since the load started
        self.gauges = None              # its gauges at the window's end
        self.engine_latency = None      # its latency_summary(), whole load
        self.engine_steps = None        # reduce.engine_spans.steps, whole
                                        # load, a traced run only
        # training
        self.steps = None               # dicts: start, end, loss, input_wait
        self.tokens_per_step = None
        self.flops_per_token = None
        self.loss_first = None

    @property
    def seconds(self):
        return self.t1 - self.t0

    def in_window(self, t):
        return self.t0 <= t <= self.t1

    def window_steps(self):
        """The engine's steps that began and ended inside the window."""
        return [s for s in self.engine_steps or ()
                if s["start"] >= self.t0 and s["end"] <= self.t1]

    def train_tok_s(self):
        """Tokens of the steps that ended inside the window, over the time
        from the first such step's start to the last one's end."""
        seconds = self.steps[-1]["end"] - self.steps[0]["start"]
        return len(self.steps) * self.tokens_per_step / seconds

    def to_capture_ns(self, mono):
        return mono * 1e9 + self.trace_clock_ns
