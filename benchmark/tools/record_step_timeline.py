"""Record, on one chip, what `benchmark/tests/test_step_timeline.py` reads: a
capture of a tiny traced engine serving a few requests, taken as a traced
run takes its own (`tracing.py DeviceTracer`: the `bench.window` annotation
around the traced stretch), and that run's step spans as JSON
(`reduce/engine_spans.py steps`, the records `Run.engine_steps` holds).

    python3 benchmark/tools/record_step_timeline.py --out chiprun_out/st

writes `step_timeline_1chip.xplane.pb` and `step_timeline_1chip.steps.json`
there and prints what the program's join makes of them. The model is a GPT of
two layers with two heads of 128 (the kernels' path), 4 lanes: twenty steps,
four of them chunk-carrying, so two step programs. The capture is cut to size
for the repository (`cut_to_size`: 1.8 MB as recorded, 0.17 MB kept): no
event's time, line or `run_id` is touched.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path[0] = str(ROOT)

NAME = "step_timeline_1chip"
NAME_CHARS = 96     # `reduce/intervals.py category` reads up to the first dot


def cut_to_size(src, dst):
    """Copy a capture without what no reader here looks at: the
    `/host:metadata` plane (the programs' HLO, 1.3 MB), the stats of the
    device's operation events (the `XLA Modules` line keeps its `run_id`)
    and the tails of the operations' names (their whole HLO text)."""
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    xs.ParseFromString(Path(src).read_bytes())
    for plane in xs.planes:
        if plane.name == "/host:metadata":
            plane.event_metadata.clear()
        if not plane.name.startswith("/device:"):
            continue
        for md in plane.event_metadata.values():
            md.name = md.name[:NAME_CHARS]
            del md.stats[:]
        for line in plane.lines:
            if line.name != "XLA Modules":
                for ev in line.events:
                    del ev.stats[:]
    Path(dst).write_bytes(xs.SerializeToString())


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: rehearse the tool (no device plane)")
    args = p.parse_args()

    from benchmark import device
    if args.platform == "cpu":
        device.rehearse_on_cpu(1)
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from benchmark.reduce import engine_spans
    from benchmark.tracing import DeviceTracer
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.profiler import xplane
    from paddle_tpu.serving import LLMEngine

    if jax.devices()[0].platform != args.platform:
        print(f"record_step_timeline: needs platform {args.platform}",
              file=sys.stderr)
        return 3
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                          num_heads=2, max_seq_len=256, dropout=0.0))
    model.eval()
    engine = LLMEngine(model, block_size=16, max_batch=4, max_seq_len=256,
                       trace=1e-9, seed=0)
    engine.warmup()
    rs = np.random.RandomState(0)

    def prompts(lengths):
        return [rs.randint(0, 512, (n,)).tolist() for n in lengths]

    engine.generate(prompts([40, 24]), max_new_tokens=4)     # warm
    tracer = DeviceTracer(NAME)
    tracer.start_profiler()
    tracer.mark_start()
    t0 = tracer.t0
    engine.generate(prompts([33, 20, 28]), max_new_tokens=10)
    engine.generate(prompts([17]), max_new_tokens=8)
    tracer.stop()

    class Run:                       # what `DeviceTracer.load_into` fills
        capture = traced = trace_clock_ns = None

    tracer.load_into(Run)
    steps = engine_spans.steps(engine.tracer, t0, tracer.t1)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cut_to_size(Run.capture.path, out / f"{NAME}.xplane.pb")
    with open(out / f"{NAME}.steps.json", "w") as f:
        json.dump({"traced": Run.traced, "steps": steps}, f)
    size = (out / f"{NAME}.xplane.pb").stat().st_size
    print(f"recorded {len(steps)} steps, capture of {size} bytes; layout "
          f"{json.dumps(Run.capture.layout)}")
    join = xplane.join_engine_steps(steps, Run.capture.path)
    print(json.dumps(join.clocks))
    xplane.print_step_timeline(steps, Run.capture.path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
