"""A training cell: the configuration's model under `make_sharded_train_step`
on the mesh it names, a new batch every step from a host iterator that runs
inside the window, each step ended by waiting for its loss."""
from __future__ import annotations

import contextlib
import queue
import threading
import time

import numpy as np

from .. import correct, device, spec, weights
from ..reduce.flops import dense_train_flops_per_token
from ..tracing import DeviceTracer


class Prefetch:
    """The host iterator: a thread that keeps `depth` batches ready."""

    def __init__(self, batches, depth):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, args=(batches,),
                                        name="bench-input", daemon=True)
        self._thread.start()

    def _fill(self, batches):
        for b in batches:
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    pass
            if self._stop.is_set():
                return

    def get(self):
        return self._q.get()

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            raise RuntimeError("the input thread did not stop")


def run(run, log):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import init_mesh

    config, mix, args = run.config, run.mix, run.args
    tr = config["train"]
    kw = spec.model_kwargs(config)
    model = weights.build_model(config, args.seed)
    opt = paddle.optimizer.AdamW(learning_rate=tr["learning_rate"],
                                 parameters=model.parameters())
    mesh = init_mesh(tr["mesh"])
    step = spec.resolve(tr["step_builder"])(
        model, spec.resolve(tr["loss"]), opt, mesh,
        zero_stage=tr["zero_stage"], remat=tr["remat"])
    params, buffers, opt_state = step.init_state()
    batch, seq = tr["batch"], tr["seq_len"]
    run.tokens_per_step = batch * seq
    run.flops_per_token = dense_train_flops_per_token(
        kw["hidden_size"], kw["num_layers"], seq, kw["vocab_size"],
        kw["intermediate_size"])
    generator = spec.resolve(mix["generator"])
    feed = Prefetch(generator(mix, args.seed, kw["vocab_size"], batch, seq),
                    mix["prefetch"])
    lr = jnp.asarray(tr["learning_rate"], jnp.float32)
    tracer = DeviceTracer(run.cell["name"]) if args.trace else None
    steps = []

    def one(i, annotate):
        nonlocal params, buffers, opt_state
        t_in = time.monotonic()
        with annotate(f"bench.input {i}"):
            host = feed.get()
        t_start = time.monotonic()
        with annotate(f"bench.step {i}"):
            ids, labels = step.shard_batch(*host)
            loss, params, buffers, opt_state = step(
                params, buffers, opt_state, lr, jax.random.PRNGKey(i), ids,
                labels)
            t_sent = time.monotonic()
            loss = float(np.asarray(loss))   # the step ends here
        return host, {"index": i, "input_wait": t_start - t_in,
                      "start": t_start, "sent": t_sent,
                      "end": time.monotonic(), "loss": loss}

    quiet = lambda name: contextlib.nullcontext()   # noqa: E731
    try:
        # set-up: the first step compiles (or loads) the program, the second
        # proves it is steady
        first_batch, rec = one(0, quiet)
        run.loss_first = rec["loss"]
        log(f"step 0 (compiles): loss {rec['loss']:.4f} in "
            f"{rec['end'] - rec['start']:.1f}s")
        for i in range(1, 1 + tr["warm_steps"]):
            _, rec = one(i, quiet)
            log(f"step {i} (warm-up): loss {rec['loss']:.4f} in "
                f"{rec['end'] - rec['start']:.3f}s")
        run.compile_setup = run.clock.snapshot()
        run.t0 = time.monotonic()
        t_end = run.t0 + args.seconds
        # where the mix says how many steps a window holds it is a fixed
        # piece of work, and `--seconds` is the cap; else a window of time
        want = (mix.get("window") or {}).get("steps")
        trace_s = min(config["trace_seconds"], args.seconds)
        annotate = quiet
        i = 1 + tr["warm_steps"]
        while time.monotonic() < t_end and (want is None
                                            or len(steps) < want):
            if tracer is not None and tracer.due(
                    time.monotonic(), run.t0, t_end, trace_s, len(steps),
                    want):
                tracer.start_profiler()
                tracer.mark_start()
                annotate = jax.profiler.TraceAnnotation
            _, rec = one(i, annotate)
            steps.append(rec)
            i += 1
        run.t1 = time.monotonic()
        run.compile_window = run.clock.snapshot()
        if tracer is not None:
            tracer.stop()
    finally:
        feed.close()
    run.memory_peak_bytes = device.memory_peak_bytes()
    if tracer is not None:
        tracer.load_into(run)
    # the steps that ended inside the window; the one the window's end cut
    # is left out
    run.steps = [s for s in steps if s["end"] <= t_end]
    losses = [s["loss"] for s in run.steps]
    run.attempted = len(run.steps)
    run.failed = sum(not np.isfinite(x) for x in losses)
    log(f"window {run.seconds:.2f}s: {len(run.steps)} steps"
        + ("" if want is None else f" of the mix's {want}")
        + f", losses {run.loss_first:.4f} -> "
        f"{losses[-1] if losses else None}")
    # a stall shows as one long step: where in it the time went
    for s in sorted(steps, key=lambda s: s["start"] - s["end"])[:3]:
        log(f"longest steps: step {s['index']} at "
            f"+{s['start'] - run.t0:.1f}s took {s['end'] - s['start']:.3f}s "
            f"(hand-over {s['sent'] - s['start']:.3f}s, waiting for the "
            f"loss {s['end'] - s['sent']:.3f}s) after {s['input_wait']:.3f}s"
            " of input wait")
    del params, opt_state
    initial = weights.initial_arrays(config, args.seed)
    if losses:
        run.correct, run.compared = correct.training(
            config, initial, first_batch, run.loss_first, losses, log)
