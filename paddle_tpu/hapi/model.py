"""paddle.Model: the high-level train/eval/predict API.

Reference parity: python/paddle/hapi/model.py:1037 (Model), fit:1732,
train_batch:1178, DynamicGraphAdapter:763 vs StaticGraphAdapter:286.

TPU-native design: there is ONE adapter — the compiled-step adapter. Each
train/eval batch executes a single cached XLA program (forward + loss + grads
+ optimizer update, buffers donated) built from functional_call: one whole
XLA program a step, at the hapi level.
Eager fallback (`compiled=False`) runs the tape for debugging.
"""
from __future__ import annotations

import contextlib
import functools
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng
from ..core.functional import (
    functional_call,
    load_state_arrays,
    state_dict_arrays,
    tree_to_tensors,
)
from ..core.tensor import Tensor
from ..io import DataLoader, Dataset, DistributedBatchSampler
from ..metric import Metric
from ..optimizer.lr import LRScheduler
from ..profiler.timer import benchmark
from ..profiler.tracing import train_tracer
from . import callbacks as cbks_mod


def to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class _StaticGraphAdapter:
    """Static-mode driver for Model (reference hapi/model.py:286
    StaticGraphAdapter vs :763 DynamicGraphAdapter).

    Under `paddle.enable_static()`, Model.prepare routes batches here: the
    network forward + loss are CAPTURED once into a `static.Program` (op-log
    dry run on placeholder feeds), and training differentiates the program's
    pure replay function — capture once, `jax.value_and_grad` over the
    replay, one XLA executable per feed signature. The loss trajectory is
    identical to dynamic mode because the replay computes the same math on
    the same parameter values.

    Un-frozen state (round 5): RNG ops are captured as RNG *slots* re-keyed
    every step from the same per-step key stream the dynamic adapter uses,
    so dropout masks vary per step; buffer mutations (BN running stats) are
    recorded as state writes, fetched each step and written back — static
    training updates BN state like the reference's in-program state ops."""

    def __init__(self, model):
        self.model = model
        self._steps = {}  # feed signature -> (jit step, meta)

    def _capture(self, ins, labs):
        from ..static import program as SP

        model = self.model
        net = model.network
        prog = SP.Program()
        with SP.program_guard(prog):
            xts = [
                SP.data(f"x{i}", list(a.shape), str(a.dtype))
                for i, a in enumerate(ins)
            ]
            yts = [
                SP.data(f"y{i}", list(a.shape), str(a.dtype))
                for i, a in enumerate(labs)
            ]
            net.train()
            outs = net(*xts)
            loss = model._apply_loss(outs, yts)
        feed_names = [f"x{i}" for i in range(len(ins))] + [
            f"y{i}" for i in range(len(labs))
        ]
        out_list = to_list(outs)
        fetch_ids = [id(loss._array)] + [id(o._array) for o in out_list]
        # buffer updates (BN stats) ride as extra fetches, written back per step
        fetch_ids += [aid for aid, _ in prog._state_writes]
        externals, run = prog._plan(feed_names, fetch_ids)
        name_by_id = {
            id(p): n for n, p in net.named_parameters_dict().items()
        }
        trainables = [
            (pos, name_by_id[id(t)])
            for pos, (aid, t) in enumerate(externals)
            if isinstance(t, Tensor) and id(t) in name_by_id and not t.stop_gradient
        ]
        return prog, externals, run, trainables, len(out_list)

    def train_batch(self, ins, labs):
        model = self.model
        net = model.network
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in ins + labs)
        if sig not in self._steps:
            prog, externals, run, trainables, n_outs = self._capture(ins, labs)
            opt = model._optimizer
            tr_pos = [p for p, _ in trainables]
            tr_names = [n for _, n in trainables]

            def step(params, opt_state, lr, feed_vals, ext_rest):
                def loss_fn(pd):
                    ev = list(ext_rest)
                    for pos, name in zip(tr_pos, tr_names):
                        ev[pos] = pd[name]
                    res = run(feed_vals, ev)
                    return res[0], (res[1 : 1 + n_outs], res[1 + n_outs :])

                (loss, (outs, bufs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                new_params, new_opt = opt.apply_gradients_arrays(
                    params, grads, opt_state, lr
                )
                return loss, outs, bufs, new_params, new_opt

            # jaxlint: disable=JL004 -- static-program adapter is single-device (no mesh shardings); the gate only exists for the host-platform-mesh sharded-donation miscompile. Not IR-checkable: the adapter jit is built per traced static Program, not one of hlolint's registered programs
            jstep = jax.jit(step, donate_argnums=(0, 1))
            self._steps[sig] = (jstep, prog, externals, tr_pos, tr_names)
        jstep, prog, externals, tr_pos, tr_names = self._steps[sig]
        # one step key per batch, exactly like the dynamic adapter (it hands
        # the key to functional_call; we fold it into the program's RNG
        # slots the same way key_scope would) — the global stream advances
        # identically under either adapter, so fit trajectories match
        step_key = rng.next_key()
        named = net.named_parameters_dict()
        params = {n: named[n]._array for n in tr_names}
        if model._opt_state is None:
            model._opt_state = model._optimizer.state_arrays_for(named)
        opt_state = {
            n: model._opt_state.get(n, {}) for n in tr_names
        }
        from ..static.program import Program

        prog_vals = Program._external_values(externals)
        prog_vals = prog._substitute_rng(externals, prog_vals, step_key)
        lr = jnp.asarray(model._optimizer.get_lr(), jnp.float32)
        loss, outs, bufs, new_params, new_opt = jstep(
            params, opt_state, lr, list(ins) + list(labs), prog_vals
        )
        for n, v in new_params.items():
            named[n]._array = v
        # persist buffer mutations (BN running stats) computed this step
        for (aid, target), v in zip(prog._state_writes, bufs):
            target._array = v
        model._opt_state.update(new_opt)
        model._optimizer._step_count += 1
        model._optimizer.sync_state_arrays(named, model._opt_state)
        metrics = model._update_metrics(list(outs), labs)
        loss_val = [float(np.asarray(loss))]
        return (loss_val, metrics) if metrics else loss_val


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = to_list(inputs)
        self._labels = to_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._compiled_steps = {}
        self._opt_state = None
        self.stop_training = False
        self._compiled = True
        self._static_adapter = None
        self.mode = "train"
        # observability (profiler/tracing.py + callbacks.TrainMonitor):
        # all dormant — one pointer test per step — unless the process
        # train tracer / a monitor turns them on
        self._in_fit = False          # fit emits the train_step span itself
        self._trace_phases = {}       # last step's {phase: (t0, t1)}
        self._trace_sid = None        # last step's trace id, unclaimed
        self._trace_args = {}         # and what its launch stamped
        self._jit_traces = 0          # bumped at TRACE time in step bodies
        self._monitor_grad_norm = False
        self._last_grad_norm = None

    # ---- preparation -------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None, compiled=True):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric must be paddle_tpu.metric.Metric, got {type(m)}")
        self._compiled = compiled
        self._compiled_steps = {}
        self._jit_traces = 0
        # adapter selection (reference model.py:286): static mode active at
        # prepare() time routes batches through the captured-Program path
        from ..static.program import in_static_mode

        self._static_adapter = _StaticGraphAdapter(self) if in_static_mode() else None

    # ---- compiled step construction ----------------------------------------
    def _apply_loss(self, outputs, labels):
        outs = to_list(outputs)
        labs = to_list(labels)
        losses = self._loss(*(outs + labs))
        losses = to_list(losses)
        total = losses[0]
        for l in losses[1:]:
            total = total + l
        from ..ops.math import mean as _mean

        if total.size != 1:
            total = _mean(total)
        return total

    def _dist_mesh(self):
        """The active fleet/SPMD mesh, if Model.fit should train sharded
        (the reference hapi's automatic fleet integration: Model.fit +
        Fleet Sharding scaling). Pipeline degrees are the
        fleet PipelineParallel wrapper's job, not hapi's."""
        from ..distributed.mesh import get_mesh

        mesh = get_mesh()
        if mesh is None:
            return None
        shape = dict(mesh.shape)
        if shape.get("pp", 1) > 1:
            return None
        if all(shape.get(ax, 1) <= 1 for ax in ("dp", "mp", "sharding", "sp")):
            return None
        return mesh

    def _note_trace(self):
        """Runs at XLA TRACE time only (a Python side effect inside the
        step bodies, like the serving engine's ``jit_traces`` counter) —
        the recompile sentinel's raw signal. Steady state means
        `jit_traces == len(_compiled_steps)`; a surplus is a re-trace of
        an existing program (an input's shape/dtype drifting per step)."""
        self._jit_traces += 1

    @property
    def jit_traces(self):
        return self._jit_traces

    @property
    def jit_retraces(self):
        """Traces beyond one-per-compiled-program — 0 in steady state.
        `callbacks.TrainMonitor` warns when this grows after warmup."""
        return max(0, self._jit_traces - len(self._compiled_steps))

    def _make_train_step(self, n_inputs, n_labels, with_grad_norm=False):
        net = self.network
        optimizer = self._optimizer
        mesh = self._dist_mesh()

        def step(params, buffers, opt_state, lr, key, *arrays):
            self._note_trace()
            in_arrays = arrays[:n_inputs]
            lab_arrays = arrays[n_inputs:]

            def loss_fn(p):
                outs, new_buf = functional_call(
                    net, p, buffers, args=in_arrays, rng_key=key, training=True
                )
                from ..core import autograd

                with autograd.trace_mode():
                    total = self._apply_loss(
                        tree_to_tensors(outs), [Tensor._from_op(a) for a in lab_arrays]
                    )
                return total._array, (outs, new_buf)

            (loss, (outs, new_buf)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            new_params, new_opt = optimizer.apply_gradients_arrays(
                params, grads, opt_state, lr
            )
            if with_grad_norm:
                # global grad norm INSIDE the one compiled program (free
                # relative to a step; requested by TrainMonitor(grad_norm))
                gn = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)
                ))
                return loss, outs, new_buf, new_params, new_opt, gn
            return loss, outs, new_buf, new_params, new_opt

        if mesh is None:
            # jaxlint: disable=JL004 -- mesh is None here by the guard above: single-device jit, unsharded buffers; the sharded path below uses the gate AND is donation-verified by IR contract IR002 on the lowered spmd train step (tests/test_ir_contracts.py)
            return jax.jit(step, donate_argnums=(0, 2))

        # ---- sharded step: GSPMD over the fleet mesh ----------------------
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.spmd import build_state_shardings

        zero = 1 if dict(mesh.shape).get("sharding", 1) > 1 else 0
        _, pspecs, bspecs, ospecs = build_state_shardings(
            net, self._optimizer, mesh, zero
        )
        ns = lambda s: NamedSharding(mesh, s)
        batch_in = tuple(ns(P("dp")) for _ in range(n_inputs + n_labels))
        in_sh = (pspecs, bspecs, ospecs, ns(P()), ns(P())) + batch_in
        # outputs (for metrics) take compiler-chosen shardings (None)
        out_sh = (ns(P()), None, bspecs, pspecs, ospecs)
        if with_grad_norm:
            out_sh = out_sh + (ns(P()),)
        from ..parallel.spmd import mesh_donate_argnums

        return jax.jit(
            step, in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=mesh_donate_argnums((0, 2)),
        )

    def _make_eval_step(self, n_inputs, n_labels, with_loss):
        net = self.network

        def step(params, buffers, key, *arrays):
            self._note_trace()
            in_arrays = arrays[:n_inputs]
            lab_arrays = arrays[n_inputs:]
            outs, _ = functional_call(
                net, params, buffers, args=in_arrays, rng_key=key, training=False
            )
            if with_loss:
                from ..core import autograd

                with autograd.trace_mode():
                    total = self._apply_loss(
                        tree_to_tensors(outs), [Tensor._from_op(a) for a in lab_arrays]
                    )
                return outs, total._array
            return outs, None

        return jax.jit(step)

    def _shapes_key(self, mode, arrays):
        return (mode,) + tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    @staticmethod
    def _as_arrays(xs):
        out = []
        for x in to_list(xs):
            if isinstance(x, Tensor):
                out.append(x._array)
            else:
                a = np.asarray(x)
                if a.dtype == np.float64:
                    a = a.astype(np.float32)
                out.append(jnp.asarray(a))
        return out

    # ---- batch-level API ----------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        ins = self._as_arrays(inputs)
        labs = self._as_arrays(labels)
        if getattr(self, "_static_adapter", None) is not None:
            return self._static_adapter.train_batch(ins, labs)
        if not self._compiled:
            return self._train_batch_eager(ins, labs)
        tr = train_tracer()
        t_shard0 = time.monotonic() if tr is not None else 0.0
        params, buffers = state_dict_arrays(self.network)
        if self._opt_state is None:
            self._opt_state = self._optimizer.state_arrays_for(
                self.network.named_parameters_dict()
            )
        mesh = self._dist_mesh()
        if mesh is not None:
            dp = dict(mesh.shape).get("dp", 1)
            if dp > 1 and ins and ins[0].shape[0] % dp:
                raise ValueError(
                    f"Model.train_batch: batch size {ins[0].shape[0]} is not "
                    f"divisible by the mesh dp degree {dp} — use a divisible "
                    "batch_size (fit drops the ragged final batch "
                    "automatically when a mesh is active)"
                )
            # loader outputs are committed to one device; place them on the
            # mesh (jit refuses to re-shard committed args)
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(mesh, P("dp"))
            ins = [jax.device_put(a, sh) for a in ins]
            labs = [jax.device_put(a, sh) for a in labs]
        want_gn = self._monitor_grad_norm
        key = (self._shapes_key("train", ins + labs), id(mesh), want_gn)
        if key not in self._compiled_steps:
            self._compiled_steps[key] = self._make_train_step(
                len(ins), len(labs), with_grad_norm=want_gn
            )
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        if tr is not None:
            # the dispatch runs under the xplane join annotation so a
            # jax.profiler capture of this fit joins back to the host
            # train_step spans by step id (xplane.join_engine_steps), and
            # stamps the span's `launch_ns` (`Tracer.launch`)
            sid = tr.next_step_id()
            self._trace_args = {}
            ann = tr.launch(sid, self._trace_args)
        else:
            sid, ann = None, contextlib.nullcontext()
        t_disp0 = time.monotonic() if tr is not None else 0.0
        with ann:
            res = self._compiled_steps[key](
                params, buffers, self._opt_state, lr, rng.next_key(),
                *ins, *labs
            )
        if want_gn:
            loss, outs, new_buf, new_params, new_opt, gn = res
            self._last_grad_norm = gn
        else:
            loss, outs, new_buf, new_params, new_opt = res
            self._last_grad_norm = None
        t_sync0 = time.monotonic() if tr is not None else 0.0
        load_state_arrays(self.network, params=new_params, buffers=new_buf)
        self._opt_state = new_opt
        self._optimizer._step_count += 1
        # keep eager accumulators in sync so state_dict()/save emit real slots
        self._optimizer.sync_state_arrays(
            self.network.named_parameters_dict(), new_opt
        )
        metrics = self._update_metrics(outs, labs)
        loss_val = [float(np.asarray(loss))]
        if tr is not None:
            # fit wraps this step with the data/callback phases and emits
            # the span itself; a standalone train_batch closes it here
            self._trace_phases = {"shard": (t_shard0, t_disp0),
                                  "dispatch": (t_disp0, t_sync0),
                                  "sync": (t_sync0, time.monotonic())}
            self._trace_sid = sid
            if not self._in_fit:
                tr.record_train_step(sid, self._trace_phases, {
                    "batch_size": int(ins[0].shape[0]) if ins else 0,
                    "loss": loss_val[0],
                    **self._trace_args,
                })
                self._trace_sid = None
        if metrics:
            return loss_val, metrics
        return loss_val

    def _train_batch_eager(self, ins, labs):
        outs = self.network(*[Tensor._from_op(a) for a in ins])
        total = self._apply_loss(outs, [Tensor._from_op(a) for a in labs])
        total.backward()
        self._optimizer.step()
        self._optimizer.clear_grad()
        metrics = self._update_metrics(
            jax.tree_util.tree_map(
                lambda t: t._array if isinstance(t, Tensor) else t,
                outs,
                is_leaf=lambda t: isinstance(t, Tensor),
            ),
            labs,
        )
        loss_val = [float(np.asarray(total._array))]
        return (loss_val, metrics) if metrics else loss_val

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        self._last_grad_norm = None
        ins = self._as_arrays(inputs)
        labs = self._as_arrays(labels)
        params, buffers = state_dict_arrays(self.network)
        with_loss = self._loss is not None and len(labs) > 0
        key = self._shapes_key(("eval", with_loss), ins + labs)
        if key not in self._compiled_steps:
            self._compiled_steps[key] = self._make_eval_step(len(ins), len(labs), with_loss)
        outs, loss = self._compiled_steps[key](params, buffers, rng.next_key(), *ins, *labs)
        metrics = self._update_metrics(outs, labs)
        if with_loss:
            return [float(np.asarray(loss))], metrics
        return metrics

    def predict_batch(self, inputs):
        self.network.eval()
        ins = self._as_arrays(inputs)
        params, buffers = state_dict_arrays(self.network)
        key = self._shapes_key("predict", ins)
        if key not in self._compiled_steps:
            self._compiled_steps[key] = self._make_eval_step(len(ins), 0, False)
        outs, _ = self._compiled_steps[key](params, buffers, rng.next_key(), *ins)
        return to_list(jax.tree_util.tree_map(np.asarray, outs))

    def _update_metrics(self, outs, labs):
        if not self._metrics:
            return []
        out_tensors = to_list(tree_to_tensors(outs))
        lab_tensors = [Tensor._from_op(a) for a in labs]
        results = []
        for m in self._metrics:
            state = m.compute(*(out_tensors + lab_tensors))
            r = m.update(*to_list(state))
            results.append(r)
        return results

    # ---- loop API -----------------------------------------------------------
    def fit(
        self,
        train_data=None,
        eval_data=None,
        batch_size=1,
        epochs=1,
        eval_freq=1,
        log_freq=10,
        save_dir=None,
        save_freq=1,
        verbose=2,
        drop_last=False,
        shuffle=True,
        num_workers=0,
        callbacks=None,
        accumulate_grad_batches=1,
        num_iters=None,
    ):
        train_loader = self._to_loader(
            train_data, batch_size, shuffle, drop_last, num_workers, train=True
        )
        eval_loader = self._to_loader(eval_data, batch_size, False, False, num_workers) if eval_data is not None else None

        do_eval = eval_loader is not None
        steps = self._len_or_none(train_loader)
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps, log_freq=log_freq,
            save_freq=save_freq, save_dir=save_dir, verbose=verbose,
            metrics=self._metrics_name(),
        )
        cbks.on_begin("train")
        try:
            for epoch in range(epochs):
                if self.stop_training:
                    break
                cbks.on_epoch_begin(epoch)
                logs = self._run_one_epoch(train_loader, cbks, "train", num_iters)
                cbks.on_epoch_end(epoch, logs)
                if do_eval and (epoch % eval_freq == 0 or epoch == epochs - 1):
                    eval_steps = self._len_or_none(eval_loader)
                    cbks.on_begin("eval", {"steps": eval_steps, "metrics": self._metrics_name()})
                    eval_logs = self._run_one_epoch(eval_loader, cbks, "eval")
                    cbks.on_end("eval", eval_logs)
        except BaseException:
            # on_train_end will never run: give callbacks that flipped
            # process/model state on (TrainMonitor's debug switches) the
            # chance to restore it before the exception leaves fit
            cbks.on_interrupted("train")
            raise
        cbks.on_end("train", logs)
        return self

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2, num_workers=0, callbacks=None, num_iters=None):
        loader = self._to_loader(eval_data, batch_size, False, False, num_workers)
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, steps=self._len_or_none(loader),
            log_freq=log_freq, verbose=verbose, metrics=self._metrics_name(),
        )
        cbks.on_begin("eval")
        logs = self._run_one_epoch(loader, cbks, "eval", num_iters)
        cbks.on_end("eval", logs)
        result = {}
        if self._loss is not None:
            result["loss"] = logs.get("loss")
        for m in self._metrics:
            for name, val in zip(to_list(m.name()), to_list(m.accumulate())):
                result[name] = val
        return result

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False, verbose=1, callbacks=None):
        loader = self._to_loader(test_data, batch_size, False, False, num_workers)
        outputs = []
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, steps=self._len_or_none(loader), verbose=verbose
        )
        cbks.on_begin("predict")
        for step, data in enumerate(loader):
            data = to_list(data)
            n_in = len(self._inputs) or (len(data) - 1 if len(data) > 1 else 1)
            outs = self.predict_batch(data[:n_in])
            outputs.append(outs)
            cbks.on_batch_end("predict", step, {"step": step})
        cbks.on_end("predict")
        # transpose list-of-batches to per-output lists
        outputs = list(zip(*outputs))
        if stack_outputs:
            outputs = [np.concatenate(o, axis=0) for o in outputs]
        else:
            outputs = [list(o) for o in outputs]
        return outputs

    def _run_one_epoch(self, loader, cbks, mode, num_iters=None):
        metrics_names = self._metrics_name()
        for m in self._metrics:
            m.reset()
        logs = {}
        # train epochs drive the profiler.timer reader/step clocks
        # (reference hapi behavior): benchmark().state() reports
        # reader_cost/batch_cost/ips for TrainMonitor and operators, and
        # the tracer's `data` phase is the same reader window
        tr = train_tracer() if mode == "train" else None
        bm = benchmark() if mode == "train" else None
        if bm is not None:
            bm.begin()
        self._in_fit = True
        try:
            step = -1
            it = iter(loader)
            while True:
                if bm is not None:
                    bm.before_reader()
                t_data0 = time.monotonic() if tr is not None else 0.0
                try:
                    data = next(it)
                except StopIteration:
                    break
                if bm is not None:
                    bm.after_reader()
                t_data1 = time.monotonic() if tr is not None else 0.0
                step += 1
                if num_iters is not None and step >= num_iters:
                    break
                cbks.on_batch_begin(mode, step, logs)
                data = to_list(data)
                n_in = len(self._inputs) or (len(data) - len(self._labels) if self._labels else len(data) - 1)
                if n_in <= 0:
                    n_in = len(data) - 1 if len(data) > 1 else len(data)
                ins, labs = data[:n_in], data[n_in:]
                self._trace_sid = None
                if mode == "train":
                    result = self.train_batch(ins, labs)
                    if isinstance(self._optimizer._learning_rate, LRScheduler):
                        self._optimizer._learning_rate.step()
                else:
                    result = self.eval_batch(ins, labs)
                t_cb0 = time.monotonic() if tr is not None else 0.0
                batch_size = len(to_list(ins)[0]) if ins else 0
                logs = self._merge_logs(result, metrics_names, step, batch_size)
                cbks.on_batch_end(mode, step, logs)
                if bm is not None:
                    bm.step(num_samples=batch_size)
                if tr is not None and self._trace_sid is not None:
                    # one train_step span per fit step: the reader window,
                    # the shard/dispatch/sync phases train_batch deposited,
                    # and the callback tail (merge + logging + callbacks)
                    phases = dict(self._trace_phases)
                    phases["data"] = (t_data0, t_data1)
                    phases["callback"] = (t_cb0, time.monotonic())
                    tr.record_train_step(self._trace_sid, phases, {
                        "batch": step,
                        "batch_size": batch_size,
                        "loss": logs.get("loss"),
                        **self._trace_args,
                    })
                    self._trace_sid = None
                if mode == "train" and self.stop_training:
                    # a callback (TrainMonitor nan_action="stop",
                    # EarlyStopping) asked mid-epoch: don't run the rest
                    # of the epoch on state it already condemned. Train
                    # only — an eval epoch must see every sample
                    break
        finally:
            self._in_fit = False
        self._reset_nothing = None
        return logs

    def _merge_logs(self, result, metrics_names, step, batch_size):
        logs = {"step": step, "batch_size": batch_size}
        if isinstance(result, tuple):
            losses, metrics = result
            logs["loss"] = losses[0] if isinstance(losses, list) else losses
        elif isinstance(result, list) and self._loss is not None:
            # train/eval path without metrics: the list is the loss values
            logs["loss"] = result[0]
        if self._last_grad_norm is not None:
            # computed in-program when TrainMonitor(grad_norm=True) asked;
            # the host value is free here (the loss sync already ran)
            logs["grad_norm"] = float(np.asarray(self._last_grad_norm))
        for m in self._metrics:
            for name, val in zip(to_list(m.name()), to_list(m.accumulate())):
                logs[name] = val
        return logs

    def _metrics_name(self):
        names = ["loss"] if self._loss else []
        for m in self._metrics:
            names.extend(to_list(m.name()))
        return names

    def _len_or_none(self, loader):
        try:
            return len(loader)
        except TypeError:
            return None

    def _to_loader(self, data, batch_size, shuffle, drop_last, num_workers,
                   train=False):
        if data is None or isinstance(data, DataLoader):
            return data
        if train and not drop_last and self._dist_mesh() is not None:
            # TRAIN only: a ragged final batch cannot shard over the dp
            # axis; the reference pads via DistributedBatchSampler —
            # dropping keeps step semantics exact. eval/predict steps are
            # unsharded and must see every sample.
            drop_last = True
        if isinstance(data, Dataset):
            try:
                from ..distributed import get_world_size

                dist = get_world_size() > 1
            except Exception:
                dist = False
            if dist:
                sampler = DistributedBatchSampler(
                    data, batch_size=batch_size, shuffle=shuffle, drop_last=drop_last
                )
                return DataLoader(
                    data, batch_sampler=sampler, num_workers=num_workers
                )
            return DataLoader(
                data, batch_size=batch_size, shuffle=shuffle,
                drop_last=drop_last, num_workers=num_workers,
            )
        raise TypeError(f"unsupported data type {type(data)}")

    # ---- persistence --------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io import save as fsave

        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        fsave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            fsave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as fload

        self.network.set_state_dict(fload(path + ".pdparams"))
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and os.path.exists(opt_path):
            self._optimizer.set_state_dict(fload(opt_path))
        # re-seeded from optimizer accumulators on the next train_batch via
        # Optimizer.state_arrays_for (set_state_dict filled _accumulators)
        self._opt_state = None

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from .summary import summary as _summary

        return _summary(self.network, input_size, dtype)
