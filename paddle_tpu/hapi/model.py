"""High-level Model API (reference: python/paddle/hapi/model.py —
Model.prepare/fit/evaluate/predict at :1082,1808; what the reference's
ResNet50 image classification runs through).

TPU design: fit() compiles ONE jitted train step (value_and_grad over
functional_call + optimizer.apply) and reuses it every batch; parameters,
optimizer slots and buffers live as device pytrees across steps (no
host<->device traffic except input batches and scalar logs). The eager
Layer tree is only touched when syncing state for save()/state_dict().
"""

from __future__ import annotations

import contextlib
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from ..enforce import PreconditionNotMetError, enforce

from .. import optimizer as opt_mod
from ..io import DataLoader, Dataset
from ..metric import Metric
from ..nn.layer.layers import Layer, functional_call, functional_train_graph
from ..random import rng_guard
from .callbacks import config_callbacks

__all__ = ["Model"]


def _timed_iter(it, timer, name):
    """Attribute the wall time spent WAITING on the input pipeline to a
    StepTimer phase (the reader span of profiler.Benchmark, unified with
    the observability step accounting)."""
    while True:
        with timer.phase(name):
            try:
                batch = next(it)
            except StopIteration:
                return
        yield batch


def _metric_update(m: Metric, pred, labels):
    """Reference contract (hapi/model.py): update(*to_list(compute(...))) —
    compute may return a single array or a tuple to splat into update."""
    res = m.compute(pred, *labels)
    if isinstance(res, tuple):
        return m.update(*res)
    return m.update(res)


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self.stop_training = False
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._compiled = False
        self._params = None
        self._buffers = None
        self._frozen = None
        self._opt_state = None
        self._train_step_fn = None
        self._eval_step_fn = None

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            self._metrics = []
        elif isinstance(metrics, Metric):
            self._metrics = [metrics]
        else:
            self._metrics = list(metrics)
        self._amp = amp_configs
        return self

    def _sync_from_network(self):
        self._params, self._frozen, self._buffers = functional_train_graph(self.network)
        if self._optimizer is not None and self._opt_state is None:
            self._opt_state = self._optimizer.init_state(self._params)

    def _sync_to_network(self):
        if self._params is None:
            return
        named = dict(self.network.named_parameters())
        for k, v in self._params.items():
            if k in named:
                named[k].value = v
        slots = {}
        for lp, sub in self.network.named_sublayers(include_self=True):
            for name in sub._buffers:
                slots[f"{lp}.{name}" if lp else name] = (sub, name)
        for k, v in (self._buffers or {}).items():
            if k in slots:
                sub, name = slots[k]
                sub._buffers[name] = v

    def _build_train_step(self):
        network, loss_fn, optimizer = self.network, self._loss, self._optimizer

        def step(params, frozen, buffers, opt_state, lr, key, inputs, labels):
            def compute_loss(p):
                with rng_guard(key):
                    merged = {**p, **frozen}
                    outputs, new_buffers = functional_call(
                        network, merged, buffers, *inputs)
                if not isinstance(outputs, (list, tuple)):
                    outputs = (outputs,)
                loss = loss_fn(*outputs, *labels)
                return loss, (outputs, new_buffers)

            (loss, (outputs, new_buffers)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            new_params, new_opt_state = optimizer.apply(params, grads, opt_state, lr)
            return new_params, new_buffers, new_opt_state, loss, outputs

        return jax.jit(step)

    def _build_eval_step(self):
        network = self.network
        loss_fn = self._loss

        def step(params, frozen, buffers, inputs, labels):
            merged = {**params, **frozen}
            outputs, _ = functional_call(network, merged, buffers, *inputs)
            if not isinstance(outputs, (list, tuple)):
                outputs = (outputs,)
            loss = loss_fn(*outputs, *labels) if (loss_fn and labels) else None
            return outputs, loss

        return jax.jit(step)

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, (list, tuple)):
            if len(batch) >= 2:
                return list(batch[:-1]), [batch[-1]]
            return list(batch), []
        return [batch], []

    def _to_loader(self, data, batch_size, shuffle, num_workers=0):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers)
        return data  # assume iterable of batches

    # -- training ------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            resilient=None):
        """Train the model. With ``resilient={"ckpt_dir": ..., ...}`` the
        loop runs under the fault-tolerant runtime
        (distributed.resilience.fit.FitResilience): crash-safe cadence
        checkpoints, resume + batch fast-forward from the last committed
        step on restart, a watchdog span around every train step, and a
        SIGTERM handler that commits one final checkpoint within
        FLAGS_preempt_grace_s and stops training cleanly. Resume needs a
        sized train loader (len()) to fast-forward mid-epoch."""
        enforce(self._optimizer is not None and self._loss is not None,
                "call prepare(optimizer, loss) first",
                error=PreconditionNotMetError, op="Model.fit")
        loader = self._to_loader(train_data, batch_size, shuffle, num_workers)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs, steps=steps,
                                verbose=verbose, save_freq=save_freq,
                                save_dir=save_dir, metrics=self._metrics,
                                log_freq=log_freq)
        self.network.train()
        self._sync_from_network()
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        self.stop_training = False

        # observability: with FLAGS_telemetry on, fit() accounts compile
        # vs steady-state step time and the data-wait phase, and emits a
        # fit_report event to the JSONL log at the end of training
        from ..flags import flag as _flag
        tele_timer = None
        if _flag("telemetry"):
            from ..observability import StepTimer
            tele_timer = StepTimer()

        res = None
        if resilient:
            from ..distributed.resilience.fit import FitResilience
            res = FitResilience(self, dict(resilient))
            res.__enter__()
        try:
            cbks.on_train_begin()
            if res is not None:
                start_step = res.resume()
                enforce(start_step == 0 or steps is not None,
                        "resilient resume needs a sized train loader to "
                        "fast-forward to the checkpointed step",
                        error=PreconditionNotMetError, op="Model.fit")
                step_key = jax.random.PRNGKey(res.seed)
            else:
                start_step = 0
                step_key = jax.random.PRNGKey(
                    np.random.randint(0, 2**31 - 1))
            skip_epochs = start_step // steps if (res and steps) else 0
            skip_batches = start_step % steps if (res and steps) else 0
            # only a Dataset input gets wrapped in a loader that honors the
            # `shuffle` arg (lists/iterables keep their own fixed order; a
            # user-built DataLoader's order is their responsibility — see
            # the docstring)
            if skip_batches and shuffle and isinstance(train_data, Dataset):
                import warnings
                warnings.warn(
                    "resilient mid-epoch resume fast-forwards "
                    f"{skip_batches} batches, but shuffle=True reshuffles "
                    "the loader on restart — the skipped subset differs "
                    "from the one trained before the crash. Pass "
                    "shuffle=False (or a deterministically-ordered "
                    "DataLoader) for exact resume.")
            global_step = 0
            for epoch in range(epochs):
                if res is not None and epoch < skip_epochs:
                    global_step += steps  # already trained before restart
                    continue
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                epoch_logs = {}
                # resilient fast-forward runs on the RAW loader, BEFORE
                # the prefetch wrapper: skipped batches must not pay a
                # host->device transfer just to be dropped
                batches = iter(loader)
                epoch_skip = (skip_batches if res is not None
                              and epoch == skip_epochs else 0)
                for _ in range(epoch_skip):
                    next(batches, None)
                    global_step += 1
                # device double-buffering: the next batch's host->device
                # DMA rides under the current step's compute (async
                # device_put) instead of serializing before each dispatch
                from ..io import prefetch_to_device
                feed = prefetch_to_device(batches, size=2)
                if tele_timer is not None:
                    feed = _timed_iter(feed, tele_timer, "data")
                for step, batch in enumerate(feed, start=epoch_skip):
                    cbks.on_train_batch_begin(step)
                    inputs, labels = self._split_batch(batch)
                    lr = self._optimizer.get_lr()
                    key = jax.random.fold_in(step_key, global_step)
                    with (tele_timer.step() if tele_timer is not None
                          else contextlib.nullcontext()):
                        with (res.watch() if res is not None
                              else contextlib.nullcontext()):
                            (self._params, self._buffers, self._opt_state,
                             loss, outputs) = self._train_step_fn(
                                self._params, self._frozen, self._buffers,
                                self._opt_state,
                                jnp.asarray(lr, jnp.float32), key,
                                tuple(jnp.asarray(x) for x in inputs),
                                tuple(jnp.asarray(y) for y in labels))
                        # the fetch is INSIDE the step span: without it
                        # the timer would measure dispatch, not execution
                        loss_val = float(loss)
                    logs = {"loss": loss_val, "lr": lr}
                    for m in self._metrics:
                        r = _metric_update(m, outputs[0], labels)
                        logs[m.name() if isinstance(m.name(), str)
                             else m.name()[0]] = r
                    epoch_logs = logs
                    global_step += 1
                    cbks.on_train_batch_end(step, logs)
                    if res is not None and res.after_step():
                        self.stop_training = True  # preempted: final
                        #                            checkpoint is committed
                    if self.stop_training:
                        break
                if res is not None and res.preempted:
                    break  # don't burn the grace budget on metrics/eval —
                    #        the final checkpoint is already committed
                for m in self._metrics:
                    nm = m.name() if isinstance(m.name(), str) else m.name()[0]
                    epoch_logs[nm] = m.accumulate()
                cbks.on_epoch_end(epoch, epoch_logs)
                if eval_data is not None and (epoch + 1) % eval_freq == 0:
                    eval_logs = self.evaluate(eval_data, batch_size=batch_size,
                                              verbose=0,
                                              num_workers=num_workers)
                    cbks.on_eval_end({f"eval_{k}": v
                                      for k, v in eval_logs.items()})
                if self.stop_training:
                    break
            if res is not None:
                res.finalize()
        finally:
            if res is not None:
                res.__exit__(None, None, None)
        cbks.on_train_end()
        if tele_timer is not None:
            self.last_fit_telemetry = tele_timer.report()
            from ..observability import get_event_log
            log = get_event_log()
            if log is not None:
                log.emit("fit_report", report=self.last_fit_telemetry)
        self._sync_to_network()
        hist = [c for c in cbks.callbacks if type(c).__name__ == "History"]
        return hist[0].history if hist else None

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = self._to_loader(eval_data, batch_size, False, num_workers)
        was_training = self.network.training
        self.network.eval()
        if self._params is None:
            self._sync_from_network()
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            inputs, labels = self._split_batch(batch)
            outputs, loss = self._eval_step_fn(
                self._params, self._frozen, self._buffers,
                tuple(jnp.asarray(x) for x in inputs),
                tuple(jnp.asarray(y) for y in labels))
            if loss is not None:
                losses.append(float(loss))
            for m in self._metrics:
                _metric_update(m, outputs[0], labels)
        logs = {}
        if losses:
            logs["loss"] = float(np.mean(losses))
        for m in self._metrics:
            nm = m.name() if isinstance(m.name(), str) else m.name()[0]
            logs[nm] = m.accumulate()
        if was_training:
            self.network.train()
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None):
        loader = self._to_loader(test_data, batch_size, False, num_workers)
        was_training = self.network.training
        self.network.eval()
        if self._params is None:
            self._sync_from_network()
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        outs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch)
            outputs, _ = self._eval_step_fn(
                self._params, self._frozen, self._buffers,
                tuple(jnp.asarray(x) for x in inputs), ())
            outs.append(tuple(np.asarray(o) for o in outputs))
        if was_training:
            self.network.train()
        if stack_outputs:
            n_out = len(outs[0])
            return [np.concatenate([o[i] for o in outs]) for i in range(n_out)]
        return outs

    def train_batch(self, inputs, labels=None, update=True):
        if self._params is None:
            self._sync_from_network()
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else ([labels] if labels is not None else [])
        key = jax.random.PRNGKey(np.random.randint(0, 2**31 - 1))
        (self._params, self._buffers, self._opt_state, loss, _) = self._train_step_fn(
            self._params, self._frozen, self._buffers, self._opt_state,
            jnp.asarray(self._optimizer.get_lr(), jnp.float32), key,
            tuple(jnp.asarray(x) for x in inputs),
            tuple(jnp.asarray(y) for y in labels))
        return float(loss)

    def eval_batch(self, inputs, labels=None):
        if self._params is None:
            self._sync_from_network()
        if self._eval_step_fn is None:
            self._eval_step_fn = self._build_eval_step()
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else ([labels] if labels is not None else [])
        was_training = self.network.training
        self.network.eval()
        outputs, loss = self._eval_step_fn(
            self._params, self._frozen, self._buffers,
            tuple(jnp.asarray(x) for x in inputs),
            tuple(jnp.asarray(y) for y in labels))
        if was_training:
            self.network.train()
        return float(loss) if loss is not None else [np.asarray(o) for o in outputs]

    def predict_batch(self, inputs):
        return self.eval_batch(inputs)

    # -- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io import save
        self._sync_to_network()
        save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            opt_state = {"opt_state": self._opt_state,
                         **self._optimizer.state_dict()}
            save(opt_state, path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load
        state = load(path + ".pdparams")
        self.network.set_state_dict(state)
        self._params = None  # force re-sync on next step
        self._opt_state = None
        # jitted closures capture frozen params/buffers — rebuild them too
        self._train_step_fn = None
        self._eval_step_fn = None
        if not reset_optimizer and os.path.exists(path + ".pdopt") and self._optimizer:
            opt_state = load(path + ".pdopt")
            self._opt_state = opt_state.pop("opt_state", None)
            self._optimizer.set_state_dict(opt_state)

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        from .summary import summary
        return summary(self.network, input_size, dtype)
