"""paddle_tpu.jit — compiled execution.

The reference's jit stack (SURVEY §3.5: SOT bytecode tracing → PIR program →
interpreter, plus CINN fusion) collapses on TPU into jax.jit: Python is traced
directly, XLA is the fusion compiler, and the compiled-program cache
(_ExecutorCache analogue) is jax's jit cache keyed on shapes/dtypes.

Exports:
- ``to_static``: decorate a function or Layer for compiled execution
  (parity: paddle.jit.to_static, jit/api.py:135).
- ``TrainStep``: whole-train-step compilation — forward, backward, optimizer
  update, buffer (BN stat) update in ONE XLA program, the idiomatic TPU
  replacement for the reference's per-op eager dispatch loop (§3.1/§3.2).
- ``save``/``load``: export a compiled callable's weights + config.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as _rng
from ..nn.module import Layer, functional_call
from ..observability.trace import PROFILE_TRACER
from ..optimizer.optimizer import Optimizer

__all__ = ["to_static", "TrainStep", "EvalStep", "PipelineTrainStep",
           "not_to_static", "save", "load", "InputSpec", "TranslatedLayer"]

from .save_load import InputSpec, TranslatedLayer, load, save  # noqa: E402,F401


def to_static(function=None, input_spec=None, full_graph=True, backend=None,
              **kwargs):
    """Compile a function or Layer.forward with jax.jit.

    Unlike the reference there are no graph breaks: anything jax can't trace
    raises — the same strictness as SOT's full_graph=True mode.
    """

    def deco(fn):
        if isinstance(fn, Layer):
            layer = fn
            @functools.partial(jax.jit)
            def _apply(state, *args):
                out, _ = functional_call(layer, state, *args, training=layer.training)
                return out

            @functools.wraps(layer.forward)
            def wrapper(*args):
                return _apply(layer.state_dict(), *args)

            wrapper.__wrapped_layer__ = layer
            return wrapper
        jitted = jax.jit(fn)

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            return jitted(*args, **kw)

        wrapper.__jit__ = jitted
        return wrapper

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn.__not_to_static__ = True
    return fn


class TrainStep:
    """One-jit training step over a mutable Layer + Optimizer.

    Usage::

        step = TrainStep(model, opt, loss_fn)   # loss_fn(output, *labels)
        loss = step(inputs, labels)             # updates model & opt in place

    ``loss_fn`` receives the model output and the remaining batch elements;
    set ``n_inputs`` if the model takes more than one input tensor.
    The compiled program: forward + vjp backward + clip + optimizer + buffer
    writeback, all fused by XLA; params/opt-state buffers are donated so
    updates are in-place in HBM.

    While a JAX profiler session is on, each call records a ``step`` span
    with its phases ``step_args``, ``dispatch`` and ``writeback`` on the
    ``train`` track of ``observability.PROFILE_TRACER`` (``train.step``
    ... in the profiler's trace), and a ``compile`` instant with the
    ``compiles`` counter when the call compiled (OBSERVABILITY.md).
    """

    def __init__(self, model: Layer, optimizer: Optimizer, loss_fn: Callable,
                 n_inputs: int = 1, has_aux: bool = False, donate: bool = True):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.n_inputs = n_inputs
        self.has_aux = has_aux
        self._opt_state = None
        self._host_step = 0
        self._base_key = _rng.next_key()

        def pure_step(params, buffers, opt_state, lr, key, *batch):
            loss, aux, grads, new_buffers = self._loss_and_grads(
                params, buffers, key, *batch)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = self.optimizer.update(
                    params, grads, opt_state, lr=lr)
            return loss, aux, new_params, new_buffers, new_opt_state

        donate_argnums = (0, 1, 2) if donate else ()
        self._pure_step = pure_step
        self._donate_argnums = donate_argnums
        self._compiled = jax.jit(pure_step, donate_argnums=donate_argnums)
        self._programs_seen = 0
        from ..autograd import param_grad_hooks_version
        self._hooks_version = param_grad_hooks_version()

    def _loss_and_grads(self, params, buffers, key, *batch):
        """Default: jax.value_and_grad of loss_fn(model(*inputs), *labels).
        Subclasses (PipelineTrainStep) override with custom grad schedules."""
        inputs, labels = batch[: self.n_inputs], batch[self.n_inputs:]

        def loss_of(p):
            out, new_buffers = functional_call(
                self.model, {**buffers, **p}, *inputs, rngs=key, training=True)
            with jax.named_scope("loss"):
                loss_out = self.loss_fn(out, *labels)
            if self.has_aux:
                loss, aux = loss_out
                return loss, (aux, new_buffers)
            return loss_out, (None, new_buffers)

        (loss, (aux, new_buffers)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        # parameter grad hooks (parity: Tensor.register_hook via the
        # GradNode hook slot) run between backward and optimizer
        from ..autograd import apply_param_grad_hooks
        grads = apply_param_grad_hooks(grads)
        return loss, aux, grads, new_buffers

    def _step_args(self, batch):
        """The compiled program's full argument tuple for ``batch`` at
        the current host step."""
        params = self.model.param_dict(trainable_only=True)
        buffers = self.model.buffer_dict()
        if self._opt_state is None:
            self._opt_state = self.optimizer.init_state(params)
        lr = jnp.asarray(float(self.optimizer.get_lr(self._host_step + 1)), jnp.float32)
        key = jax.random.fold_in(self._base_key, self._host_step)
        batch = tuple(jnp.asarray(b) if isinstance(b, (np.ndarray, np.number, int, float))
                      else b for b in batch)
        return (params, buffers, self._opt_state, lr, key, *batch)

    def __call__(self, *batch):
        # fault-injection site: advance the harness's step cursor and give
        # chaos tests a per-step hook (no-op unless a FaultPlan is armed)
        from ..distributed import fault
        fault.set_step(self._host_step)
        fault.trip("train.step")
        # grad hooks are baked into the traced program; retrace when the
        # registry changed after compilation
        from ..autograd import param_grad_hooks_version
        tr = PROFILE_TRACER
        with tr.span("step", track="train", step=self._host_step):
            if param_grad_hooks_version() != self._hooks_version:
                self._compiled = jax.jit(self._pure_step,
                                         donate_argnums=self._donate_argnums)
                self._programs_seen = 0
                self._hooks_version = param_grad_hooks_version()
            with tr.span("step_args", track="train"):
                args = self._step_args(batch)
            with tr.span("dispatch", track="train"):
                (loss, aux, new_params, new_buffers,
                 self._opt_state) = self._compiled(*args)
                del args    # the donated buffers are gone
            n = int(self._compiled._cache_size())
            if n != self._programs_seen:
                # the training twin of the engine's retrace sentinel: the
                # count is followed always, reported while the tracer is on
                self._programs_seen = n
                tr.instant("compile", track="train", programs=n)
                tr.bump("compiles", track="train")
            with tr.span("writeback", track="train"):
                self.model.set_state_dict({**new_params, **new_buffers})
            self._host_step += 1
        return (loss, aux) if self.has_aux else loss

    def lower(self, *batch):
        """jax AOT view of the program ``self(*batch)`` runs, without
        running it: ``step.lower(x, y).compile()`` gives ``as_text()``
        (is the Pallas kernel in the step: ``tpu_custom_call``) and
        ``memory_analysis()`` (does the step fit the device). Nothing
        is donated or updated."""
        return self._compiled.lower(*self._step_args(batch))

    step = __call__

    @property
    def opt_state(self):
        return self._opt_state

    def state_dict(self):
        return {"opt_state": self._opt_state, "host_step": self._host_step}

    def set_state_dict(self, s):
        self._opt_state = s["opt_state"]
        self._host_step = s["host_step"]


class PipelineTrainStep(TrainStep):
    """Train step for pipeline-parallel models (1F1B microbatch schedule).

    The model must expose ``pipeline_loss_and_grads(params, buffers, *batch)
    -> (loss, grads)`` (e.g. ``LlamaForCausalLMPipe``); the optimizer update
    and donation semantics are inherited — forward, 1F1B backward, optimizer
    and p2p handoffs all compile into ONE XLA program (the TPU-native
    replacement for PipelineParallel.train_batch +
    HybridParallelOptimizer.step, hybrid_parallel_optimizer.py:479).
    """

    def __init__(self, model: Layer, optimizer: Optimizer, **kw):
        if not hasattr(model, "pipeline_loss_and_grads"):
            raise TypeError("model must define pipeline_loss_and_grads")
        super().__init__(model, optimizer, loss_fn=None, **kw)

    def _loss_and_grads(self, params, buffers, key, *batch):
        loss, grads = self.model.pipeline_loss_and_grads(params, buffers,
                                                         *batch)
        return loss, None, grads, buffers


class EvalStep:
    """Compiled inference step (no grad, eval mode)."""

    def __init__(self, model: Layer):
        self.model = model

        def pure_eval(state, *inputs):
            out, _ = functional_call(model, state, *inputs, training=False)
            return out

        self._compiled = jax.jit(pure_eval)

    def __call__(self, *inputs):
        return self._compiled(self.model.state_dict(), *inputs)
