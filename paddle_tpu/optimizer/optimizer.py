"""Optimizers (parity: python/paddle/optimizer/ — Optimizer base
optimizer.py:104 and SGD/Momentum/Adam/AdamW/... subclasses).

Design: each optimizer owns hyperparameters and exposes a **pure** pair
``init_state(params) -> state`` / ``update(params, grads, state) ->
(new_params, new_state)`` over path-keyed dicts — this is what the jit'd
train step calls, and what FSDP shards (opt state inherits each param's
sharding, giving ZeRO-1 semantics for free — SURVEY §7 translation table).

The paddle-style stateful surface (``opt.step()`` writing back into the
bound Layer) is a thin eager wrapper used outside jit.

The reference implements each rule as a CUDA kernel plus fused multi-tensor
variants (phi/kernels/gpu/adamw_kernel.cu, fused_adam_kernel.cu); on TPU the
whole update is one XLA fusion across all parameters, so no multi-tensor
path is needed.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..nn.module import Layer
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax", "Adagrad",
           "Adadelta", "RMSProp", "Lamb", "Lars", "NAdam", "RAdam", "ASGD", "Rprop"]


def _tree_cast(x, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), x)


class Optimizer:
    # names of per-param state slots, e.g. ("moment1", "moment2")
    slots: tuple[str, ...] = ()

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision: bool = True, name=None):
        self._lr = learning_rate
        self.weight_decay = 0.0 if weight_decay is None else weight_decay
        self.grad_clip = grad_clip
        self.multi_precision = multi_precision
        self._layer: Layer | None = None
        self._param_keys = None
        if isinstance(parameters, Layer):
            self._layer = parameters
        elif parameters is not None:
            parameters = list(parameters)
            self._param_keys = [str(i) for i in range(len(parameters))]
        self._eager_state = None

    # ---- lr ----

    def get_lr(self, step=None):
        if isinstance(self._lr, LRScheduler):
            return self._lr.lr_at(step) if step is not None else self._lr.get_lr()
        return self._lr

    def set_lr(self, value):
        self._lr = value

    @property
    def lr_scheduler(self):
        return self._lr if isinstance(self._lr, LRScheduler) else None

    # ---- pure functional interface ----

    def init_state(self, params: dict[str, jax.Array]) -> dict[str, Any]:
        state = {"step": jnp.zeros((), jnp.int32)}
        sharding = getattr(next(iter(jax.tree.leaves(params)), None),
                           "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            # under a mesh the counter comes back from the first step
            # replicated over it; born anywhere else, step 2 would see
            # a new input sharding and compile the whole step again
            state["step"] = jax.device_put(
                state["step"], jax.sharding.NamedSharding(
                    sharding.mesh, jax.sharding.PartitionSpec()))
        for slot in self.slots:
            # zeros_like, not zeros(shape): a slot is born with its
            # parameter's sharding. Plain zeros all land on the default
            # device until the first step reshards them — under a mesh
            # that is every moment of the model on device 0 (7.7 GB of a
            # 13.5 GB state on one v5e chip of four, PERF.md PR 23)
            state[slot] = jax.tree.map(
                lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
        if self.multi_precision:
            state["master"] = jax.tree.map(
                lambda p: p.astype(jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating) and p.dtype != jnp.float32
                else None,
                params)
        return state

    def update(self, params: dict, grads: dict, state: dict, lr=None):
        """Pure update. grads may be a subset of params (frozen params skip)."""
        step = state["step"] + 1
        lr_t = lr if lr is not None else self.get_lr(step)
        if self.grad_clip is not None:
            grads = self.grad_clip(grads)
        new_params = dict(params)
        new_state = {k: (dict(v) if isinstance(v, dict) else v) for k, v in state.items()}
        new_state["step"] = step
        for k, g in grads.items():
            if g is None:
                continue
            p = params[k]
            master = state.get("master", {}).get(k) if self.multi_precision else None
            p32 = master if master is not None else p.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            slots = {s: state[s][k] for s in self.slots}
            p32_new, slots_new = self._rule(p32, g32, slots, lr_t, step, key=k)
            if master is not None:
                new_state["master"][k] = p32_new
            new_params[k] = p32_new.astype(p.dtype)
            for s in self.slots:
                new_state[s][k] = slots_new[s]
        return new_params, new_state

    def _rule(self, p, g, slots, lr, step, key=None):
        raise NotImplementedError

    def _wd(self, p, g):
        """L2-regularization style decay (coupled; AdamW overrides)."""
        if self.weight_decay:
            return g + self.weight_decay * p
        return g

    # ---- eager paddle-style interface ----

    def _bound_params(self) -> dict[str, jax.Array]:
        if self._layer is None:
            raise ValueError("Optimizer was not constructed with parameters=Layer; "
                             "use the functional init_state/update API instead.")
        return self._layer.param_dict(trainable_only=True)

    def step(self, grads: dict[str, jax.Array] | None = None):
        """Apply an update to the bound Layer (eager mode).
        ``grads`` is the path-keyed grad dict from jax.grad."""
        params = self._bound_params()
        if grads is None:
            raise ValueError("pass grads={path: grad} (functional autograd has no "
                             ".grad attribute to harvest)")
        if self._eager_state is None:
            self._eager_state = self.init_state(params)
        new_params, self._eager_state = self.update(params, grads, self._eager_state)
        self._layer.set_state_dict(new_params)
        if isinstance(self._lr, LRScheduler):
            pass  # paddle convention: user calls scheduler.step() explicitly
        return new_params

    def clear_grad(self):
        pass  # grads are values, not storage — nothing to clear

    def state_dict(self):
        out = {}
        if self._eager_state is not None:
            out["state"] = self._eager_state
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state):
        if "state" in state:
            self._eager_state = state["state"]
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])


class SGD(Optimizer):
    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        return p - lr * g, slots


class Momentum(Optimizer):
    slots = ("velocity",)

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.momentum = momentum
        self.use_nesterov = use_nesterov

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        v = self.momentum * slots["velocity"] + g
        if self.use_nesterov:
            p_new = p - lr * (g + self.momentum * v)
        else:
            p_new = p - lr * v
        return p_new, {"velocity": v}


class Adam(Optimizer):
    slots = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, amsgrad=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.amsgrad = amsgrad
        if amsgrad:
            self.slots = ("moment1", "moment2", "moment2_max")

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        if self.amsgrad:
            vmax = jnp.maximum(slots["moment2_max"], v)
            vhat = vmax / (1 - self.beta2 ** t)
            out_slots = {"moment1": m, "moment2": v, "moment2_max": vmax}
        else:
            vhat = v / (1 - self.beta2 ** t)
            out_slots = {"moment1": m, "moment2": v}
        p_new = p - lr * mhat / (jnp.sqrt(vhat) + self.epsilon)
        return p_new, out_slots


class AdamW(Adam):
    """Decoupled weight decay (parity: paddle.optimizer.AdamW;
    reference kernel phi/kernels/gpu/adamw_kernel.cu)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, amsgrad=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, amsgrad, name)
        self.weight_decay = weight_decay or 0.0
        self.apply_decay_param_fun = apply_decay_param_fun
        self.lr_ratio = lr_ratio

    def _rule(self, p, g, slots, lr, step, key=None):
        decay = self.weight_decay
        if self.apply_decay_param_fun is not None and key is not None:
            if not self.apply_decay_param_fun(key):
                decay = 0.0
        if self.lr_ratio is not None and key is not None:
            lr = lr * self.lr_ratio(key)
        p = p * (1 - lr * decay)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        p_new = p - lr * mhat / (jnp.sqrt(vhat) + self.epsilon)
        return p_new, {"moment1": m, "moment2": v}


class Adamax(Optimizer):
    slots = ("moment", "inf_norm")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        m = self.beta1 * slots["moment"] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * slots["inf_norm"], jnp.abs(g))
        t = step.astype(jnp.float32)
        p_new = p - lr / (1 - self.beta1 ** t) * m / (u + self.epsilon)
        return p_new, {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    slots = ("moment",)

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.epsilon = epsilon
        self.initial_accumulator_value = initial_accumulator_value

    def init_state(self, params):
        state = super().init_state(params)
        if self.initial_accumulator_value:
            state["moment"] = jax.tree.map(
                lambda m: m + self.initial_accumulator_value, state["moment"])
        return state

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        acc = slots["moment"] + g * g
        return p - lr * g / (jnp.sqrt(acc) + self.epsilon), {"moment": acc}


class Adadelta(Optimizer):
    slots = ("avg_squared_grad", "avg_squared_update")

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.epsilon, self.rho = epsilon, rho

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        sg = self.rho * slots["avg_squared_grad"] + (1 - self.rho) * g * g
        upd = g * jnp.sqrt(slots["avg_squared_update"] + self.epsilon) / jnp.sqrt(
            sg + self.epsilon)
        su = self.rho * slots["avg_squared_update"] + (1 - self.rho) * upd * upd
        return p - lr * upd, {"avg_squared_grad": sg, "avg_squared_update": su}


class RMSProp(Optimizer):
    slots = ("mean_square", "mean_grad", "momentum_acc")

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.rho, self.epsilon, self.momentum, self.centered = rho, epsilon, momentum, centered

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        ms = self.rho * slots["mean_square"] + (1 - self.rho) * g * g
        if self.centered:
            mg = self.rho * slots["mean_grad"] + (1 - self.rho) * g
            denom = jnp.sqrt(ms - mg * mg + self.epsilon)
        else:
            mg = slots["mean_grad"]
            denom = jnp.sqrt(ms + self.epsilon)
        mom = self.momentum * slots["momentum_acc"] + lr * g / denom
        return p - mom, {"mean_square": ms, "mean_grad": mg, "momentum_acc": mom}


class Lamb(Optimizer):
    slots = ("moment1", "moment2")

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self.lamb_weight_decay = lamb_weight_decay
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.exclude_fn = exclude_from_weight_decay_fn

    def _rule(self, p, g, slots, lr, step, key=None):
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        decay = self.lamb_weight_decay
        if self.exclude_fn is not None and key is not None and self.exclude_fn(key):
            decay = 0.0
        r = mhat / (jnp.sqrt(vhat) + self.epsilon) + decay * p
        p_norm = jnp.sqrt(jnp.sum(p * p))
        r_norm = jnp.sqrt(jnp.sum(r * r))
        trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v}


class Lars(Momentum):
    """LARS (parity: fleet meta_optimizer LarsOptimizer / lars_momentum op)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=1e-9,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, momentum, parameters, False, None,
                         grad_clip, multi_precision, name)
        self.lars_coeff = lars_coeff
        self.lars_weight_decay = lars_weight_decay
        self.exclude = exclude_from_weight_decay or []
        self.epsilon = epsilon

    def _rule(self, p, g, slots, lr, step, key=None):
        decay = self.lars_weight_decay
        if key is not None and any(e in key for e in self.exclude):
            decay = 0.0
        p_norm = jnp.sqrt(jnp.sum(p * p))
        g_norm = jnp.sqrt(jnp.sum(g * g))
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            self.lars_coeff * p_norm / (g_norm + decay * p_norm + self.epsilon), 1.0)
        v = self.momentum * slots["velocity"] + local_lr * lr * (g + decay * p)
        return p - v, {"velocity": v}


class NAdam(Adam):
    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        nesterov_m = self.beta1 * mhat + (1 - self.beta1) * g / (1 - self.beta1 ** t)
        return p - lr * nesterov_m / (jnp.sqrt(vhat) + self.epsilon), \
            {"moment1": m, "moment2": v}


class RAdam(Adam):
    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        m = self.beta1 * slots["moment1"] + (1 - self.beta1) * g
        v = self.beta2 * slots["moment2"] + (1 - self.beta2) * g * g
        t = step.astype(jnp.float32)
        mhat = m / (1 - self.beta1 ** t)
        rho_inf = 2.0 / (1 - self.beta2) - 1
        rho_t = rho_inf - 2 * t * self.beta2 ** t / (1 - self.beta2 ** t)
        r = jnp.sqrt(jnp.clip(
            (rho_t - 4) * (rho_t - 2) * rho_inf /
            jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t, 1e-12), 0.0))
        vhat = jnp.sqrt(v / (1 - self.beta2 ** t))
        upd = jnp.where(rho_t > 5.0, r * mhat / (vhat + self.epsilon), mhat)
        return p - lr * upd, {"moment1": m, "moment2": v}


class ASGD(Optimizer):
    slots = ("d", "ys")

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self.batch_num = batch_num

    def _rule(self, p, g, slots, lr, step, key=None):
        g = self._wd(p, g)
        # simplified averaged-SGD: running average of gradients
        d = slots["d"] - slots["ys"] + g
        ys = g
        return p - lr / self.batch_num * d, {"d": d, "ys": ys}


class Rprop(Optimizer):
    slots = ("prev_grad", "step_size")

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self.lr_range = learning_rate_range
        self.etas = etas

    def init_state(self, params):
        state = super().init_state(params)
        state["step_size"] = jax.tree.map(
            lambda p: jnp.full(p.shape, float(self.get_lr(0) if not isinstance(
                self._lr, LRScheduler) else self._lr.base_lr), jnp.float32), params)
        return state

    def _rule(self, p, g, slots, lr, step, key=None):
        sign = jnp.sign(g * slots["prev_grad"])
        eta = jnp.where(sign > 0, self.etas[1], jnp.where(sign < 0, self.etas[0], 1.0))
        ss = jnp.clip(slots["step_size"] * eta, self.lr_range[0], self.lr_range[1])
        g_eff = jnp.where(sign < 0, 0.0, g)
        return p - jnp.sign(g_eff) * ss, {"prev_grad": g_eff, "step_size": ss}
