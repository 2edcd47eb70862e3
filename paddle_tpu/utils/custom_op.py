"""Custom-op registration — the TPU-native cpp_extension (parity:
python/paddle/utils/cpp_extension/cpp_extension.py:79 ``setup``/``load`` +
``PD_BUILD_OP`` op_meta_info.h:1150 + fluid/framework/custom_operator.cc).

The reference compiles user C++/CUDA against installed headers and registers
the result as a first-class op (dygraph + static + inference). On TPU the
"kernel language" is Pallas (or any jax-traceable callable), so the
toolchain collapses to ONE registration call that wires up everything the
C++ macro stack did:

- **autograd**: a custom VJP (``bwd``) installed via jax.custom_vjp;
- **sharding rule**: the SPMD rule (``sharding_rule``) — the analogue of a
  phi/infermeta/spmd_rules entry — applied by wrapping the kernel in
  shard_map when a mesh is active, so the op composes with dp/tp/fsdp
  programs instead of falling off the GSPMD propagation path;
- **contract enrollment**: a numpy reference + input generator auto-enrolls
  the op in the OpTest-style contract suite (tests/test_op_contract.py);
- **inventory**: the op appears in ``core.registry.all_ops()``.

Example — a fused scale-and-shift op with a hand-written backward::

    import jax.numpy as jnp
    from paddle_tpu.utils.custom_op import register_custom_op

    def sscale_fwd(x, alpha):
        return jnp.tanh(x) * alpha

    def sscale_bwd(residuals, g):
        x, alpha = residuals
        t = jnp.tanh(x)
        return g * alpha * (1 - t * t), jnp.sum(g * t)

    sscale = register_custom_op(
        "sscale", sscale_fwd, bwd=sscale_bwd,
        ref=lambda x, a: np.tanh(x) * a,
        make_inputs=lambda rng: (rng.standard_normal((4, 8)).astype("float32"),
                                 np.float32(1.7)),
        grad_ref=True,
        sharding_rule=lambda mesh, x, a: (((P("dp"), None), P("dp"))
                                          if "dp" in mesh.axis_names else None))

The returned callable is the public op; the contract suite picks it up on
the next run with zero extra test code.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P  # noqa: F401  (docstring example)

from ..core.registry import register_contract
from ..core import mesh as mesh_lib

__all__ = ["register_custom_op", "CustomOpBuilder"]


def register_custom_op(
    name: str,
    fwd: Callable,
    *,
    bwd: Callable | None = None,
    fwd_res: Callable | None = None,
    ref: Callable | None = None,
    make_inputs: Callable | None = None,
    grad_ref: bool = False,
    sharding_rule: Callable | None = None,
    notes: str = "",
) -> Callable:
    """Register ``fwd`` as a first-class custom op.

    Args:
      fwd: the kernel — a Pallas call or any jax-traceable function.
      bwd: custom backward ``bwd(residuals, cotangent) -> grads`` (one per
        positional input). Default residuals are the primal inputs; pass
        ``fwd_res(out, *inputs) -> residuals`` to save something else
        (e.g. the flash-attention LSE).
      ref / make_inputs / grad_ref: OpTest contract hooks — numpy reference,
        input generator, and whether jax.grad is finite-difference checked.
      sharding_rule: ``rule(mesh, *inputs) -> (in_specs, out_specs) | None``
        — when a mesh is active and the rule returns specs, the kernel runs
        under shard_map with them (SPMD-rule parity for kernels GSPMD cannot
        see through).
    """
    kernel = fwd
    if bwd is not None:
        @jax.custom_vjp
        def op_core(*args):
            return kernel(*args)

        def op_fwd(*args):
            out = kernel(*args)
            res = fwd_res(out, *args) if fwd_res is not None else args
            return out, res

        def op_bwd(res, g):
            grads = bwd(res, g)
            return grads if isinstance(grads, tuple) else (grads,)

        op_core.defvjp(op_fwd, op_bwd)
    else:
        op_core = kernel

    @functools.wraps(fwd)
    def op(*args, **kwargs):
        mesh = mesh_lib.current_mesh()
        if sharding_rule is not None and mesh is not None and \
                any(s > 1 for s in mesh.shape.values()):
            specs = sharding_rule(mesh, *args)
            if specs is not None:
                in_specs, out_specs = specs
                return jax.jit(shard_map(
                    lambda *a: op_core(*a, **kwargs), mesh=mesh,
                    in_specs=tuple(in_specs), out_specs=out_specs,
                    check_vma=False))(*args)
        return op_core(*args, **kwargs)

    op.__name__ = name
    register_contract(name, op, ref, make_inputs, fn_call=op,
                      grad_ref=grad_ref, category="custom",
                      notes=notes or "custom op (register_custom_op)")
    return op


class CustomOpBuilder:
    """Fluent variant mirroring the PD_BUILD_OP macro chain::

        op = (CustomOpBuilder("my_op")
              .forward(fwd).backward(bwd)
              .reference(np_ref, make_inputs)
              .sharding(rule).build())
    """

    def __init__(self, name: str):
        self._name = name
        self._kw = {}
        self._fwd = None

    def forward(self, fn):
        self._fwd = fn
        return self

    def backward(self, fn, fwd_res=None):
        self._kw["bwd"] = fn
        if fwd_res is not None:
            self._kw["fwd_res"] = fwd_res
        return self

    def reference(self, ref, make_inputs=None, grad_ref=False):
        self._kw.update(ref=ref, make_inputs=make_inputs, grad_ref=grad_ref)
        return self

    def sharding(self, rule):
        self._kw["sharding_rule"] = rule
        return self

    def build(self):
        if self._fwd is None:
            raise ValueError("forward kernel not set")
        return register_custom_op(self._name, self._fwd, **self._kw)
