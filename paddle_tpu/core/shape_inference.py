"""Shape/dtype inference by abstract evaluation.

Fluid hand-writes an InferShape function per op (~430 of them, e.g.
``framework/operator.cc:930`` runtime InferShape). Here shapes are derived
from the op implementations themselves: each appended op is abstractly
evaluated with ``jax.eval_shape`` over ShapeDtypeStructs — zero FLOPs, no
duplicate shape rules, and impossible for shape inference to disagree with
the kernel. Dynamic (batch) dims are threaded through as a sentinel value and
mapped back to -1.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .dtypes import to_jnp_dtype

# Placeholder for dynamic (-1) dims during abstract eval. Prime & unusual to
# make accidental collision with a real static dim unlikely.
DYNAMIC_SENTINEL = 509


def _subst_dynamic(shape):
    return tuple(DYNAMIC_SENTINEL if d == -1 else d for d in shape)


def _restore_dynamic(shape):
    return tuple(-1 if d == DYNAMIC_SENTINEL else d for d in shape)


def infer_op_shapes(op, block) -> None:
    """Best-effort: fills in shape/dtype of output vars with unknown shape.

    Silently skips ops it cannot evaluate (unregistered type, inputs with
    unknown shapes, data-dependent shapes); runtime tracing remains the
    source of truth.
    """
    from .registry import OpContext, has_op, get_op_impl

    if not has_op(op.type):
        return

    env_structs = {}
    for names in op.inputs.values():
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.shape is None:
                return  # unknown input — give up
            env_structs[n] = jax.ShapeDtypeStruct(_subst_dynamic(v.shape), to_jnp_dtype(v.dtype))

    out_names = [n for names in op.outputs.values() for n in names]

    class _Trace:
        is_test = False
        current_op_idx = 0
        # an op whose result's shape needs none of its body may ask, and
        # skip a body that is dear to trace (a Pallas kernel's)
        shapes_only = True

        def __init__(self):
            self.base_rng = None

        def op_rng(self, ctx):
            return self.base_rng

    def _absfn(env, key):
        trace = _Trace()
        trace.base_rng = key
        impl = get_op_impl(op.type)
        ctx = OpContext(op, env, trace)
        impl(ctx)
        return {n: env[n] for n in out_names if n in env}

    try:
        out = jax.eval_shape(
            _absfn, env_structs, jax.ShapeDtypeStruct((2,), np.uint32)
        )
    except Exception:
        return

    for n, s in out.items():
        v = block._find_var_recursive(n)
        if v is None:
            continue
        if v.shape is None:
            v.shape = _restore_dynamic(s.shape)
            v.dtype = np.dtype(s.dtype).name if s.dtype != jax.numpy.bfloat16 else "bfloat16"
