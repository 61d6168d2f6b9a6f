"""Device time of a program scope whose instructions JAX differentiated.

`trace.scope_seconds` finds the instructions XLA named after a
`jax.named_scope` (`%attn.17`). Where a scope's contents went through
`jax.value_and_grad` as they are (a `custom_vjp` called inside the scope,
not a framework primitive with its own backward), JAX wraps the scope's
name before XLA sees it: the forward's instructions are `jvp(scope)`,
the backward's `transpose(jvp(scope))`, which XLA spells
`%jvp_scope_.N` and `%transpose_jvp_scope__.N`. This reader takes all
three spellings.
"""
from __future__ import annotations

import re

from chipbench import trace


def scope_seconds(summary, scope):
    """Device time of the instructions that carry `scope`'s name, plain
    or wrapped by differentiation (forward and backward)."""
    name = re.escape(scope)
    pattern = re.compile(
        rf"^({name}|jvp_{name}_|transpose_jvp_{name}__)(\.\d+)?$")
    return sum(secs for text, secs in trace.leaf_ops(summary).items()
               if pattern.match(trace.op_instruction_name(text)))
