"""One trace a process of a kernel's body.

A ``pallas_call`` traces its body to a jaxpr where it is bound and
lowers it to Mosaic where its equation is lowered: once a CALL SITE,
and a kernel is a call site in every layer of every scan, in the
forward and in remat's replay, in each of the programs a boot lowers
(``PERF.md`` section 6, PR 46: 36 traces of four bodies a lowering of
the xing4 step, 15 s of a warm boot). So every call goes through ONE
``jax.jit`` a kernel, its static arguments and its operand shapes:
JAX's trace cache hands the first trace's jaxpr to every later site of
the process, and a module lowers the function once and calls it (XLA
inlines the calls first of all: the compiled step is the one the bare
calls give).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax

_SHARED: Dict[tuple, Callable] = {}


def shared_call(name, scope, static, operands, build):
    """``build()`` (a ``pallas_call``) applied to ``operands`` under
    ``scope``, through the one ``jax.jit`` of ``name``, ``static`` (all
    else that decides the program, hashable) and the operands' shapes.
    The callee starts a name stack of its own, so the scope is opened
    inside it; the function is ``name`` in the lowered module."""
    key = (name, scope, static,
           tuple((a.shape, str(a.dtype)) for a in operands))
    if key not in _SHARED:
        call = build()

        def shared(*operands):
            with jax.named_scope(scope):
                return call(*operands)

        shared.__name__ = name
        _SHARED[key] = jax.jit(shared)
    return _SHARED[key](*operands)
