"""What the layout tests ask of a compiled train step: the CPU mesh's in
``test_fsdp_layout.py``, the chip's in ``test_tpu_compile.py``."""

import re

import jax
import jax.numpy as jnp


def stack_gathers(hlo_text, num_layers):
    """Result shapes of the all-gathers that yield a whole stack: rank
    3 or more with the layer count leading."""
    found = []
    for match in re.finditer(r"= ([^=\n]*?) all-gather(?:-start)?\(",
                             hlo_text):
        for dims in re.findall(r"\w+\[([0-9,]+)\]", match.group(1)):
            shape = tuple(int(d) for d in dims.split(","))
            if len(shape) >= 3 and shape[0] == num_layers:
                found.append(shape)
    return found


def compile_step(result, batch):
    """The train step of an ``accelerate`` result, lowered from shapes
    alone and compiled for the devices it was built on."""
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    return result.train_step.lower(
        state, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
