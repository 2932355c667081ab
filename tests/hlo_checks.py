"""What the layout tests ask of a compiled train step: the CPU mesh's in
``test_fsdp_layout.py``, the chip's in ``test_tpu_compile*.py``."""

import collections
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 15.75e9  # what memory_stats() reports as bytes_limit


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


Move = collections.namedtuple("Move", "name shape op_name relayout")

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(%?([\w.\-]*)", re.M)


def _elements(shape):
    dims = re.search(r"\[([0-9,]*)\]", shape)
    count = 1
    for d in (dims.group(1).split(",") if dims and dims.group(1) else []):
        count *= int(d)
    return count


def _layout(shape):
    found = re.search(r"\{([0-9,]*)", shape)
    return found.group(1) if found else ""


def moves_of(hlo_text, elements):
    """The ``copy`` and ``transpose`` instructions of a compiled program
    whose result has ``elements`` elements, the ones inside fusions
    under the fusion's name and ``op_name``: passes over an array that
    compute nothing. ``relayout`` says the result's layout is not the
    operand's (a same-layout ``copy`` is a buffer's second home, e.g. a
    loop's carry that is also kept for the backward)."""
    shapes = {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}
    fused_into = {}
    for line in hlo_text.splitlines():
        called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        if called:
            fused_into[called.group(1)] = line
    found, computation = [], ""
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if (not m or m.group(3) not in ("copy", "transpose")
                or _elements(m.group(2)) != elements):
            continue
        outer = fused_into.get(computation, line)
        name = _INSTRUCTION.match(outer).group(1)
        op_name = re.search(r'op_name="([^"]*)"', outer)
        found.append(Move(
            name, m.group(2).split(":")[0],
            op_name.group(1) if op_name else "",
            m.group(3) == "transpose"
            or _layout(shapes.get(m.group(4), "")) != _layout(m.group(2))))
    return found


def lower_step(result, batch):
    """The train step of an ``accelerate`` result, lowered from shapes
    alone for the devices it was built on."""
    state = jax.eval_shape(result.init_fn, jax.random.PRNGKey(0))
    return result.train_step.lower(
        state, jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def compile_step(result, batch):
    """``lower_step``, compiled."""
    return lower_step(result, batch).compile()


def _resident_bytes(compiled):
    """Arguments + temporaries + outputs - aliases of
    ``memory_analysis()``: the compiler's ESTIMATE, what the program's
    ``attribution_captured.peak_hbm_mb`` and the benchmark's
    ``compiled_step_bytes`` say. The v5e compiler's
    ``temp_size_in_bytes`` is no allocation's size: it holds the
    donated parameters a second time (PR 43) and a buffer that a
    ``while`` loop carries twice (PR 50), so it reads over the total of
    everything the program allocates. The keye step (deviceless
    compiles, PR 50), parent -> with the selected attention's output
    and logsumexp kept, 1.09 GB in one stacked slot of the arena: 13.31
    -> 15.30 GB here, 9.94 -> 10.43 by ``_peak_bytes``, 10.08 -> 10.85
    in the buffer assignment's total (parameters + the arena)."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _peak_bytes(compiled):
    """What the compiler ALLOCATES at the program's peak
    (``memory_analysis().peak_memory_in_bytes``): the most that is live
    at once in its buffer assignment, on the v5e the arguments among
    them, donated ones once (the CPU backend's field is arguments +
    outputs and leaves the temporaries out: it says nothing of a
    chip). Refuses a jaxlib that does not say: the v5e compiler's
    ``serialized_buffer_assignment_proto`` is empty, so there is
    nothing else to read it off, and a bar held against 0 holds
    nothing."""
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak > 0, "memory_analysis() gives no peak_memory_in_bytes"
    return peak


def _on(device, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


def _kernel_names(text):
    """The instructions of a compiled program that are Mosaic kernels."""
    return {line.split(" = ")[0].strip() for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def _entry_results(text):
    """The result types of the entry computation's instructions: the
    arrays that exist between operations, not inside a fusion."""
    body = text[text.index("\nENTRY "):]
    return [line.split(" = ", 1)[1]
            for line in body[:body.index("\n}")].splitlines()
            if " = " in line]
