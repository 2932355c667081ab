"""What the layout tests ask of a compiled train step: the CPU mesh's in
``test_fsdp_layout.py``, the chip's in ``test_tpu_compile*.py``; what
the latent flash ops' tests ask of a checkpoint around one op; and what
the chip's compiler said of a module it has compiled before
(``compile_once``)."""

import base64
import collections
import gzip
import hashlib
import importlib.metadata
import json
import os
import re
import tempfile
import time
import types

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
    """``lower_step``, compiled (``compile_once``)."""
    return compile_once(lower_step(result, batch))


def _compiler():
    """Everything but the module that decides what a compile gives: the
    packages that lower and compile, the described chip, and the flags
    either reads from the environment."""
    versions = [f"{name} {importlib.metadata.version(name)}"
                for name in ("jax", "jaxlib", "libtpu")]
    return versions + ["v5e:2x2"] + [
        f"{name}={os.environ.get(name, '')}"
        for name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")]


_BODY = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'


def _meaning(lowered):
    """What means something in a lowered module, as
    ``lowered_step_digests.py`` reads it: the module printed without
    locations, its kernel bodies taken out, and each body decoded and
    printed the same way. A Pallas body carries the files, lines and
    columns of the call stack that first traced it, and in a worker
    that has run another test of the same kernel that stack is the
    other test's: the raw text then differs between two runs of one
    program."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def plain(raw):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(raw).operation.get_asm(
                enable_debug_info=False)

    text = lowered.as_text()
    return [re.sub(_BODY, "body", text)] + [
        plain(base64.b64decode(found.group(1)))
        for found in re.finditer(_BODY, text)]


def _record(text, memory):
    """What the tests read of a compiled program: its text and its
    memory analysis."""
    return types.SimpleNamespace(
        as_text=lambda: text,
        memory_analysis=lambda: types.SimpleNamespace(**memory))


def compile_once(lowered):
    """``lowered.compile()`` for the described v5e, run once a module:
    the compiled text and the memory analysis are kept, gzipped, under
    the compile cache's directory (``tpu_compiles/``, by the sha256 of
    ``_meaning(lowered)`` and of ``_compiler()``), and a later run that
    lowers the very same module for the same compiler reads them back
    and asserts on them. The persistent compile cache cannot do it (a
    deviceless executable is written but not read back without a chip),
    and the whole-step compiles were 2,500 of tier-1's 6,900
    worker-seconds, many-threaded beside every other test (PR 58;
    ROADMAP.md D9). A module that differs in one character outside a
    location, another jax, jaxlib or libtpu, or another flag compiles
    anew; entries that no run has read for 30 days go when one is
    written; with no cache directory nothing is kept."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not root:
        return lowered.compile()
    digest = hashlib.sha256("\0".join(
        _meaning(lowered) + _compiler()).encode()).hexdigest()
    kept = os.path.join(root, "tpu_compiles")
    path = os.path.join(kept, digest + ".json.gz")
    try:
        with gzip.open(path, "rt") as fh:
            found = json.load(fh)
        os.utime(path)
        return _record(found["text"], found["memory"])
    except (OSError, ValueError, KeyError, EOFError):
        pass  # never compiled, or a file cut short: compile
    program = lowered.compile()
    analysis = program.memory_analysis()
    memory = {name: getattr(analysis, name) for name in dir(analysis)
              if not name.startswith("_")
              and isinstance(getattr(analysis, name), int)}
    text = program.as_text()
    os.makedirs(kept, exist_ok=True)
    for name in os.listdir(kept):
        old = os.path.join(kept, name)
        try:
            if time.time() - os.path.getmtime(old) > 30 * 86400:
                os.remove(old)
        except OSError:
            pass  # another worker's, or gone already
    fd, partial = tempfile.mkstemp(dir=kept, suffix=".partial")
    with gzip.open(os.fdopen(fd, "wb"), "wt", compresslevel=3) as fh:
        json.dump({"text": text, "memory": memory}, fh)
    os.replace(partial, path)
    return _record(text, memory)


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


def kept_names_spare_the_forward(f, args, names, forward, kept):
    """``f``, a scalar function of ``args`` around one flash op, under a
    ``"full"`` checkpoint: given ``names`` the checkpoint holds arrays
    of the shapes ``kept`` beside its arguments and its gradient program
    has the kernel ``forward`` once; given nothing it holds its
    arguments alone and has the kernel again in its replay; both give
    the bits of ``f`` under no checkpoint. Returns the gradient
    program's jaxpr text with the names kept."""
    from jax._src.ad_checkpoint import saved_residuals

    from dlrover_tpu.ops.remat import apply_remat

    every = tuple(range(len(args)))
    got, text = {}, {}
    for keep, forwards, held in (((), 2, []), (tuple(names), 1, kept)):
        g = apply_remat(f, "full", keep=keep)
        assert [value.shape for value, why in saved_residuals(g, *args)
                if why.startswith(("output of", "named"))] == held
        # traced once: the text and the program run are one trace's
        traced = jax.jit(jax.grad(g, every)).trace(*args)
        text[keep] = str(traced.jaxpr)
        assert text[keep].count(f"name={forward}") == forwards
        got[keep] = traced.lower().compile()(*args)
    for a, b, c in zip(got[()], got[tuple(names)],
                       jax.jit(jax.grad(f, every))(*args)):
        assert jnp.array_equal(a, b) and jnp.array_equal(a, c)
    return text[tuple(names)]
