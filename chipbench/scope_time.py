"""A traced step's device time by phase, scope and kind of instruction.

The program says, in the event ``step_scopes`` it writes before a
profiling window's ``profile_window``, which phase (``forward``,
``replay``, ``backward``, ``optimizer``, ``none``) and which path of
named scopes each instruction of its compiled step belongs to:
``instructions`` is ``{"<phase>|<outer>/<inner>": [instruction, ...]}``.
The reduced trace has every instruction's seconds by name
(``device_ops``, a Mosaic kernel's under ``mosaic:<name>``). ``rows``
joins the two; the readers in ``layer_metrics/`` that split
``step_device_ms`` are sums over it. A run with no device plane or no
such event (a program from before the event) gives nothing to read.

Loaded by path (``runpy.run_path``) by those readers; imports nothing.
"""

UNNAMED = "unnamed"  # the phase of an instruction the event leaves out
MOSAIC = "mosaic:"


def rows(ctx):
    """``[(milliseconds a step, phase, scope path outermost first, is
    it a Mosaic kernel)]``, a row an instruction of ``device_ops``; or
    None."""
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    pid = ctx["run"]["worker"]["pid"]
    event = next((e for e in reversed(ctx["run"]["events"])
                  if e.get("kind") == "step_scopes"
                  and e.get("pid") == pid), None)
    if event is None:
        return None
    where = {name: key for key, names in event["instructions"].items()
             for name in names}
    out = []
    for name, seconds in trace["device_ops"]:
        kernel = name.startswith(MOSAIC)
        phase, _, path = where.get(
            name[len(MOSAIC):] if kernel else name, UNNAMED + "|"
        ).partition("|")
        out.append((1e3 * seconds / trace["steps"], phase,
                    tuple(path.split("/")) if path else (), kernel))
    return out


def total_ms(ctx, keep):
    """Milliseconds a step of the rows ``keep(phase, path, kernel)``
    picks; None where there are no rows to pick from."""
    found = rows(ctx)
    if found is None:
        return None
    return sum(ms for ms, phase, path, kernel in found
               if keep(phase, path, kernel))


def phase_ms(ctx, phase):
    return total_ms(ctx, lambda p, path, kernel: p == phase)


def innermost_ms(ctx, scopes, kernels=True):
    """Instructions whose innermost scope is one of ``scopes``; with
    ``kernels`` false the Mosaic calls among them are left out (they
    have readers of their own names)."""
    return total_ms(ctx, lambda p, path, kernel: bool(path)
                    and path[-1] in scopes and (kernels or not kernel))


def under_ms(ctx, scopes):
    """Instructions with one of ``scopes`` anywhere in their path."""
    return total_ms(ctx, lambda p, path, kernel:
                    any(s in scopes for s in path))
