"""Milliseconds a step the train loop spent dispatching the step program
(the program's own ``dlrover_step_dispatch_seconds``, the save branch
not in it), over exactly the steps of the measured worker's profiling
window: the ``profile_window`` event's ``dispatch_seconds / steps``."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    windows = [e for e in ctx["run"]["events"]
               if e.get("kind") == "profile_window" and e.get("pid") == pid]
    if not windows or not windows[-1]["steps"]:
        return None  # a --trace 1 run's trace is the hook's, not a window
    return 1e3 * windows[-1]["dispatch_seconds"] / windows[-1]["steps"]
