"""Milliseconds a step the train loop was blocked in ``next()`` on its
batch iterator (``dlrover_input_wait_seconds``), over exactly the steps
of the measured worker's profiling window: the ``profile_window`` event's
``input_wait_seconds / steps``."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    windows = [e for e in ctx["run"]["events"]
               if e.get("kind") == "profile_window" and e.get("pid") == pid]
    if not windows or not windows[-1]["steps"]:
        return None  # a --trace 1 run's trace is the hook's, not a window
    return 1e3 * windows[-1]["input_wait_seconds"] / windows[-1]["steps"]
