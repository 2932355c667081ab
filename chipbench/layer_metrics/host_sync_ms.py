"""Milliseconds a step the train loop was blocked pulling the oldest step
in flight to the host (``dlrover_step_host_sync_seconds``), over exactly
the steps of the measured worker's profiling window: the
``profile_window`` event's ``host_sync_seconds / steps``. Near the step
time the host waits on the chip; what is missing is the host's work."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    windows = [e for e in ctx["run"]["events"]
               if e.get("kind") == "profile_window" and e.get("pid") == pid]
    if not windows or not windows[-1]["steps"]:
        return None  # a --trace 1 run's trace is the hook's, not a window
    return 1e3 * windows[-1]["host_sync_seconds"] / windows[-1]["steps"]
