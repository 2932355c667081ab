"""Seconds the measured worker's first ``jax.devices()`` took: the
backend's (TPU) initialisation (``worker_boot`` event,
``backend_seconds``)."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    event = next((e for e in ctx["run"]["events"]
                  if e.get("kind") == "worker_boot" and e.get("pid") == pid),
                 None)
    if event is None:
        return None
    return event["backend_seconds"]
