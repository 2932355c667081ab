"""Seconds from the start of the measured worker's process to its call
of ``init_worker``: the interpreter and the imports (``worker_boot``
event, ``import_seconds``)."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    event = next((e for e in ctx["run"]["events"]
                  if e.get("kind") == "worker_boot" and e.get("pid") == pid),
                 None)
    if event is None:
        return None
    return event["import_seconds"]
