"""Seconds the measured worker's ``ElasticTrainer`` took to construct its
checkpoint manager (the Orbax import; 0 in a cell that does not save)
and to build its program (``trainer_ready`` event,
``ckpt_manager_seconds + build_seconds``)."""


def read(ctx):
    pid = ctx["run"]["worker"]["pid"]
    event = next((e for e in ctx["run"]["events"]
                  if e.get("kind") == "trainer_ready" and e.get("pid") == pid),
                 None)
    if event is None:
        return None
    return event["ckpt_manager_seconds"] + event["build_seconds"]
