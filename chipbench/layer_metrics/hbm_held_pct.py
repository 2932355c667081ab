"""The share of the fullest chip's memory that is held after the
traced steps: event ``profile_window.memory``, 100 x (``bytes_in_use``
+ ``bytes_reserved``, the loaded programs' arenas) / ``bytes_limit``,
from ``memory_stats()``. A backend that keeps no such statistics (the
CPU) gives nothing to read."""


def read(ctx):
    memory = (ctx["run"].get("profile_window") or {}).get("memory")
    if not memory or not memory.get("bytes_limit"):
        return None
    return 100.0 * (memory["bytes_in_use"] + memory["bytes_reserved"]) \
        / memory["bytes_limit"]
