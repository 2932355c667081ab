"""Programs the restarted worker had to compile because the persistent
cache did not hold them, up to its first completed step."""


def read(ctx):
    resume = ctx["resume"]
    if not resume or not resume["steps"]:
        return None
    return resume["steps"][0]["cache_misses"]
