"""The share of tokens whose kept groups of experts hold a group of an
expert held here, over the expert layers and the steps of the profiling
window (event ``profile_window.step_counters``: ``moe_group_reach /
moe_group_tokens``; the loss function's aux counts both under a
group-limited router, ``StepCounter.MOE_GROUP_REACH``). Only those
tokens can send this chip a row: where a chip's experts lie in one
group and the router chooses its groups evenly it is ``topk_group /
n_group``, 0.5 at 4 of 8, and the held experts' load swings with it a
group at a time. A program without such a router gives nothing to
read."""


def read(ctx):
    window = ctx["run"].get("profile_window") or {}
    counters = window.get("step_counters") or {}
    reach, tokens = (counters.get("moe_group_reach"),
                     counters.get("moe_group_tokens"))
    return reach / tokens if reach is not None and tokens else None
