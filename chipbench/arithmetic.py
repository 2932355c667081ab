"""The benchmark's arithmetic that belongs to no model: the table of
published peaks, a share of the bf16 peak, the roofline. Nothing here
imports JAX or the program. What a step of one architecture costs
(parameters, model FLOPs, the kernels' FLOPs and bytes) is in
``families/<family>/flops.py``, named by the configuration file's
``family``.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    pass


def peaks(device_kind):
    """The published peaks of ``device_kind``; raises for a kind the
    table does not hold."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            "chipbench/peaks.json")
    return entry


def mfu_pct(flops, seconds, chips, device_kind):
    """``flops`` done in ``seconds`` on ``chips`` chips as a share of
    the published bf16 peak."""
    return 100.0 * flops / seconds / (
        chips * peaks(device_kind)["bf16_flops_per_s"])


def roofline(flops, bytes_moved, device_kind):
    """(least seconds, which bound binds) for work of ``flops`` and
    ``bytes_moved`` on one chip."""
    p = peaks(device_kind)
    t_flops = flops / p["bf16_flops_per_s"]
    t_bytes = bytes_moved / p["hbm_bytes_per_s"]
    return (max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")
