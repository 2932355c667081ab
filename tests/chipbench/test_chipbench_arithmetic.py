"""The benchmark's FLOP, byte and roofline arithmetic on its two
configurations: ``arithmetic.py`` (peaks, shares, roofline) and the
dense family's ``flops.py``. No JAX: counted from the configuration
files."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import arithmetic  # noqa: E402
from chipbench.families.dense_gqa import flops  # noqa: E402


def config(name):
    with open(os.path.join(REPO, "chipbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params,flops_per_step", [
    ("mistral-7b-v0.3-d8", 2_013_335_552, 9.9e13),
    ("mistral-7b-v0.3-d20-fsdp4", 4_630_679_552, 4.75e14),
])
def test_parameters_and_model_flops(name, params, flops_per_step):
    model = config(name)
    assert model["family"] == "dense_gqa"
    assert flops.param_count(model) == params
    assert flops.model_flops_per_step(model) == pytest.approx(
        flops_per_step, rel=0.005)


def test_model_flops_by_hand_for_d8():
    model = config("mistral-7b-v0.3-d8")
    layer = (4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336)
    matmul = 8 * layer + 4096 * 32768  # the head, not the embedding
    assert flops.matmul_params(model) == matmul
    per_token = 6 * matmul + 6 * 8 * 4096 * 4096
    assert flops.model_flops_per_token(model, 4096) == per_token
    # the embedding table and the norms are parameters, not FLOPs
    assert flops.param_count(model) - matmul == (
        32768 * 4096 + 2 * 8 * 4096 + 4096)


def test_attention_is_seven_percent_of_the_d8_step():
    model = config("mistral-7b-v0.3-d8")
    share = (flops.attention_flops_per_token(model, 4096)
             / flops.model_flops_per_token(model, 4096))
    assert 0.06 < share < 0.08


def test_the_kernels_are_held_to_the_work_the_model_asks_of_them():
    """Six half-square matmuls a head and layer, the attention term of
    the model FLOPs: a kernel that recomputes or replays (11 now) does
    not raise its own roofline share by doing more."""
    model = config("mistral-7b-v0.3-d8")
    by_hand = 8 * 2 * 32 * (2 * 4096 * 4096 * 128 / 2) * 6
    assert flops.kernel_flops_per_step(model) == by_hand
    assert flops.kernel_flops_per_step(model) == (
        flops.attention_flops_per_token(model, 4096) * 2 * 4096)
    q, k = 2 * 32 * 4096 * 128 * 2, 2 * 8 * 4096 * 128 * 2
    # forward: q, k, v in, o out; backward: q, k, v, o, do in, dq, dk, dv
    assert flops.kernel_bytes_per_step(model) == 8 * (
        (2 * q + 2 * k) + (4 * q + 4 * k))


def test_flash_roofline_is_bound_by_compute():
    model = config("mistral-7b-v0.3-d8")
    work = flops.kernel_flops_per_step(model)
    moved = flops.kernel_bytes_per_step(model)
    least, bound = arithmetic.roofline(work, moved, "TPU v5 lite")
    assert bound == "compute"
    assert least == pytest.approx(work / 197e12)
    # and a byte-heavy call is bound by memory
    assert arithmetic.roofline(1e9, 1e9, "TPU v5 lite")[1] == "memory"


def test_mfu_of_a_known_step():
    # 9.85e13 FLOPs in one second on one chip of 197 TFLOP/s is half
    assert arithmetic.mfu_pct(9.85e13, 1.0, 1, "TPU v5 lite") == (
        pytest.approx(50.0))
    assert arithmetic.mfu_pct(9.85e13, 1.0, 4, "TPU v5 lite") == (
        pytest.approx(12.5))


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "about"])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(arithmetic.UnknownDevice):
        arithmetic.peaks(kind)
