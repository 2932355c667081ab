"""``chipbench/families/dense_gqa/reference.py`` (the Mistral block in
plain float32
``jax.numpy``) against ``models/llama.py``, the code the cells run, at a
toy size on the CPU: the loss and every gradient.

Both sides compute in float32 here (the toy configuration states
float32 parameters and compute, XLA attention), so they differ only by
the order of float32 sums: 2^-24 = 6e-8 a rounding, a few hundred
roundings deep. Losses near 5.5 agree to 1e-5 and gradient leaves to
1e-5 of their largest entry plus 1e-7; computing either side in bf16
(2^-8) would miss both by three orders of magnitude. On the chip the
same comparison runs in every first worker round at the published
widths, against bf16 compute, with the tolerance the family's
``job.py`` gives.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import worker  # noqa: E402
from chipbench.families.dense_gqa import reference  # noqa: E402
from chipbench.families.dense_gqa.job import NAMES  # noqa: E402

LOSS_TOL = 1e-5
GRAD_RTOL, GRAD_ATOL = 1e-5, 1e-7


@pytest.fixture(scope="module")
def job():
    with open(os.path.join(HERE, "tiny.json")) as f:
        model = json.load(f)
    built = worker.build_job(model)
    params = built.init_fn(jax.random.PRNGKey(3))
    # norm scales are ones at init: perturb them so that a reference
    # that dropped a scale would show
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                              a.shape, a.dtype), params)
    batch = worker.batch_for(11, 0, built.vocab_size, 1, built.seq_len)
    return model, built, built.loss_fn, params, batch


def reference_loss(model, params, ids, labels):
    """The reference as one differentiable function of the program's
    parameter tree (``reference.loss`` runs it layer by layer)."""
    h = params["embed_tokens"]["embedding"][ids]
    for i in range(model["num_hidden_layers"]):
        w = {key: params["layers"][a][b][i] for key, (a, b) in NAMES.items()}
        h = reference.block(h, w, model)
    return reference.next_token_loss(
        h, params["norm"]["scale"], params["lm_head"]["kernel"], labels,
        model["rms_norm_eps"])


def test_loss_agrees(job):
    model, config, loss_fn, params, batch = job
    system = float(loss_fn(params, batch, jax.random.PRNGKey(0))[0])
    # as the worker's reference check runs it: layer by layer
    ref = config.reference_loss(params, batch["input_ids"][0],
                                batch["labels"][0])
    assert np.isfinite(ref) and 4.0 < ref < 8.0
    assert abs(system - ref) <= LOSS_TOL, (system, ref)
    # and the differentiable form used below is the same function
    again = float(reference_loss(model, params, batch["input_ids"][0],
                                 batch["labels"][0]))
    assert abs(again - ref) <= 1e-6


def test_gradients_agree(job):
    model, config, loss_fn, params, batch = job
    got = jax.grad(lambda p: loss_fn(p, batch, jax.random.PRNGKey(0))[0])(
        params)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: reference_loss(
            model, p, batch["input_ids"][0], batch["labels"][0]))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want) == 12
    for (path, g), w in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0,
            atol=GRAD_RTOL * scale + GRAD_ATOL,
            err_msg=jax.tree_util.keystr(path))


def test_a_wrong_rotary_base_or_a_missing_mask_would_show(job):
    """The tolerance separates the model from its near misses."""
    model, config, loss_fn, params, batch = job
    ids, labels = batch["input_ids"][0], batch["labels"][0]
    right = float(reference_loss(model, params, ids, labels))
    wrong_theta = float(reference_loss(
        dict(model, rope_theta=10000.0), params, ids, labels))
    wrong_eps = float(reference_loss(
        dict(model, rms_norm_eps=1e-2), params, ids, labels))
    assert abs(wrong_theta - right) > 100 * LOSS_TOL
    assert abs(wrong_eps - right) > 100 * LOSS_TOL


def test_batch_k_is_a_function_of_seed_and_k():
    a = worker.batch_for(2 ** 31 + 5, 7, 256, 2, 16)
    b = worker.batch_for(2 ** 31 + 5, 7, 256, 2, 16)
    c = worker.batch_for(2 ** 31 + 5, 8, 256, 2, 16)
    d = worker.batch_for(2 ** 31 + 6, 7, 256, 2, 16)
    assert np.array_equal(a["input_ids"], b["input_ids"])
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    assert not np.array_equal(a["input_ids"], d["input_ids"])
    assert np.array_equal(a["input_ids"][:, 1:], a["labels"][:, :-1])
    assert a["input_ids"].shape == (2, 16) and a["input_ids"].max() < 256
