"""Latent-attention decoder with shared and routed gated experts (the
DeepSeek-V2/V3 block; A.X-K1 publishes it at 61 layers of 7168):
multi-head latent attention (MLA) in every layer, a dense SwiGLU FFN in
the leading ``first_k_dense`` layers and an expert layer in the rest.
Training only.

Every layer is ``x = x + MLA(RMSNorm(x)); x = x + FFN(RMSNorm(x))``, no
biases, an untied head. MLA, per token::

    c_q = RMSNorm(x W_qa)                     q = c_q W_qb
    [c_kv | k_r] = x W_kva                    c_kv = RMSNorm(c_kv)
    [q_nope | q_rope] = q  (a head)           [k_nope | v] = c_kv W_kvb
    s = (q_nope . k_nope + rot(q_rope) . rot(k_r)) * scale
    out = concat_heads(softmax_causal(s) v) W_o

``k_r`` is ONE rotary key head for all query heads; ``rot`` is the
rotary embedding with YaRN's blended inverse frequencies
(``yarn_inv_freq``) and ``scale = (nope + rope)^-0.5 * m^2`` with
``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (``yarn_mscale``). This
is the materialised form: keys and values are expanded from the latent
and no weight is absorbed. On a TPU the attention is
``ops.flash_attention.flash_attention_mla`` (the two score operands side
by side, the shared rotary key read once a k block);
``use_kernels=False`` takes XLA's dense attention (a CPU rehearsal).
Each layer is its own checkpoint under ``remat_policy``, and so is a
prediction module; the checkpoint keeps its attention kernel's output
and logsumexp beside what the policy saves (``_kept_names``: the latent
flash ops' ``ops.flash_attention.KEPT_NAMES``, named inside their
forward rules), so the forward kernel runs once a step and not again
in the layer's replay.

The expert layer, per token::

    s = sigmoid(x W_r)  over ALL n_routed_experts, float32
    top = the num_experts_per_tok largest s
    y = shared(x) + routed_scaling_factor
        * sum_{i in top, i held here} (s_i / sum_{top} s) expert_i(x)

with ``shared`` and every ``expert_i`` a SwiGLU of
``moe_intermediate_size``. ``experts_held`` says which of the
``n_routed_experts`` this chip holds (all of them when empty): the
router is whole, the normalisation is over all the selected experts,
and what the experts held elsewhere would add is left out, as on a chip
of an expert-parallel deployment before an exchange that one chip does
not have (``ops.moe.held_expert_ffn``). No capacity: an assignment to a
held expert is computed unless the static row buffer
(``expert_row_factor`` times the uniform expectation) is full; the
loss function's aux counts the assignments left out, and a job that
promises none reads it there. A layer computes on the smallest halving
of that buffer that holds the rows it got (``ops.moe.
held_row_ladder``).
``seq_aux``: the sequence-wise balance loss (``ops.moe.
sequence_balance_loss``) times ``balance_loss_weight`` is added a
layer.

``router_bias``: the router holds a per-expert bias that is added to
the scores for the selection alone (DeepSeek-V3's ``noaux_tc``; ``ops.
moe.sigmoid_topk_routing``). It takes no gradient; the rule that
updates it from the experts' load is not part of a step here.

``hc_mult`` n > 1 (Xing4.0 publishes 4): the residual is n streams a
token, side by side in one ``[B, S, n * D]`` through the layer scans
(stream j the slice ``j * D`` to ``(j + 1) * D``), and each of a layer's
two sublayers reads a per-token mix of them and writes back through a
doubly stochastic n x n mapping and an n x 1 one (manifold-constrained
hyper-connections, ``ops/hyper_connections.py``). All streams enter as
the token's embedding and leave summed. ``hc_mult`` 1 is the plain
residual above, not a one-stream mapping.

``mtp_layers`` (DeepSeek-V3's multi-token prediction, depth 1 as
published): with ``h_i`` the main model's streams summed before the
final norm, module ``k`` computes ``h'_i = [RMSNorm(h_i) | RMSNorm(Emb(
t_{i+k}))] W_eh``, one more expert layer of the model's own kind, a
norm of the final norm's form and the main model's head, and predicts
``t_{i+k+1}``; its cross entropy, over the positions that have such a
target, times ``mtp_loss_weight`` joins the loss. The table and the
head are the main model's own: their gradients have two sources.

**The sparse switches** (A.X-K2 publishes all four; at their defaults
the model is the block above and its program the same):

``index_n_heads`` J > 0: every layer attends to the ``index_topk`` keys
an indexer selects for each query (DeepSeek-V3.2's ``Indexer``;
``ops/sparse_attention.py``, its latent layout). The indexer reads
``u' = stop_gradient(u)`` and ``c_q' = stop_gradient(c_q)``::

    qI_j = (c_q' W_Iq)_j      J heads of index_head_dim, the first
                              qk_rope_head_dim columns rotated
    kI = LayerNorm(u' W_Ik)   ONE head, scale and bias, the same
                              columns rotated
    w = u' W_Iw               J a token
    I[t, s] = (J E)^-1/2 sum_j w[t, j] relu(qI_j[t] . kI[s])   s <= t

A query attends to every causal key where there are no more than
``index_topk``, else to the ``index_topk`` of largest ``I``, ties to
the lower position. The indexer is trained by its own loss, ``L_I =
mean_t KL(pbar[t] || softmax over the selected of I[t])``, ``pbar`` the
mean over the heads of the attention's probabilities as data;
``make_loss_fn`` adds ``index_loss_weight`` times its sum over the
layers. The indexer's leaves get their gradient from ``L_I`` alone,
every other leaf from the rest alone. A layer's checkpoint keeps the
selected attention's output and logsumexp (``sparse_attention.
KEPT_NAMES``), so its replay leaves ``dsa_attn_fwd`` out, and the three
gradients of ``L_I`` to the indexer's queries, key head and weights
(``INDEX_KEPT_NAMES``), which the loss's one kernel makes beside its
value in the forward pass: ``dsa_index_kl`` runs once a layer a step,
and neither the replay nor the backward pass has it. Not written for
streams or a prediction module.

``attn_output_gate``: ``x' = x + (a * sigmoid(u W_g)) W_o``, one gate
value a head and value column, read from the layer's normed input.

``gated_norm_rank`` r > 0: the layers' two norms and the final norm
are ``n * sigmoid((n A) B)``, ``n = RMSNorm(x)``, ``A [D, r]``, ``B [r,
D]``; the latent norms, the indexer's and a prediction module's are
plain.

``n_group`` > 1: the router keeps ``topk_group`` of ``n_group`` groups
of experts a token (the groups whose two largest selection scores add
up to the most) and selects among their experts alone
(``ops.moe.group_limited_routing``).

**The differential switches** (Motif-3-Beta publishes all of them; at
their defaults the model is the block above and its program the same):

``num_kv_heads`` G < ``num_heads`` H: ``kv_b_proj`` makes G key and
value heads and query heads ``rg .. rg + r - 1`` (``r = H / G``) read
head ``g`` (``ops.flash_attention.flash_attention_mla_grouped``: a
group's query heads in one grid step, a K/V block read once a group).
``num_noise_heads`` = G: the last query head of a group is its noise
head (grouped differential attention, V2)::

    lam = sigmoid(u W_lam)          a float32 value a token and signal head
    d_(g,i) = a_(rg+i) - lam_(g,i) * a_(rg+r-1)      i = 0 .. r - 2

every head with its own softmax; ``o_proj`` and the output gate read
the ``H - G`` subtracted heads; no norm after the subtraction and no
``(1 - lam0)`` factor (``models/sambay.py`` keeps V1's form, with both).
``sliding_window`` w > 0: every layer but ``full_attention_layers``
attends to the band ``t - w < s <= t`` (the ``flash_mla_win_*``
kernels on the band's tiles, squares of ``window_block``); a stack of
both kinds is still ONE scan over layers, which carries a flag a layer
(``flash_attention_mla_by_kind``: the forward and the backward branch
once each). ``ffn_activation`` ``"poly_norm"``: the dense FFN, the
shared expert and a layer's routed experts together each hold
PolyNorm's three weights and bias (``poly_norm``), in place of SiLU;
the held experts' gate stage takes it as a row-wise activation with
parameters (``ops.moe.held_expert_ffn``). ``router_bias_rate`` > 0:
the selection bias is no parameter but a buffer of the training state
(``init_buffers``, ``TrainState.buffers``) that starts at zero; the
loss function reads it as data and counts, a layer, the tokens that
selected each of the router's experts, and the compiled step moves it
after the optimizer (``update_buffers``, ``ops.moe.
selection_bias_update``). Not written beside an indexer, gated norms
or a group limit.

The loss function's aux carries, summed over the expert layers, the
counters of ``telemetry.names.StepCounter``: assignments to held
experts, the fullest expert's, those past the bound, and the rows of
the buffer each layer computed on; with streams the mean defect of
``H_res``, with a prediction module its loss, with a group limit the
tokens whose kept groups reach an expert held here, with an indexer the
selection's counters, the indexer's loss and the bytes its layers'
checkpoints keep (``gqa_moe``'s names), without one the bytes of the
latent kernels' outputs that the layers' and the modules' checkpoints
keep (``ATTN_KEPT_BYTES``), with
noise heads lambda's mean, with a window the band's tiles, and with
``router_bias_rate`` the loads that move the bias (``ROUTER_LOAD``,
which ``update_buffers`` takes out again).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
import functools
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import common
from dlrover_tpu.models.common import (
    cast_floats,
    dense_init,
    layer_norm,
    rms_norm,
)
from dlrover_tpu.models.losses import IGNORE_INDEX, lm_head_loss
from dlrover_tpu.ops import hyper_connections as hc
from dlrover_tpu.ops import flash_attention, moe, sparse_attention
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.remat import apply_remat, remat_enabled
from dlrover_tpu.parallel.accelerate import StepBuffers
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

KINDS = ("dense", "moe")
# a layer's attention, where the model has a sliding window: over every
# causal key, or over the band
ATTENTION_KINDS = ("full", "window")


@dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432  # of a leading dense layer's FFN
    moe_intermediate_size: int = 2048  # of one expert, shared or routed
    num_layers: int = 61
    first_k_dense: int = 1
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192  # the router's width
    # the routed experts this chip holds, by index; () = all of them
    experts_held: Tuple[int, ...] = ()
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    balance_loss_weight: float = 1e-4
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 8192
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat_policy: str = "full"
    # the Pallas kernels (Mosaic on a TPU, the interpreter elsewhere);
    # False takes XLA's dense attention and the einsum experts
    use_kernels: bool = True
    # None = interpret off the TPU; False forces Mosaic (a deviceless
    # compile traced on a CPU host)
    kernel_interpret: Any = None
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # the row buffer of the held experts, as a multiple of what uniform
    # routing sends them (``ops.moe.held_row_bound``), and its row tile
    expert_row_factor: float = 4.0
    expert_block_t: int = 128
    # a per-expert bias on the scores, for the selection alone
    router_bias: bool = False
    # residual streams a token; 1 = the plain residual. Above 1: the
    # Sinkhorn iterations of ``H_res``, the clamp under its exp, and
    # the eps of the norm over the streams
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    hc_eps: float = 1e-6
    # multi-token-prediction modules, and the weight of their loss
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # the sparse switches (module docstring): the indexer's heads (0 =
    # none: causal attention over every key), their width, the keys a
    # query keeps, the weight of the indexer's loss
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 2048
    index_loss_weight: float = 1.0
    # tiles, as ``GqaMoeConfig`` names them: the selection's and the
    # indexer loss's query block, the key tile of all the sparse
    # kernels, the attention's query block
    index_block_q: int = 128
    index_block_k: int = 512
    sparse_block_q: int = 512
    # a sigmoid gate on the attention's output, a head and value column
    attn_output_gate: bool = False
    # the rank of the layers' and the final norm's gate; 0 = plain norms
    gated_norm_rank: int = 0
    # the router's groups of experts and those a token keeps; 1 = none
    n_group: int = 1
    topk_group: int = 1
    # the differential switches (module docstring): the key and value
    # heads (0 = one a query head) and, among ``num_heads``, the noise
    # heads (0 = none; else one a key/value head, the last query head
    # of its group)
    num_kv_heads: int = 0
    num_noise_heads: int = 0
    # a causal band of ``sliding_window`` keys (0 = none) on every
    # layer but those listed in ``full_attention_layers`` (indices into
    # the model's layers; a prediction module's layer k is
    # ``num_layers + k``), walked in square tiles of ``window_block``
    sliding_window: int = 0
    full_attention_layers: Tuple[int, ...] = ()
    window_block: int = 128
    # the FFNs' activation on the gate rows: "silu", or "poly_norm"
    # with its output scale, the clamp of its bias and its eps
    ffn_activation: str = "silu"
    polynorm_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    polynorm_eps: float = 1e-6
    # > 0: the router's selection bias is no parameter but a buffer of
    # the training state that starts at zero and that every step moves
    # by this much, by the sign of each expert's load against the mean
    router_bias_rate: float = 0.0

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(self.experts_held) or tuple(
            range(self.n_routed_experts))

    @property
    def moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def out_heads(self) -> int:
        """The heads ``o_proj`` reads: all but the noise heads."""
        return self.num_heads - self.num_noise_heads

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5`` times YaRN's ``m^2``; at
        ``rope_factor`` 1 (a model that computes no YaRN) ``m`` is 1 and
        this is the plain ``d^-0.5``."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim
                + self.qk_rope_head_dim) ** -0.5 * m * m


def mla_moe_tiny(**overrides) -> MlaMoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                moe_intermediate_size=32, num_layers=3, num_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=24,
                num_experts_per_tok=4, rope_original_max=16,
                max_seq_len=64, expert_block_t=8, use_kernels=False)
    base.update(overrides)
    return MlaMoeConfig(**base)


def layer_plan(config: MlaMoeConfig) -> List[str]:
    """The kind of every layer, by index."""
    if not 0 <= config.first_k_dense < config.num_layers:
        raise ValueError(
            f"{config.first_k_dense} leading dense layers of "
            f"{config.num_layers}: at least one expert layer follows")
    if config.index_n_heads and (config.hc_mult > 1 or config.mtp_layers):
        raise ValueError("an indexer beside streams or a prediction "
                         "module is not written")
    c = config
    differential = (c.num_kv_heads or c.num_noise_heads or c.sliding_window
                    or c.router_bias_rate or c.ffn_activation != "silu")
    if differential and (c.index_n_heads or c.gated_norm_rank
                         or c.n_group > 1):
        raise ValueError("the differential switches beside an indexer, "
                         "gated norms or a group limit are not written")
    if c.num_heads % c.kv_heads or c.num_noise_heads not in (0, c.kv_heads):
        raise ValueError(
            f"{c.num_heads} query heads on {c.kv_heads} key/value heads "
            f"with {c.num_noise_heads} noise heads: whole groups, and "
            "one noise head a group or none")
    if c.num_noise_heads and c.num_heads == c.kv_heads:
        raise ValueError("a group of one head has no signal head")
    if c.ffn_activation not in ("silu", "poly_norm"):
        raise ValueError(f"ffn_activation {c.ffn_activation!r}")
    if c.router_bias_rate and c.router_bias:
        raise ValueError("the selection bias is a parameter the step "
                         "leaves alone (router_bias) or a buffer it moves "
                         "(router_bias_rate), not both")
    full = c.full_attention_layers
    if full and not (c.sliding_window and all(
            0 <= i < c.num_layers + c.mtp_layers for i in full)):
        raise ValueError(f"full_attention_layers {full}: layers of a "
                         "model with a sliding_window")
    return (["dense"] * config.first_k_dense
            + ["moe"] * config.moe_layers)


def attention_plan(config: MlaMoeConfig) -> List[str]:
    """``"full"`` or ``"window"`` for every layer by index, the
    prediction modules' layers last."""
    c = config
    return ["window" if c.sliding_window and i not in c.full_attention_layers
            else "full" for i in range(c.num_layers + c.mtp_layers)]


def layer_kinds(config: MlaMoeConfig) -> Dict[str, int]:
    plan = layer_plan(config)
    kinds = {kind: plan.count(kind) for kind in KINDS}
    if config.sliding_window:  # the main model's layers by attention
        attention = attention_plan(config)[:config.num_layers]
        kinds.update({a: attention.count(a) for a in ATTENTION_KINDS})
    return kinds


# -- rotary, YaRN -----------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(config: MlaMoeConfig) -> List[float]:
    """The rotary part's inverse frequencies: ``1 / theta^(2i/d)`` for
    the pairs that turn more than ``beta_fast`` times over the original
    context, that over ``factor`` for those that turn fewer than
    ``beta_slow`` times, and a linear blend by pair index between. At
    ``rope_factor`` 1 (no YaRN: Motif-3-Beta's ``apply_yarn_scaling``
    false) every pair is the plain ``1 / theta^(2i/d)``, whatever the
    other fields say, and ``_rotary_tables``' scale is 1."""
    c, d = config, config.qk_rope_head_dim

    def pair_of(turns):  # the pair that turns ``turns`` times
        return (d * math.log(c.rope_original_max / (turns * 2 * math.pi))
                / (2 * math.log(c.rope_theta)))

    low = max(math.floor(pair_of(c.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(c.rope_beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        plain = 1.0 / c.rope_theta ** (2 * i / d)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(plain / c.rope_factor * ramp + plain * (1.0 - ramp))
    return out


def _rotary_tables(seq: int, config: MlaMoeConfig):
    angles = (jnp.arange(seq, dtype=jnp.float32)[:, None]
              * jnp.asarray(yarn_inv_freq(config), jnp.float32)[None, :])
    scale = (yarn_mscale(config.rope_factor, config.rope_mscale)
             / yarn_mscale(config.rope_factor, config.rope_mscale_all_dim))
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale  # [S, d/2]


def _rotate(x, cos, sin):
    """Pair ``i`` is (x[i], x[i + d/2]); ``x`` is [..., S, d] and the
    tables [S, d/2]. float32 inside, x's dtype out."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# -- init -------------------------------------------------------------------


def _gated_norm(key, tag, lead, c: MlaMoeConfig):
    """A norm over the hidden state, with its gate's two factors where
    the model gates its norms (keys of their own, ``tag`` folded into
    ``key``: the other leaves are the ones a model without draws)."""
    d, r, dt = c.hidden_size, c.gated_norm_rank, c.param_dtype
    out = common.norm_init(lead, d, dt)
    if r:
        k = jax.random.split(jax.random.fold_in(key, tag), 2)
        out["gate_a"] = dense_init(k[0], lead + (d, r), dt)
        out["gate_b"] = dense_init(k[1], lead + (r, d), dt)
    return out


def _sparse_init(key, lead, c: MlaMoeConfig):
    """What the sparse switches add to a layer's attention: the
    indexer's four leaves and the output gate (keys folded out of the
    attention's ``key``)."""
    d, dt = c.hidden_size, c.param_dtype
    j, e = c.index_n_heads, c.index_head_dim
    out = {}
    if j or c.attn_output_gate:
        k = jax.random.split(jax.random.fold_in(key, 5), 4)
    if c.num_noise_heads:  # lambda, a value a token and signal head
        out["lam_proj"] = {"kernel": dense_init(
            jax.random.fold_in(key, 6), lead + (d, c.out_heads), dt)}
    if j:
        out["index"] = {
            "q_proj": {"kernel": dense_init(
                k[0], lead + (c.q_lora_rank, j * e), dt)},
            "k_proj": {"kernel": dense_init(k[1], lead + (d, e), dt)},
            "k_norm": common.norm_init(lead, e, dt, bias=True),
            "w_proj": {"kernel": dense_init(k[2], lead + (d, j), dt)}}
    if c.attn_output_gate:
        out["g_proj"] = {"kernel": dense_init(
            k[3], lead + (d, c.out_heads * c.v_head_dim), dt)}
    return out


def _mla_init(key, lead, c: MlaMoeConfig):
    d, h, dt = c.hidden_size, c.num_heads, c.param_dtype
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    k = jax.random.split(key, 5)

    def proj(key, fan_in, fan_out):
        return {"kernel": dense_init(key, lead + (fan_in, fan_out), dt)}

    return {
        "q_a_proj": proj(k[0], d, c.q_lora_rank),
        "q_a_norm": common.norm_init(lead, c.q_lora_rank, dt),
        "q_b_proj": proj(k[1], c.q_lora_rank, h * qk),
        "kv_a_proj": proj(k[2], d, c.kv_lora_rank + c.qk_rope_head_dim),
        "kv_a_norm": common.norm_init(lead, c.kv_lora_rank, dt),
        "kv_b_proj": proj(k[3], c.kv_lora_rank, c.kv_heads * (
            c.qk_nope_head_dim + c.v_head_dim)),
        "o_proj": proj(k[4], c.out_heads * c.v_head_dim, d),
        **_sparse_init(key, lead, c),
    }


def _swiglu_init(key, lead, d, f, dt):
    k = jax.random.split(key, 3)
    return {
        "gate_proj": {"kernel": dense_init(k[0], lead + (d, f), dt)},
        "up_proj": {"kernel": dense_init(k[1], lead + (d, f), dt)},
        "down_proj": {"kernel": dense_init(k[2], lead + (f, d), dt)},
    }


def _act_init(lead, c: MlaMoeConfig):
    """``{"act": PolyNorm's leaves}`` for one FFN a layer (its three
    weights at 1/3, its bias at 0), or nothing under SiLU."""
    if c.ffn_activation != "poly_norm":
        return {}
    return {"act": {"weight": jnp.full(lead + (3,), 1.0 / 3, c.param_dtype),
                    "bias": jnp.zeros(lead + (1,), c.param_dtype)}}


# the std of the router's selection bias at the start: the fourth and
# fifth of 64 sigmoid scores lie about 0.02 apart, so a bias of this
# size moves the selected set of about a third of the tokens a layer
# and the held experts' loads by about a tenth
ROUTER_BIAS_STD = 0.01


def _layers_init(key, n, c: MlaMoeConfig, kind):
    lead, d, dt = (n,), c.hidden_size, c.param_dtype
    k = jax.random.split(key, 4)
    out = {"input_norm": _gated_norm(key, 3, lead, c),
           "attn": _mla_init(k[0], lead, c),
           "post_norm": _gated_norm(key, 4, lead, c)}
    if c.hc_mult > 1:
        kh = jax.random.split(jax.random.fold_in(key, 1), 2)
        out["hc_attn"] = hc.init(kh[0], lead, c.hc_mult, d, dt)
        out["hc_ffn"] = hc.init(kh[1], lead, c.hc_mult, d, dt)
    if kind == "dense":
        out["mlp"] = {**_swiglu_init(k[1], lead, d, c.intermediate_size,
                                     dt), **_act_init(lead, c)}
        return out
    f, held = c.moe_intermediate_size, len(c.held)
    experts = _swiglu_init(k[3], lead + (held,), d, f, dt)
    out["moe"] = {
        "router": {"kernel": dense_init(
            k[1], lead + (d, c.n_routed_experts), dt)},
        "shared": {**_swiglu_init(k[2], lead, d, f * c.n_shared_experts,
                                  dt), **_act_init(lead, c)},
        # one activation for all of a layer's routed experts
        "experts": {"gate": experts["gate_proj"], "up": experts["up_proj"],
                    "down": experts["down_proj"], **_act_init(lead, c)},
    }
    if c.router_bias:
        out["moe"]["router"]["bias"] = ROUTER_BIAS_STD * jax.random.normal(
            jax.random.fold_in(key, 2), lead + (c.n_routed_experts,), dt)
    return out


def _mtp_init(key, c: MlaMoeConfig):
    """The prediction modules, stacked: the two norms of a module's
    inputs, its projection of their concatenation, its layer (an expert
    layer of the model's kind) and its final norm."""
    lead, d, dt = (c.mtp_layers,), c.hidden_size, c.param_dtype
    k = jax.random.split(key, 2)
    return {"h_norm": common.norm_init(lead, d, dt),
            "e_norm": common.norm_init(lead, d, dt),
            "eh_proj": {"kernel": dense_init(k[0], lead + (2 * d, d), dt)},
            "layer": _layers_init(k[1], c.mtp_layers, c, "moe"),
            "norm": common.norm_init(lead, d, dt)}


def init(rng: jax.Array, config: MlaMoeConfig) -> Dict:
    c = config
    layer_plan(c)  # refuses a plan without an expert layer
    if sorted(set(c.held)) != list(c.held) or not (
            0 <= c.held[0] and c.held[-1] < c.n_routed_experts):
        raise ValueError(f"experts_held {c.held}: distinct indices in "
                         f"order, below {c.n_routed_experts}")
    k = jax.random.split(rng, 4)
    out = {
        # a table of std 1 beside kernels of std 1/sqrt(fan_in): a
        # token's own vector is as large as what a block adds to it.
        # With the other models' 0.02 the stream at random weights is
        # what the blocks add, some 3-11% of it a direction all tokens
        # share, and a random router then sends a few experts most of
        # the rows (PERF.md section 6, PR 34)
        "embed_tokens": {"embedding": jax.random.normal(
            k[0], (c.vocab_size, c.hidden_size), c.param_dtype)},
        "moe_layers": _layers_init(k[2], c.moe_layers, c, "moe"),
        "norm": _gated_norm(rng, 2, (), c),
        "lm_head": {"kernel": dense_init(
            k[3], (c.hidden_size, c.vocab_size), c.param_dtype)},
    }
    if c.first_k_dense:
        out["dense_layers"] = _layers_init(k[1], c.first_k_dense, c,
                                           "dense")
    if c.mtp_layers:
        out["mtp"] = _mtp_init(jax.random.fold_in(rng, 1), c)
    return out


# -- forward ----------------------------------------------------------------


def _rms(x, p, c):
    n = rms_norm(x, p["scale"], c.rms_norm_eps)
    if "gate_a" not in p:
        return n
    with jax.named_scope(DeviceScope.GATED_NORM):
        gate = jax.nn.sigmoid(jnp.einsum(
            "...r,rd->...d", n @ p["gate_a"], p["gate_b"],
            preferred_element_type=jnp.float32))
        return n * gate.astype(n.dtype)


def _rotate_leading(x, width, cos, sin):
    """``x`` [..., S, d] with its first ``width`` columns rotated."""
    return jnp.concatenate(
        [_rotate(x[..., :width], cos, sin), x[..., width:]], axis=-1)


def _indexer(x, c_q, p, c: MlaMoeConfig, rotary):
    """The indexer's queries ``[B, J, S, E]``, its one key ``[B, S, E]``
    and its per-head weights ``[B, S, J]`` from the layer's normed
    input ``x`` and the query latent ``c_q``, both detached."""
    x, c_q = lax.stop_gradient(x), lax.stop_gradient(c_q)
    j, e, dr = c.index_n_heads, c.index_head_dim, c.qk_rope_head_dim
    qi = jnp.einsum("bsr,rje->bjse", c_q, p["q_proj"]["kernel"].reshape(
        c.q_lora_rank, j, e))
    ki = layer_norm(x @ p["k_proj"]["kernel"], p["k_norm"]["scale"],
                    p["k_norm"]["bias"], c.rms_norm_eps)
    return (_rotate_leading(qi, dr, *rotary),
            _rotate_leading(ki[:, None], dr, *rotary)[:, 0],
            x @ p["w_proj"]["kernel"])


def _dense_latent_attention(q_nope, q_rope, k_nope, k_rope, v, scale,
                            window, windowed):
    """XLA's dense form of the grouped and windowed latent attention (a
    CPU rehearsal): every query head's own softmax over its group's
    keys, over the band where ``window`` is set and the traced
    ``windowed`` (None: yes) says so."""
    group = q_nope.shape[1] // k_nope.shape[1]
    k_nope, v = (jnp.repeat(a, group, axis=1) for a in (k_nope, v))
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhqd,bkd->bhqk", q_rope, k_rope[:, 0],
                           preferred_element_type=jnp.float32)) * scale
    seq = q_nope.shape[2]
    ahead = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]
    seen = ahead >= 0
    if window:
        band = seen & (ahead < window)
        seen = band if windowed is None else jnp.where(windowed != 0, band,
                                                       seen)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _differential(out, x, p, c: MlaMoeConfig):
    """Grouped differential attention's subtraction: of the ``[B, H, S,
    Dv]`` outputs of a group's heads (each its own softmax) the last is
    the group's noise head, taken from every signal head times that
    head's ``lam = sigmoid(u W_lam)``, a float32 value a token.
    ``([B, H - G, S, Dv], lambda's mean)``."""
    b, h, s, dv = out.shape
    g = c.kv_heads
    with jax.named_scope(DeviceScope.ATTN_DIFF):
        lam = jax.nn.sigmoid(jnp.einsum(
            "bsd,dn->bsn", x, p["lam_proj"]["kernel"],
            preferred_element_type=jnp.float32))
        heads = out.reshape(b, g, h // g, s, dv)
        lam_h = lam.reshape(b, s, g, h // g - 1).transpose(0, 2, 3, 1)
        out = (heads[:, :, :-1].astype(jnp.float32) - lam_h[..., None]
               * heads[:, :, -1:].astype(jnp.float32)).astype(out.dtype)
        return out.reshape(b, c.out_heads, s, dv), jnp.mean(lam)


@jax.named_scope(DeviceScope.MLA)
def _mla(x, p, c: MlaMoeConfig, rotary, attention="full", windowed=None):
    """Latent attention of the normed ``x`` [B, S, D]; with an indexer
    ``(output, the indexer's loss a query, the Selection)``, with noise
    heads ``(output, lambda's mean)``. ``attention``: the layer's kind
    (``ATTENTION_KINDS``), or ``"mixed"`` where the traced ``windowed``
    says which it is."""
    b, s, _ = x.shape
    h, dn, dr, dv = (c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                     c.v_head_dim)
    window = c.sliding_window if attention != "full" else 0
    # a head's [nope | rope] and [nope | value] columns are projected
    # apart (the weights are sliced, which is cheap, never the
    # activations), so no [H, S, 192] or [H, S, 256] tensor exists
    w_q = p["q_b_proj"]["kernel"].reshape(c.q_lora_rank, h, dn + dr)
    w_kv = p["kv_b_proj"]["kernel"].reshape(c.kv_lora_rank, c.kv_heads,
                                            dn + dv)

    def heads(latent, w):
        return jnp.einsum("bsr,rhd->bhsd", latent, w)

    c_q = _rms(x @ p["q_a_proj"]["kernel"], p["q_a_norm"], c)
    ckv = x @ p["kv_a_proj"]["kernel"]
    c_kv = _rms(ckv[..., :c.kv_lora_rank], p["kv_a_norm"], c)
    if c.kv_heads != h:
        # grouped: the queries are ONE matmul and split afterwards. The
        # two sliced projections' weight gradients, padded back into
        # ``q_b_proj``'s, are a convolution that the v5e's compiler
        # lays out rank-minor at 80 heads: 142 ms a layer where the
        # matmul is 1.3 (PERF.md section 6, PR 55); the 252 MB
        # [S, H, 192] tensor this makes is read once
        q = (c_q @ p["q_b_proj"]["kernel"]).reshape(
            b, s, h, dn + dr).transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], *rotary)
    else:
        q_nope = heads(c_q, w_q[..., :dn])
        q_rope = _rotate(heads(c_q, w_q[..., dn:]), *rotary)
    k_nope, v = heads(c_kv, w_kv[..., :dn]), heads(c_kv, w_kv[..., dn:])
    k_rope = _rotate(ckv[:, None, :, c.kv_lora_rank:], *rotary)  # one head
    if c.index_n_heads:
        how = dict(use_kernels=c.use_kernels, interpret=c.kernel_interpret)
        with jax.named_scope(DeviceScope.DSA_INDEX):
            qi, ki, w = _indexer(x, c_q, p["index"], c, rotary)
            selection = sparse_attention.select_topk(
                qi, ki, w, c.index_topk, block_q=c.index_block_q,
                block_k=c.index_block_k, **how)
        with jax.named_scope(DeviceScope.ATTN_SPARSE):
            out, lse = sparse_attention.selected_attention_latent(
                q_nope, q_rope, k_nope, k_rope, v, selection,
                c.softmax_scale, block_q=c.sparse_block_q, **how)
        with jax.named_scope(DeviceScope.DSA_INDEX):
            kl = sparse_attention.index_kl_latent(
                qi, ki, w, q_nope, q_rope, k_nope, k_rope, lse, selection,
                c.softmax_scale, block_q=c.index_block_q, **how)
    elif c.use_kernels:
        out = flash_attention.flash_attention_mla_auto(
            q_nope, q_rope, k_nope, k_rope, v, c.softmax_scale,
            c.flash_block_q, c.flash_block_k, c.kernel_interpret,
            **(dict(window=window, window_block=c.window_block,
                    windowed=windowed) if window else {}))
    elif window or c.kv_heads != h:
        out = _dense_latent_attention(q_nope, q_rope, k_nope, k_rope, v,
                                      c.softmax_scale, window, windowed)
    else:
        out = mha_reference(
            jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope, (b, h, s, dr))], axis=-1),
            v, causal=True, scale=c.softmax_scale)
    if c.num_noise_heads:
        out, lam = _differential(out, x, p, c)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, c.out_heads * dv)
    if c.attn_output_gate:
        with jax.named_scope(DeviceScope.ATTN_GATE):
            out = out * jax.nn.sigmoid(jnp.einsum(
                "bsd,dg->bsg", x, p["g_proj"]["kernel"],
                preferred_element_type=jnp.float32)).astype(out.dtype)
    out = out @ p["o_proj"]["kernel"]
    if c.num_noise_heads:
        return out, lam
    return (out, kl, selection) if c.index_n_heads else out


@functools.lru_cache(maxsize=None)
def poly_norm(scale: float, clamp: float, eps: float):
    """PolyNorm (PolyCom, arXiv:2411.03884) as ``f(z [..., F], {"weight"
    [3], "bias" [1]})``: ``scale * (a_1 n(z^3) + a_2 n(z^2) + a_3 n(z) +
    clip(b, -clamp, clamp))`` with ``n(t) = t / sqrt(mean(t^2) + eps)``
    over the last axis, in float32, the result in ``z``'s dtype. One
    function a triple of constants, so that it names one program where
    it is a kernel wrapper's static argument (``ops.moe.
    held_expert_ffn``)."""

    def norm(t):
        return t * lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)

    @jax.named_scope(DeviceScope.POLYNORM)
    def activation(z, w):
        zf = z.astype(jnp.float32)
        a = w["weight"].astype(jnp.float32)
        bias = jnp.clip(w["bias"].astype(jnp.float32), -clamp, clamp)
        return (scale * (a[0] * norm(zf * zf * zf) + a[1] * norm(zf * zf)
                         + a[2] * norm(zf) + bias[0])).astype(z.dtype)

    return activation


def _activation(c: MlaMoeConfig):
    return poly_norm(c.polynorm_scale, c.polynorm_bias_clamp,
                     c.polynorm_eps)


def _swiglu(x, p, c=None):
    """``W_down(act(W_gate x) * W_up x)``: SiLU, or PolyNorm where the
    FFN holds its leaves (``p["act"]``; ``c`` has its constants)."""
    if "act" not in p:
        return (jax.nn.silu(x @ p["gate_proj"]["kernel"])
                * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]
    return (_activation(c)(x @ p["gate_proj"]["kernel"], p["act"])
            * (x @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def _moe(x, p, c: MlaMoeConfig, tell=None, bias=None):
    """The expert layer of the normed ``x``: (output, balance loss
    before its weight, the held experts' counters). ``tell``, a
    dictionary, receives what the router chose: ``experts`` [B S, k]
    and, under a group limit, ``groups`` [B S, n_group]. ``bias``: the
    selection bias of a model that keeps it as a buffer
    (``router_bias_rate``; None there is the zeros it starts at), whose
    counters then hold ``load``, the tokens that selected each of ALL
    the router's experts."""
    b, s, d = x.shape
    if bias is None:
        bias = p["router"].get("bias")
    xt = x.reshape(b * s, d)
    with jax.named_scope(DeviceScope.MOE_ROUTER):
        logits = jnp.einsum("td,de->te", xt, p["router"]["kernel"],
                            preferred_element_type=jnp.float32)
        if c.n_group > 1:
            with jax.named_scope(DeviceScope.MOE_GROUPS):
                top_i, top_w, scores, groups = moe.group_limited_routing(
                    logits, c.num_experts_per_tok, c.n_group, c.topk_group,
                    c.norm_topk_prob, c.routed_scaling_factor, bias)
        else:
            top_i, top_w, scores = moe.sigmoid_topk_routing(
                logits, c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor, bias)
        # before its weight; a model without the loss does not count
        balance = (moe.sequence_balance_loss(scores, top_i, b)
                   if c.balance_loss_weight else jnp.float32(0.0))
    with jax.named_scope(DeviceScope.MOE_SHARED):
        shared = _swiglu(xt, p["shared"], c)
    # a parameterised activation reads the experts' ``act`` leaves
    act = ({"activation": _activation(c)} if "act" in p["experts"] else {})
    with jax.named_scope(DeviceScope.MOE_EXPERTS):
        if c.use_kernels:
            routed, stats = moe.held_expert_ffn(
                p["experts"], xt, top_i, top_w, c.held,
                moe.held_row_ladder(b * s, c.num_experts_per_tok,
                                    c.n_routed_experts, len(c.held),
                                    c.expert_row_factor, c.expert_block_t),
                c.expert_block_t, c.kernel_interpret, **act)
        else:
            routed = moe.held_expert_ffn_reference(
                p["experts"], xt, top_i, top_w, c.held, **act)
            per_expert = jnp.sum(
                top_i[:, :, None] == jnp.asarray(c.held, jnp.int32),
                axis=(0, 1)).astype(jnp.float32)
            stats = {"rows_held": per_expert.sum(),
                     "rows_max": per_expert.max(),
                     "rows_dropped": jnp.float32(0.0),
                     "rows_buffered": jnp.float32(0.0)}  # no buffer
    if tell is not None:
        tell["experts"] = top_i
    if c.router_bias_rate:
        with jax.named_scope(DeviceScope.ROUTER_BIAS):
            stats = dict(stats, load=moe.expert_load(
                top_i, c.n_routed_experts))
    if c.n_group > 1:
        # the groups of the experts held here: a token sends this chip
        # a row only if it keeps one of them
        mine = sorted({e * c.n_group // c.n_routed_experts for e in c.held})
        stats = dict(stats, group_tokens=jnp.float32(b * s),
                     group_reach=jnp.sum(jnp.any(
                         groups[:, jnp.asarray(mine)], axis=1),
                         dtype=jnp.float32))
        if tell is not None:
            tell["groups"] = groups
    return (shared + routed).reshape(b, s, d), balance, stats


def _layer(c: MlaMoeConfig, kind: str, rotary, tell: bool = False,
           attention_kind: str = "full"):
    """``layer(x, p) -> (x, per-layer outputs)`` of one kind, for the
    scan: ``None`` from a dense layer, ``(balance, stats)`` from an
    expert layer; with streams ``x`` is [B, S, n * D] and ``[the mean
    defect of the layer's two H_res, those the kernels ran]`` go first;
    with an indexer the selection's counters and the indexer's loss, a
    dictionary, go last (a dense layer's ``(counters,)``). ``tell`` (an
    indexer, no streams) adds to that dictionary what the layer chose:
    ``selected`` [B, S, S] int8 and, of an expert layer, ``experts``
    and ``groups``. With noise heads the outputs are a tuple that ends
    with ``{"diff_lambda": lambda's mean}`` (``_without_counters``
    undoes it). ``attention_kind`` as ``_mla`` takes it; the layer
    takes ``windowed`` (a ``"mixed"`` stack's flag) and ``bias`` (the
    router's selection bias as a buffer) by keyword."""
    diff = bool(c.num_noise_heads)

    def attention(u, p, windowed=None):
        return _mla(_rms(u, p["input_norm"], c), p["attn"], c, rotary,
                    attention_kind, windowed)

    def ffn(u, p, chose=None, bias=None):
        normed = _rms(u, p["post_norm"], c)
        if kind == "dense":
            with jax.named_scope(DeviceScope.FFN):
                return _swiglu(normed, p["mlp"], c), None
        y, balance, stats = _moe(normed, p["moe"], c, chose, bias)
        return y, (balance, stats)

    def with_counters(out, lam):
        """The layer's outputs as a tuple that ends with its counters."""
        if not diff:
            return out
        out = () if out is None else out if isinstance(out, tuple) else (
            out,)
        return out + ({"diff_lambda": lam},)

    def layer(x, p, windowed=None, bias=None):
        p = cast_floats(p, c.compute_dtype)
        a = attention(x, p, windowed)
        a, lam = a if diff else (a, None)
        x = x + a
        y, out = ffn(x, p, bias=bias)
        return x + y, with_counters(out, lam)

    def sparse_layer(x, p):
        p = cast_floats(p, c.compute_dtype)
        a, kl, chosen = attention(x, p)
        counters = sparse_attention.selection_counters(
            chosen, c.num_heads, c.sparse_block_q, c.use_kernels)
        counters[StepCounter.DSA_INDEX_KL] = kl
        if tell:
            counters["selected"] = sparse_attention.dense_mask(chosen.mask)
        x = x + a
        y, out = ffn(x, p, counters if tell else None)
        return x + y, (out or ()) + (counters,)

    if c.index_n_heads:
        return sparse_layer

    def connected(x, p, f):
        return hc.connect(x, p, f, c.hc_mult, c.hc_sinkhorn_iters,
                          c.hc_clamp, c.hc_eps, c.use_kernels,
                          c.kernel_interpret)

    def streams_layer(x, p, windowed=None, bias=None):
        p = cast_floats(p, c.compute_dtype)

        def attend(u):
            a = attention(u, p, windowed)
            return a if diff else (a, None)

        x, lam, d_attn, k_attn = connected(x, p["hc_attn"], attend)
        x, out, d_ffn, k_ffn = connected(
            x, p["hc_ffn"], lambda u: ffn(u, p, bias=bias))
        # the two sublayers' mean defect, and those the kernels ran
        stats = jnp.stack([0.5 * (d_attn + d_ffn), 1.0 * (k_attn + k_ffn)])
        return x, with_counters(
            stats if out is None else (stats,) + out, lam)

    return layer if c.hc_mult == 1 else streams_layer


def _without_counters(out, kind: str):
    """A scan's outputs of ``_layer`` with noise heads, ``(in the form a
    model without has them, the counters stacked)``."""
    *out, counters = out
    if kind == "dense":  # None, or the streams' defects alone
        return (out[0] if out else None), counters
    return tuple(out), counters


def _kind_of(attention: List[str], extras: Dict):
    """``(what _layer takes as attention_kind, extras)`` for a stack
    whose layers attend as ``attention`` lists: one kind, or
    ``"mixed"`` with a ``windowed`` flag a layer among the extras."""
    kinds = set(attention) or {"full"}
    if len(kinds) == 1:
        return kinds.pop(), dict(extras)
    return "mixed", dict(extras, windowed=jnp.asarray(
        [a == "window" for a in attention], jnp.int32))


def _stack_of(c: MlaMoeConfig, kind: str, rotary, attention: List[str],
              remat, extras: Dict):
    """``(the function a scan over a stack of layers of one ``kind``
    runs, what it scans beside the parameters)``: the layers' attention
    kinds decide whether the scan carries a flag a layer, ``extras``
    (the router's bias buffer a layer, or nothing) rides with it."""
    attention_kind, extras = _kind_of(attention, extras)
    layer = remat(_layer(c, kind, rotary, attention_kind=attention_kind))
    if not extras:
        return layer, lambda p: p
    return (lambda x, xs: layer(x, xs[0], **xs[1])), lambda p: (p, extras)


def _bias_of(buffers, *path):
    """A stack's selection bias in the buffers (``init_buffers``), or
    nothing where the model keeps none or is run without them."""
    if buffers is None:
        return {}
    for key in path:
        buffers = buffers[key]
    return {"bias": buffers["moe"]["router"]["bias"]}


def _kept_names(c: MlaMoeConfig):
    """What a layer's checkpoint keeps beside what the policy saves: its
    attention kernel's output and logsumexp, so that its replay leaves
    the forward kernel out (``flash_mla_fwd``, ``flash_mla_win_fwd``,
    ``dsa_attn_fwd``); a sparse layer's also the three gradients of its
    indexer's loss, and ``dsa_index_kl`` is out too. By the op the layer
    calls (``_mla``); XLA's dense forms name nothing."""
    if c.index_n_heads:
        return sparse_attention.KEPT_NAMES + sparse_attention.INDEX_KEPT_NAMES
    return flash_attention.KEPT_NAMES


def _trunk(params: Dict, input_ids: jax.Array, c: MlaMoeConfig,
           buffers=None):
    """The layers: (the residual before the final norm [B, S, D], the
    streams summed; what the expert layers returned, stacked; the
    layers' mean ``H_res`` defects, stacked, or None; the rotary
    tables). With an indexer what the expert layers returned ends with
    the selection's counters summed over ALL the layers, with noise
    heads with ``{"diff_lambda": lambda's means summed over them}``."""
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    rotary = _rotary_tables(input_ids.shape[1], c)
    if c.hc_mult > 1:  # every stream enters as the token's embedding
        x = hc.enter(x, c.hc_mult)
    defects = []
    remat = partial(apply_remat, policy=c.remat_policy, keep=_kept_names(c))
    attention = attention_plan(c)
    lambdas = []
    if c.first_k_dense:
        layer, xs = _stack_of(c, "dense", rotary,
                              attention[:c.first_k_dense], remat, {})
        x, out = lax.scan(layer, x, xs(params["dense_layers"]))
        if c.num_noise_heads:
            out, more = _without_counters(out, "dense")
            lambdas.append(more)
        defects.append(out)
    layer, xs = _stack_of(
        c, "moe", rotary, attention[c.first_k_dense:c.num_layers], remat,
        _bias_of(buffers, "moe_layers"))
    x, out = lax.scan(layer, x, xs(params["moe_layers"]))
    if c.num_noise_heads:
        out, more = _without_counters(out, "moe")
        lambdas.append(more)
        out = out + (jax.tree.map(
            lambda *a: sum(t.sum(axis=0) for t in a), *lambdas),)
    if c.index_n_heads:
        # the expert layers' and (in ``defects``: no streams here) the
        # dense layers' counters, each stacked over its scan
        counters = jax.tree.map(
            lambda *a: sum(t.sum(axis=0) for t in a), out[-1],
            *(d[-1] for d in defects))
        return x, out[:-1] + (counters,), None, rotary
    if c.hc_mult == 1:
        return x, out, None, rotary
    defects.append(out[0])
    return (hc.leave(x, c.hc_mult), out[1:], jnp.concatenate(defects),
            rotary)


def _mtp(params: Dict, h: jax.Array, next_ids: jax.Array, c: MlaMoeConfig,
         rotary, buffers=None):
    """The prediction modules on the trunk's ``h`` [B, S, D]:
    ``next_ids[k]`` [B, S] are module k's input tokens ``t_{i+k+1}``.
    (Each module's final normed hidden states, stacked [K, B, S, D];
    what its expert layer returned, stacked, with noise heads ending
    with its counters stacked; its defects or None)."""
    table = params["embed_tokens"]["embedding"]
    # a module's layer is layer ``num_layers + k`` of the attention
    # plan; the modules scan as one stack
    attention_kind, extras = _kind_of(
        attention_plan(c)[c.num_layers:], _bias_of(buffers, "mtp", "layer"))
    run_layer = _layer(c, "moe", rotary, attention_kind=attention_kind)

    def module(h, p, ids, **extras):
        p = cast_floats(p, c.compute_dtype)
        x = jnp.concatenate(
            [_rms(h, p["h_norm"], c),
             _rms(table[ids].astype(c.compute_dtype), p["e_norm"], c)],
            axis=-1) @ p["eh_proj"]["kernel"]
        if c.hc_mult > 1:
            x = hc.enter(x, c.hc_mult)
        x, out = run_layer(x, p["layer"], **extras)
        if c.hc_mult > 1:
            x = hc.leave(x, c.hc_mult)
        return x, (_rms(x, p["norm"], c), out)

    module = apply_remat(module, c.remat_policy, keep=_kept_names(c))
    with jax.named_scope(DeviceScope.MTP):
        if extras:
            _, (hidden, out) = lax.scan(
                lambda h, xs: module(h, *xs[0], **xs[1]),
                h, ((params["mtp"], next_ids), extras))
        else:
            _, (hidden, out) = lax.scan(
                lambda h, p_ids: module(h, *p_ids),
                h, (params["mtp"], next_ids))
    if c.hc_mult == 1:
        return hidden, out, None
    return hidden, out[1:], out[0]


def _summed(out):
    """(The balance losses summed; the counters summed over a stack's
    expert layers, but ``load``, which stays a row a layer)."""
    balance, stats = out[:2]
    load = {k: v for k, v in stats.items() if k == "load"}
    return balance.sum(), {**jax.tree.map(
        lambda a: a.sum(axis=0),
        {k: v for k, v in stats.items() if k != "load"}), **load}


def apply_hidden(params: Dict, input_ids: jax.Array, config: MlaMoeConfig):
    """(final hidden states [B, S, D] in the compute dtype, the balance
    loss summed over the expert layers before its weight, the held
    experts' counters summed over the expert layers). The main model
    alone: no prediction module."""
    c = config
    x, out, _, _ = _trunk(params, input_ids, c)
    x = _rms(x, cast_floats(params["norm"], c.compute_dtype), c)
    return (x,) + _summed(out)


def apply_layers(params: Dict, input_ids: jax.Array, config: MlaMoeConfig):
    """``apply_hidden`` of a model with an indexer a layer at a time,
    outside any scan and with no remat, for a comparison that wants
    what each layer chose: yields every layer's counters with
    ``selected`` and, of an expert layer, ``experts`` and ``groups``
    (``_layer``'s ``tell``), in order, and last the final normed hidden
    states [B, S, D]."""
    c = config
    rotary = _rotary_tables(input_ids.shape[1], c)
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    for kind, name in zip(KINDS, ("dense_layers", "moe_layers")):
        layer = _layer(c, kind, rotary, tell=True)
        # the layer's index is an argument: one compile serves a kind
        run = jax.jit(lambda x, stack, i, layer=layer: layer(
            x, jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, i, keepdims=False), stack)))
        for i in range(layer_kinds(c)[kind]):
            x, out = run(x, params[name], i)
            yield out[-1]
    yield _rms(x, cast_floats(params["norm"], c.compute_dtype), c)


def apply(params: Dict, input_ids: jax.Array,
          config: MlaMoeConfig) -> jax.Array:
    """Logits [B, S, V] in float32."""
    x, _, _ = apply_hidden(params, input_ids, config)
    return (x @ params["lm_head"]["kernel"].astype(
        config.compute_dtype)).astype(jnp.float32)


def mtp_targets(labels: jax.Array, depth: int):
    """For a batch whose ``labels[i]`` is ``t_{i+1}``: module k's input
    tokens ``t_{i+k+1}`` and targets ``t_{i+k+2}``, both [depth, B, S].
    The last k + 1 positions have no such target and are masked (of the
    row's S + 1 tokens, the last k + 2 have no token k + 2 ahead)."""
    seq = labels.shape[1]
    padded = jnp.pad(labels, ((0, 0), (0, depth)),
                     constant_values=IGNORE_INDEX)
    shifted = jnp.stack([padded[:, k:k + seq] for k in range(depth + 1)])
    # an input token past the row is masked as a target: any id serves
    return jnp.maximum(shifted[:-1], 0), shifted[1:]


def apply_all_hidden(params: Dict, input_ids: jax.Array, labels: jax.Array,
                     config: MlaMoeConfig) -> jax.Array:
    """The final normed hidden states of the main model and of every
    prediction module, [1 + mtp_layers, B, S, D]: what a comparison
    with a reference reads."""
    c = config
    h, _, _, rotary = _trunk(params, input_ids, c)
    hidden = _rms(h, cast_floats(params["norm"], c.compute_dtype), c)[None]
    if c.mtp_layers:
        more, _, _ = _mtp(params, h, mtp_targets(labels, c.mtp_layers)[0],
                          c, rotary)
        hidden = jnp.concatenate([hidden, more])
    return hidden


# -- training glue ----------------------------------------------------------


def make_init_fn(config: MlaMoeConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


# the aux entry that carries every expert layer's load to the update
ROUTER_LOAD = "router_load"


def _bias_buffer(bias):
    return {"moe": {"router": {"bias": bias}}}


def init_buffers(config: MlaMoeConfig) -> Dict:
    """The router's selection bias of every expert layer, float32 zeros
    as torchtitan starts it, under the paths its parameter would have
    (``moe_layers/moe/router/bias``, ``mtp/layer/moe/router/bias``: the
    sharding rule for ``router/bias`` matches them under ``buffers/``)."""
    c = config

    def zeros(layers):
        return _bias_buffer(jnp.zeros((layers, c.n_routed_experts),
                                      jnp.float32))

    out = {"moe_layers": zeros(c.moe_layers)}
    if c.mtp_layers:
        out["mtp"] = {"layer": zeros(c.mtp_layers)}
    return out


def update_buffers(buffers: Dict, aux: Dict, config: MlaMoeConfig):
    """``StepBuffers.update``: every expert layer's bias moved by its
    own load of this step (``ops.moe.selection_bias_update``), and the
    aux with the loads taken out and ``router_bias_abs`` put in."""
    aux = dict(aux)
    load = aux.pop(ROUTER_LOAD)

    def moved(stack, n):
        return _bias_buffer(moe.selection_bias_update(
            stack["moe"]["router"]["bias"], n, config.router_bias_rate))

    with jax.named_scope(DeviceScope.ROUTER_BIAS):
        new = {"moe_layers": moved(buffers["moe_layers"],
                                   load["moe_layers"])}
        if "mtp" in buffers:
            new["mtp"] = {"layer": moved(buffers["mtp"]["layer"],
                                         load["mtp"])}
        leaves = jax.tree.leaves(new)
        aux[StepCounter.ROUTER_BIAS_ABS] = (
            sum(jnp.sum(jnp.abs(b)) for b in leaves)
            / sum(b.size for b in leaves))
    return new, aux


def make_loss_fn(config: MlaMoeConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"} plus the
    balance loss; the aux counts the held experts' rows, those past
    the row buffer among them. With ``head_chunk`` the head is fused
    with the cross entropy over sequence chunks
    (``losses.lm_head_loss``)."""

    def loss_fn(params, batch, rng, buffers=None):
        del rng  # no dropout, no router noise
        c = config
        h, out, defects, rotary = _trunk(params, batch["input_ids"], c,
                                         buffers)
        hidden = _rms(h, cast_floats(params["norm"], c.compute_dtype), c)
        balance, stats = _summed(out)
        head = params["lm_head"]["kernel"]
        loss = lm_head_loss(hidden, head, batch["labels"], head_chunk)
        extra = {}
        # the selections a layer of every expert of the router, by
        # stack: what moves the bias (``step_buffers``), no metric
        load = ({"moe_layers": stats.pop("load")} if c.router_bias_rate
                else {})
        lam = out[-1]["diff_lambda"] if c.num_noise_heads else 0.0
        if c.mtp_layers:
            next_ids, targets = mtp_targets(batch["labels"], c.mtp_layers)
            more, out, more_defects = _mtp(params, h, next_ids, c, rotary,
                                           buffers)
            with jax.named_scope(DeviceScope.MTP):
                mtp_loss = sum(
                    lm_head_loss(more[k], head, targets[k], head_chunk)
                    for k in range(c.mtp_layers)) / c.mtp_layers
            loss = loss + c.mtp_loss_weight * mtp_loss
            balance_m, stats_m = _summed(out)
            if c.router_bias_rate:
                load["mtp"] = stats_m.pop("load")
            if c.num_noise_heads:
                lam = lam + out[-1]["diff_lambda"].sum()
            balance = balance + balance_m
            stats = jax.tree.map(jnp.add, stats, stats_m)
            extra[StepCounter.MTP_LOSS] = mtp_loss
            if defects is not None:
                defects = jnp.concatenate([defects, more_defects])
        if c.router_bias_rate:
            extra[ROUTER_LOAD] = load
        if c.num_noise_heads:
            extra[StepCounter.DIFF_LAMBDA_MEAN] = lam / (
                c.num_layers + c.mtp_layers)
        if c.sliding_window and c.use_kernels:
            rows, seq = batch["input_ids"].shape
            extra.update(jax.tree.map(
                jnp.float32, flash_attention.mla_band_tile_counters(
                    rows * c.num_heads * attention_plan(c).count("window"),
                    seq, c.sliding_window, c.window_block)))
        if defects is not None:
            extra[StepCounter.HC_RES_DEFECT] = defects[:, 0].mean()
            extra[StepCounter.HC_KERNEL_PASSES] = defects[:, 1].sum()
        loss = loss + c.balance_loss_weight * balance
        if c.n_group > 1:
            extra[StepCounter.MOE_GROUP_REACH] = stats["group_reach"]
            extra[StepCounter.MOE_GROUP_TOKENS] = stats["group_tokens"]
        # the kernels' forward rules alone name what is kept, and with no
        # remat there is no checkpoint to keep it; either kind of
        # kernel's ``out`` and ``lse`` are the same bytes a layer
        kept = (c.use_kernels and remat_enabled(c.remat_policy)) * (
            c.num_layers + c.mtp_layers)
        rows, seq = batch["input_ids"].shape
        out_and_lse = jnp.float32(kept * sparse_attention.kept_bytes(
            rows, c.num_heads, seq, c.v_head_dim, c.compute_dtype))
        if c.index_n_heads:
            extra.update(out[-1])
            loss = loss + c.index_loss_weight * extra[
                StepCounter.DSA_INDEX_KL]
            extra[StepCounter.DSA_ATTN_KEPT_BYTES] = out_and_lse
            extra[StepCounter.DSA_INDEX_KEPT_BYTES] = jnp.float32(
                kept * sparse_attention.index_kept_bytes(
                    rows, c.index_n_heads, seq, c.index_head_dim,
                    c.compute_dtype))
        else:
            extra[StepCounter.ATTN_KEPT_BYTES] = out_and_lse
        return loss, {
            StepCounter.MOE_ROWS_HELD: stats["rows_held"],
            StepCounter.MOE_ROWS_MAX: stats["rows_max"],
            StepCounter.MOE_ROWS_DROPPED: stats["rows_dropped"],
            StepCounter.MOE_ROWS_BUFFERED: stats["rows_buffered"],
            **extra,
        }

    if config.router_bias_rate:
        # ``accelerate`` keeps the bias in ``TrainState.buffers``, hands
        # it to ``loss_fn`` and moves it after the optimizer
        loss_fn.step_buffers = StepBuffers(
            init=lambda params: init_buffers(config),
            update=partial(update_buffers, config=config))
    return loss_fn


def param_count(config: MlaMoeConfig) -> int:
    return common.param_count(make_init_fn(config))
