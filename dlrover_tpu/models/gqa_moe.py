"""Grouped-query decoder whose layers are of several kinds, chosen by
per-layer lists, and whose every layer is an expert layer
(SmallThinker-21BA3B publishes it at 52 layers of 2560: one full,
position-free layer in four, three windowed rotary ones, routed from
the attention's input; Keye-VL-2.0-30B-A3B's language model at 48
layers of 2048, every one attending to the keys a learned indexer
selects, see "The switches" below). Training only.

Layer ``l``, with ``w_l = window_layout[l]`` and ``r_l =
rope_layout[l]``, pre-norm residual, RMSNorm, no bias, an untied head::

    u = RMSNorm_in(x)
    q, k, v = u W_q, u W_k, u W_v         num_heads, num_kv_heads heads
    if r_l: q, k = rot(q), rot(k)         rotate-half; else NO position
    a = softmax(q k^T / sqrt(head_dim) + mask_l) v
        mask_l: key j visible to query t where j <= t and, if w_l,
        t - sliding_window < j
    x' = x + a W_o
    g = u W_r                             float32, ALL n_routed_experts
    top = the num_experts_per_tok largest g;  p = softmax over those
    z = RMSNorm_post(x')
    y = sum_{e in top, e held here} p_e W_down_e (relu(W_gate_e z)
                                                  * (W_up_e z))
    x'' = x' + y

**The router reads ``u``, the attention's input, not ``z``**: a layer's
routing (scores, top-k, the sort of assignments into the held experts'
rows) depends on nothing its attention computes, and stands before it
in the program. The experts are ReGLU; there is no shared expert, no
dense layer, no balance loss and no router bias.

The two lists are the published ones, as long as the published depth;
the first ``num_layers`` entries are used. Their smallest common period
``p`` is found (SmallThinker: 4), ``num_layers`` is a whole number of
periods, and the layers are stacked and scanned by the period, each
under ``remat_policy`` on its own (``models/common.py``, "the
period-stacked decoder"); a layer's checkpoint keeps its
attention kernel's output and logsumexp beside what the policy saves
(``ops.flash_attention.KEPT_NAMES``), so the forward kernel runs once a
step and not again in the layer's replay. On a TPU a full layer's attention
is ``ops.flash_attention.flash_attention`` and a window layer's
``flash_attention_window`` (both through ``flash_attention_auto``:
under ``shard_map`` where a mesh is ambient); ``use_kernels=False``
takes XLA's dense attention and the einsum experts (a CPU rehearsal).

**The switches**, data of the config, whose defaults are the block
above. ``window_layout[l] = 2`` makes layer ``l``'s attention SPARSE
(``ops/sparse_attention.py``): an indexer beside the attention reads
``u' = stop_gradient(u)``, scores every causal key with
``index_heads`` small ReLU heads against ONE key head and a learned
per-head weight, and the query attends to the ``sparse_topk`` keys of
largest score (to every causal key where there are no more); the
indexer is trained by its own loss, the KL divergence from the
attention's head-mean probabilities to the softmax of its scores over
the selected keys, added to the model's loss by ``make_loss_fn``
(``index_loss_weight``; the indexer's three matrices get their
gradient from it alone, every other leaf from the language-model loss
alone; its value and those gradients come out of one kernel in the
forward pass, ``dsa_index_kl``, and a sparse layer's checkpoint keeps
the gradients beside the selected attention's output and logsumexp, so
the replay runs neither kernel again). ``router_input = "post_norm"``
routes from ``z``;
``expert_activation = "silu"`` makes the experts SwiGLU; ``qk_norm``
puts an RMSNorm with a learned scale on each query and key head before
the rotation; ``rope_sections`` (three numbers of rotary pairs) turns
pair ``i`` by the position row of its section, positions ``[3, S]`` or
``[B, 3, S]`` (temporal, height, width: on text the three rows are
equal and this is plain rotary).

``experts_held`` says which of the ``n_routed_experts`` this chip holds
(all of them when empty): the router is whole, the softmax is over all
the selected experts, and what the experts held elsewhere would add is
left out (``ops.moe.held_expert_ffn``; ``models/mla_moe.py`` has the
same contract). No capacity: an assignment to a held expert is computed
unless the static row buffer (``expert_row_factor`` times the uniform
expectation) is full; the loss function's aux counts the assignments
left out (``telemetry.names.StepCounter``), and a layer computes on the
smallest halving of the buffer that holds its rows (``ops.moe.
held_row_ladder``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models import common
from dlrover_tpu.models.common import cast_floats, dense_init, rms_norm
from dlrover_tpu.models.losses import lm_head_loss
from dlrover_tpu.ops import flash_attention, moe, sparse_attention
from dlrover_tpu.ops.attention_ref import mha_reference
from dlrover_tpu.ops.remat import apply_remat, remat_enabled
from dlrover_tpu.telemetry.names import DeviceScope, StepCounter

# SmallThinker's published lists: one period of four, thirteen times
_PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13
# a layer's attention, as ``window_layout`` gives it
FULL, WINDOW, SPARSE = 0, 1, 2
_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


@dataclass(frozen=True)
class GqaMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    moe_intermediate_size: int = 768  # of one expert
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 4096
    # by layer, as long as the published depth: 1 = windowed / rotary;
    # in ``window_layout`` 2 = sparse (the keys a learned indexer selects)
    window_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    rope_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    rope_theta: float = 1.5e6
    n_routed_experts: int = 64  # the router's width
    # the routed experts this chip holds, by index; () = all of them
    experts_held: Tuple[int, ...] = ()
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 16384
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
    # the most a side of a window layer's tiles may be; the kernels pick
    # the tiles from the row and the window (``flash_attention.
    # window_tiles``)
    window_block: int = 1024
    # the row buffer of the held experts, as a multiple of what uniform
    # routing sends them (``ops.moe.held_row_bound``), and its row tile
    expert_row_factor: float = 4.0
    expert_block_t: int = 128
    # what the router reads: "attn_input" (u) or "post_norm" (z)
    router_input: str = "attn_input"
    expert_activation: str = "relu"  # or "silu"
    qk_norm: bool = False  # RMSNorm with a learned scale a q and k head
    # three-axis rotary: rotary pairs by position row; () = one row
    rope_sections: Tuple[int, ...] = ()
    # the sparse layers' indexer and selection
    index_heads: int = 16
    index_head_dim: int = 64
    sparse_topk: int = 2048
    index_loss_weight: float = 1.0
    # tiles: the selection's and the indexer loss's query block, the key
    # tile of all the sparse kernels, the attention's query block
    index_block_q: int = 128
    index_block_k: int = 512
    sparse_block_q: int = 512

    @property
    def has_sparse(self) -> bool:
        return SPARSE in self.window_layout[:self.num_layers]

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(self.experts_held) or tuple(
            range(self.n_routed_experts))


def gqa_moe_tiny(**overrides) -> GqaMoeConfig:
    base = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
                sliding_window=16, window_layout=(0, 1) * 4,
                rope_layout=(0, 1) * 4, rope_theta=1e4,
                n_routed_experts=16, num_experts_per_tok=3, max_seq_len=64,
                expert_block_t=8, expert_row_factor=4.0, window_block=16,
                use_kernels=False)
    base.update(overrides)
    return GqaMoeConfig(**base)


def layer_plan(config: GqaMoeConfig) -> List[Tuple[int, int]]:
    """One period of the model's layers, each ``(attention kind,
    rotary)``, the kind 0 full, 1 windowed, 2 sparse:
    ``common.period_of`` both published lists, zipped. Refuses lists of
    unequal length and an attention kind it does not know."""
    c = config
    if len(c.window_layout) != len(c.rope_layout):
        raise ValueError(
            f"window_layout ({len(c.window_layout)} entries) and "
            f"rope_layout ({len(c.rope_layout)}) give each layer its kind: "
            "equally long")
    kinds = list(zip(c.window_layout, c.rope_layout))
    period = common.period_of(kinds, c.num_layers,
                              "window_layout with rope_layout")
    if not set(c.window_layout) <= {FULL, WINDOW, SPARSE}:
        raise ValueError(f"window_layout {set(c.window_layout)}: 0 full, "
                         "1 windowed, 2 sparse")
    return [(int(w), int(bool(r))) for w, r in kinds[:period]]


def layer_kinds(config: GqaMoeConfig) -> Dict[str, int]:
    """Layers by attention kind, for whoever reads a trace without the
    config."""
    plan = layer_plan(config)
    periods = config.num_layers // len(plan)
    count = {kind: periods * sum(w == kind for w, _ in plan)
             for kind in (FULL, WINDOW, SPARSE)}
    kinds = {DeviceScope.ATTN_FULL: count[FULL],
             DeviceScope.ATTN_WINDOW: count[WINDOW]}
    if count[SPARSE]:
        kinds[DeviceScope.ATTN_SPARSE] = count[SPARSE]
    return kinds


# -- rotary -----------------------------------------------------------------


def _rotary_tables(seq: int, c: GqaMoeConfig):
    half = c.head_dim // 2
    inv_freq = c.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)  # [S, d/2]


def _position_rows(positions, rows: int, seq: int):
    """``positions`` [3, S] or [B, 3, S] (None: 0..S-1 on every row) as
    float32 [B or 1, 3, S]."""
    if positions is None:
        return jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.float32), (1, 3, seq))
    pos = jnp.asarray(positions, jnp.float32)
    pos = pos[None] if pos.ndim == 2 else pos
    if pos.shape[1:] != (3, seq) or pos.shape[0] not in (1, rows):
        raise ValueError(f"positions {pos.shape}: [3, {seq}] or "
                         f"[{rows}, 3, {seq}]")
    return pos


def _section_tables(pos, c: GqaMoeConfig):
    """Three-axis rotary tables [B or 1, 1, S, d/2]: pair ``i`` turns by
    the position row of its section (``rope_sections`` pairs each), at
    the same ``theta^(-i / (d/2))`` as plain rotary."""
    half = c.head_dim // 2
    if sum(c.rope_sections) != half or len(c.rope_sections) != 3:
        raise ValueError(f"rope_sections {c.rope_sections}: three counts "
                         f"of rotary pairs that add up to {half}")
    inv_freq = c.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    row = jnp.repeat(jnp.arange(3), jnp.asarray(c.rope_sections),
                     total_repeat_length=half)
    # [B, S, half]: the row of each pair's section
    angles = jnp.take(pos, row, axis=1).transpose(0, 2, 1) * inv_freq
    return jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]


def _index_tables(pos, c: GqaMoeConfig):
    """The indexer's plain rotary over the temporal row, all
    ``index_head_dim / 2`` pairs: [B or 1, 1, S, e/2]."""
    half = c.index_head_dim // 2
    inv_freq = c.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    angles = pos[:, 0, :, None] * inv_freq
    return jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]


def _rotate(x, cos, sin):
    """Pair ``i`` is (x[i], x[i + d/2]); ``x`` is [..., S, d] and the
    tables [S, d/2]. float32 inside, x's dtype out."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# -- init -------------------------------------------------------------------


def _layers_init(key, lead, c: GqaMoeConfig, sparse: bool = False):
    """The layers at one position of the period, stacked over the
    periods (``lead``); ``sparse`` ones hold an indexer too."""
    d, hd, f, dt = (c.hidden_size, c.head_dim, c.moe_intermediate_size,
                    c.param_dtype)
    held = len(c.held)
    k = jax.random.split(key, 8)

    def proj(key, *shape):
        return {"kernel": dense_init(key, lead + shape, dt)}

    attn = {"q_proj": proj(k[0], d, c.num_heads * hd),
            "k_proj": proj(k[1], d, c.num_kv_heads * hd),
            "v_proj": proj(k[2], d, c.num_kv_heads * hd),
            "o_proj": proj(k[3], c.num_heads * hd, d)}
    if c.qk_norm:
        attn["q_norm"] = common.norm_init(lead, hd, dt)
        attn["k_norm"] = common.norm_init(lead, hd, dt)
    if sparse:
        # keys of their own, folded in: the leaves above are the ones a
        # model without an indexer draws
        ki = jax.random.split(jax.random.fold_in(key, 8), 3)
        attn["index"] = {
            "q_proj": proj(ki[0], d, c.index_heads * c.index_head_dim),
            "k_proj": proj(ki[1], d, c.index_head_dim),
            "w_proj": proj(ki[2], d, c.index_heads)}
    return {
        "input_norm": common.norm_init(lead, d, dt),
        "attn": attn,
        "post_norm": common.norm_init(lead, d, dt),
        "moe": {"router": proj(k[4], d, c.n_routed_experts),
                "experts": {"gate": proj(k[5], held, d, f),
                            "up": proj(k[6], held, d, f),
                            "down": proj(k[7], held, f, d)}},
    }


def init(rng: jax.Array, config: GqaMoeConfig) -> Dict:
    c = config
    plan = layer_plan(c)  # refuses a depth the plan cannot have
    if (c.router_input not in ("attn_input", "post_norm")
            or c.expert_activation not in _ACTIVATIONS):
        raise ValueError(
            f"router_input {c.router_input!r} is attn_input or post_norm, "
            f"expert_activation {c.expert_activation!r} one of "
            f"{sorted(_ACTIVATIONS)}")
    if sorted(set(c.held)) != list(c.held) or not (
            0 <= c.held[0] and c.held[-1] < c.n_routed_experts):
        raise ValueError(f"experts_held {c.held}: distinct indices in "
                         f"order, below {c.n_routed_experts}")
    if c.num_heads % c.num_kv_heads:
        raise ValueError(f"{c.num_kv_heads} KV heads do not divide "
                         f"{c.num_heads} query heads")
    k = jax.random.split(rng, 3)
    lead = (c.num_layers // len(plan),)
    return {
        # a table of std 1 beside kernels of std 1/sqrt(fan_in), as
        # ``models/mla_moe.py`` has it: a token's own vector is as large
        # as what a block adds to it
        "embed_tokens": {"embedding": jax.random.normal(
            k[0], (c.vocab_size, c.hidden_size), c.param_dtype)},
        "layers": common.stacked_init(
            k[1], plan, lambda key, kind: _layers_init(
                key, lead, c, kind[0] == SPARSE)),
        "norm": common.norm_init((), c.hidden_size, c.param_dtype),
        "lm_head": {"kernel": dense_init(
            k[2], (c.hidden_size, c.vocab_size), c.param_dtype)},
    }


# -- forward ----------------------------------------------------------------


def _heads(u, p, c: GqaMoeConfig, rotary):
    """The normed ``u`` [B, S, D] as query, key and value heads [B, h,
    S, head_dim]: projected, normed a head where the model has
    ``qk_norm``, turned where ``rotary`` is the tables."""
    d, hd = c.hidden_size, c.head_dim

    def heads(name, n):
        return jnp.einsum("bsd,dhk->bhsk", u,
                          p[name]["kernel"].reshape(d, n, hd))

    q, k, v = (heads("q_proj", c.num_heads), heads("k_proj", c.num_kv_heads),
               heads("v_proj", c.num_kv_heads))
    if c.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"], c.rms_norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], c.rms_norm_eps)
    if rotary is not None:
        q, k = _rotate(q, *rotary), _rotate(k, *rotary)
    return q, k, v


def _out_proj(out, p, c: GqaMoeConfig):
    return jnp.einsum("bhsk,hkd->bsd", out, p["o_proj"]["kernel"].reshape(
        c.num_heads, c.head_dim, c.hidden_size))


def _attention(u, p, c: GqaMoeConfig, window: bool, rotary):
    """Grouped-query attention of the normed ``u`` [B, S, D]: over the
    window where the layer is windowed, with rotary positions where
    ``rotary`` is the tables and with none where it is None."""
    q, k, v = _heads(u, p, c, rotary)
    if c.use_kernels:
        out = flash_attention.flash_attention_auto(
            q, k, v, causal=True,
            block_q=c.window_block if window else c.flash_block_q,
            block_k=c.flash_block_k, interpret=c.kernel_interpret,
            window=c.sliding_window if window else None)
    else:
        bias = None
        if window:
            t = jnp.arange(u.shape[1])
            bias = jnp.where(t[:, None] - t[None, :] < c.sliding_window,
                             0.0, jnp.finfo(jnp.float32).min)
        out = mha_reference(q, k, v, causal=True, bias=bias)
    return _out_proj(out, p, c)


def _sparse_attention(u, p, c: GqaMoeConfig, rotary, index_rotary):
    """Attention of the normed ``u`` [B, S, D] over the keys the
    layer's indexer selects: (output [B, S, D], the indexer's loss, a
    mean over the queries, and the ``Selection``). The indexer
    reads ``u`` detached, the selection has no gradient, and the
    indexer's loss reads the attention's probabilities as data."""
    d = c.hidden_size
    q, k, v = _heads(u, p, c, rotary)
    kernels = dict(use_kernels=c.use_kernels, interpret=c.kernel_interpret)
    with jax.named_scope(DeviceScope.DSA_INDEX):
        ui, pi = lax.stop_gradient(u), p["index"]
        qi = jnp.einsum("bsd,dje->bjse", ui, pi["q_proj"]["kernel"].reshape(
            d, c.index_heads, c.index_head_dim))
        ki = ui @ pi["k_proj"]["kernel"]
        w = ui @ pi["w_proj"]["kernel"]
        if index_rotary is not None:
            qi = _rotate(qi, *index_rotary)
            ki = _rotate(ki[:, None], *index_rotary)[:, 0]
        selection = sparse_attention.select_topk(
            qi, ki, w, c.sparse_topk, block_q=c.index_block_q,
            block_k=c.index_block_k, **kernels)
    out, lse = sparse_attention.selected_attention(
        q, k, v, selection, block_q=c.sparse_block_q, **kernels)
    with jax.named_scope(DeviceScope.DSA_INDEX):
        kl = sparse_attention.index_kl(
            qi, ki, w, q, k, lse, selection, block_q=c.index_block_q,
            **kernels)
    return _out_proj(out, p, c), kl, selection


def route(u, p, c: GqaMoeConfig):
    """The routing of a layer from ``u`` [B, S, D], the normed input of
    its ATTENTION (or of its experts, ``router_input``): ``(experts [B
    S, k], weights [B S, k])``."""
    with jax.named_scope(DeviceScope.MOE_ROUTER):
        logits = jnp.einsum(
            "td,de->te", u.reshape(-1, c.hidden_size), p["router"]["kernel"],
            preferred_element_type=jnp.float32)
        top_i, top_w, _ = moe.topk_softmax_routing(
            logits, c.num_experts_per_tok, c.norm_topk_prob)
    return top_i, top_w


def _experts(z, p, c: GqaMoeConfig, top_i, top_w):
    """What the held experts give the normed ``z`` [B, S, D] under a
    routing made elsewhere: (output, the held experts' counters)."""
    b, s, d = z.shape
    zt = z.reshape(b * s, d)
    activation = _ACTIVATIONS[c.expert_activation]
    with jax.named_scope(DeviceScope.MOE_EXPERTS):
        if c.use_kernels:
            out, stats = moe.held_expert_ffn(
                p["experts"], zt, top_i, top_w, c.held,
                moe.held_row_ladder(b * s, c.num_experts_per_tok,
                                    c.n_routed_experts, len(c.held),
                                    c.expert_row_factor, c.expert_block_t),
                c.expert_block_t, c.kernel_interpret, activation)
        else:
            out = moe.held_expert_ffn_reference(
                p["experts"], zt, top_i, top_w, c.held, activation)
            per_expert = jnp.sum(
                top_i[:, :, None] == jnp.asarray(c.held, jnp.int32),
                axis=(0, 1)).astype(jnp.float32)
            stats = {"rows_held": per_expert.sum(),
                     "rows_max": per_expert.max(),
                     "rows_dropped": jnp.float32(0.0),
                     "rows_buffered": jnp.float32(0.0)}  # no buffer
    return out.reshape(b, s, d), stats


# what a model with sparse layers counts a layer, beside the experts'
_SELECTION_COUNTERS = (
    StepCounter.DSA_PAIRS_SELECTED, StepCounter.DSA_PAIRS_CAUSAL,
    StepCounter.DSA_TILES_VISITED, StepCounter.DSA_TILES_SKIPPED,
    StepCounter.DSA_INDEX_KL)


def _layer(c: GqaMoeConfig, kind: Tuple[int, int], rotary,
           index_rotary=None, tell: bool = False):
    """``layer(x, p) -> (x, the layer's counters)`` of one kind
    ``(attention kind, rotary)``: the held experts' counters and, in a
    model with sparse layers, the selection's and the indexer's loss.
    ``tell`` adds what the layer chose: ``experts`` [B S, k] and, of a
    sparse layer, ``selected`` [B, S, S] int8."""
    attention, rope = kind
    scope = {FULL: DeviceScope.ATTN_FULL, WINDOW: DeviceScope.ATTN_WINDOW,
             SPARSE: DeviceScope.ATTN_SPARSE}[attention]
    rotary = rotary if rope else None

    def layer(x, p):
        p = cast_floats(p, c.compute_dtype)
        u = rms_norm(x, p["input_norm"]["scale"], c.rms_norm_eps)
        if c.router_input == "attn_input":
            # before the attention, on its input: nothing below feeds it
            top_i, top_w = route(u, p["moe"], c)
        selection, chose = {}, {}
        with jax.named_scope(scope):
            if attention == SPARSE:
                a, kl, chosen = _sparse_attention(
                    u, p["attn"], c, rotary, index_rotary if rope else None)
                selection = sparse_attention.selection_counters(
                    chosen, c.num_heads, c.sparse_block_q, c.use_kernels)
                selection[StepCounter.DSA_INDEX_KL] = kl
                if tell:
                    chose["selected"] = sparse_attention.dense_mask(
                        chosen.mask)
            else:
                a = _attention(u, p["attn"], c, attention == WINDOW, rotary)
                if c.has_sparse:  # one structure a period's layers
                    selection = {name: jnp.float32(0.0)
                                 for name in _SELECTION_COUNTERS}
            x = x + a
        z = rms_norm(x, p["post_norm"]["scale"], c.rms_norm_eps)
        if c.router_input == "post_norm":
            top_i, top_w = route(z, p["moe"], c)
        y, stats = _experts(z, p["moe"], c, top_i, top_w)
        if tell:
            chose["experts"] = top_i
        return x + y, {**stats, **selection, **chose}

    return layer


def _rotaries(c: GqaMoeConfig, rows: int, seq: int, positions):
    """(the main heads' rotary tables, the indexer's or None)."""
    if c.rope_sections:
        pos = _position_rows(positions, rows, seq)
        return _section_tables(pos, c), _index_tables(pos, c)
    index = _index_tables(_position_rows(None, rows, seq),
                          c) if c.has_sparse else None
    return _rotary_tables(seq, c), index


def apply_hidden(params: Dict, input_ids: jax.Array, config: GqaMoeConfig,
                 positions=None):
    """(final hidden states [B, S, D] in the compute dtype, the layers'
    counters summed over the layers). ``positions`` [3, S] or [B, 3, S]
    are for a model with ``rope_sections``; None is text."""
    c = config
    plan = layer_plan(c)
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    rotary, index_rotary = _rotaries(c, *input_ids.shape, positions)
    # a layer's checkpoint keeps its attention kernel's output and
    # logsumexp beside what the policy saves, so its replay leaves the
    # forward kernel out (``flash_fwd``, ``flash_win_fwd``,
    # ``dsa_attn_fwd``); a sparse layer's also the three gradients of
    # its indexer's loss, and ``dsa_index_kl`` is out too
    sparse_keep = (sparse_attention.KEPT_NAMES
                   + sparse_attention.INDEX_KEPT_NAMES)
    layers = [apply_remat(
        _layer(c, kind, rotary, index_rotary), c.remat_policy,
        keep=sparse_keep if kind[0] == SPARSE else flash_attention.KEPT_NAMES)
        for kind in plan]
    x, stats = common.scan_periods(layers, x, params["layers"])
    x = rms_norm(x, params["norm"]["scale"].astype(c.compute_dtype),
                 c.rms_norm_eps)
    stats = jax.tree.map(lambda a: a.sum(axis=0), stats)
    # the kernels' forward rules alone name what is kept, and with no
    # remat there is no checkpoint to keep it; either kind of kernel's
    # ``out`` and ``lse`` are the same bytes a layer
    keeps = c.use_kernels and remat_enabled(c.remat_policy)
    kinds = layer_kinds(c)
    out_and_lse = sparse_attention.kept_bytes(
        input_ids.shape[0], c.num_heads, input_ids.shape[1], c.head_dim,
        c.compute_dtype)
    flash_layers = kinds[DeviceScope.ATTN_FULL] + kinds[
        DeviceScope.ATTN_WINDOW]
    if flash_layers:
        stats[StepCounter.ATTN_KEPT_BYTES] = jnp.float32(
            keeps * flash_layers * out_and_lse)
    if c.has_sparse:
        kept = keeps * kinds[DeviceScope.ATTN_SPARSE]
        stats[StepCounter.DSA_ATTN_KEPT_BYTES] = jnp.float32(
            kept * out_and_lse)
        stats[StepCounter.DSA_INDEX_KEPT_BYTES] = jnp.float32(
            kept * sparse_attention.index_kept_bytes(
                input_ids.shape[0], c.index_heads, input_ids.shape[1],
                c.index_head_dim, c.compute_dtype))
    return x, stats


def apply_layers(params: Dict, input_ids: jax.Array, config: GqaMoeConfig,
                 positions=None):
    """``apply_hidden`` a layer at a time, outside any scan and with no
    remat, for a comparison that wants what each layer chose: yields
    every layer's counters with ``experts`` and, of a sparse layer,
    ``selected`` (``_layer``'s ``tell``), in order, and last the final
    normed hidden states [B, S, D]."""
    c = config
    plan = layer_plan(c)
    rotary, index_rotary = _rotaries(c, *input_ids.shape, positions)

    def run(kind):
        layer = _layer(c, kind, rotary, index_rotary, tell=True)
        # the layer's index is an argument: one compile serves a kind
        return jax.jit(lambda x, stack, i: layer(x, jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
            stack)))

    layers = {str(j): run(kind) for j, kind in enumerate(plan)}
    x = params["embed_tokens"]["embedding"][input_ids].astype(
        c.compute_dtype)
    for index in range(c.num_layers):
        j, at = common.layer_slot(index, len(plan))
        x, chose = layers[j](x, params["layers"][j], at)
        yield chose
    yield rms_norm(x, params["norm"]["scale"].astype(c.compute_dtype),
                   c.rms_norm_eps)


def apply(params: Dict, input_ids: jax.Array,
          config: GqaMoeConfig) -> jax.Array:
    """Logits [B, S, V] in float32."""
    x, _ = apply_hidden(params, input_ids, config)
    return (x @ params["lm_head"]["kernel"].astype(
        config.compute_dtype)).astype(jnp.float32)


# -- training glue ----------------------------------------------------------


def make_init_fn(config: GqaMoeConfig):
    return common.make_init_fn(init, config, layer_kinds(config))


def make_loss_fn(config: GqaMoeConfig, head_chunk: int = 0):
    """Causal-LM loss over batches {"input_ids", "labels"} and, where
    the model has them, "position_ids" [B, 3, S] (absent: text); the aux
    counts the held experts' rows, those past the row buffer among
    them. With ``head_chunk`` the head is fused with the cross entropy
    over sequence chunks (``losses.lm_head_loss``). A model
    with sparse layers adds ``index_loss_weight`` times the sum over
    those layers of the indexer's loss, and its aux counts the
    selection and carries that sum."""

    def loss_fn(params, batch, rng):
        del rng  # no dropout, no router noise
        hidden, stats = apply_hidden(params, batch["input_ids"], config,
                                     batch.get("position_ids"))
        rows, seq = batch["input_ids"].shape
        # what the window layers' forward kernels visit, a call a row,
        # head and layer; XLA's dense attention visits no tile
        window_counters = flash_attention.band_tile_counters(
            rows * config.num_heads
            * layer_kinds(config)[DeviceScope.ATTN_WINDOW],
            seq, config.sliding_window, config.window_block,
        ) if config.use_kernels else {}
        loss = lm_head_loss(hidden, params["lm_head"]["kernel"],
                            batch["labels"], head_chunk)
        counted = {name: stats[name] for name in (
            *_SELECTION_COUNTERS, StepCounter.DSA_ATTN_KEPT_BYTES,
            StepCounter.DSA_INDEX_KEPT_BYTES, StepCounter.ATTN_KEPT_BYTES)
            if name in stats}
        if config.has_sparse:
            loss = loss + config.index_loss_weight * counted[
                StepCounter.DSA_INDEX_KL]
        return loss, {
            StepCounter.MOE_ROWS_HELD: stats["rows_held"],
            StepCounter.MOE_ROWS_MAX: stats["rows_max"],
            StepCounter.MOE_ROWS_DROPPED: stats["rows_dropped"],
            StepCounter.MOE_ROWS_BUFFERED: stats["rows_buffered"],
            **window_counters, **counted,
        }

    return loss_fn


def param_count(config: GqaMoeConfig) -> int:
    return common.param_count(make_init_fn(config))
