"""Parameter/activation sharding rules: regex path -> PartitionSpec.

Role parity: the *declarative* replacement for atorch's wrapper stack —
``modules_registry.py`` (shardable-op -> sharded-op map driving automatic
TP), ``zero_optimization.py`` (FSDP wrapping) and the MIP planner's output.
On TPU all of those collapse into: every parameter gets a
``NamedSharding``, and XLA's SPMD partitioner inserts the collectives.

Rule grammar (first match wins):
  (r"attention/(q|k|v)_proj/kernel", ("embed", "tensor"))   explicit spec
  (r".*", FSDP_AUTO)                                        shard largest
                                                            divisible dim
                                                            on the fsdp axis
Axis-name tokens in specs are *mesh* axis names; None replicates that dim.

Scan-stacked layers under FSDP (one layout, every model): a stacked
``layers/`` leaf ``[L, ...]`` never has ``fsdp`` on its layer axis. The
scan over layers slices that axis in every iteration, so a stack sharded
there is gathered whole, all L layers of it, to take one layer out, L
times a pass. ``fsdp`` goes on the kernel's hidden (embedding) axis, the
one Megatron's column/row split leaves free, and ``tensor`` stays on the
other: the slice is local and the partitioner gathers one layer's weight
where that layer uses it (ZeRO-3's per-layer gather). Stacked norms and
biases are a few KB a layer and replicate over ``fsdp``. Only a pipeline
shards the layer axis (``*_pp_rules``, on ``pipe``: a stage owns its
layers and never slices another's).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from dlrover_tpu.common.log import get_logger

logger = get_logger("parallel.rules")

FSDP_AUTO = "FSDP_AUTO"
REPLICATED = "REPLICATED"

# A scan-stacked [L, in, out] kernel under FSDP (module docstring): the
# layer axis whole, fsdp on the hidden axis, tensor on the other. Column
# = q/k/v/gate/up (hidden in), row = o/down (hidden out).
STACKED_COLUMN = (None, "fsdp", "tensor")
STACKED_ROW = (None, "tensor", "fsdp")

SpecLike = Union[str, Tuple, None]
Rule = Tuple[str, SpecLike]


def _flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        out.append((name, leaf))
    return out


def _auto_fsdp_spec(shape: Sequence[int], mesh_axis_sizes: Dict[str, int],
                    fsdp_axis: str = "fsdp") -> Tuple:
    """Shard the largest dim divisible by the fsdp axis size; replicate if
    nothing divides (small params aren't worth scattering)."""
    size = mesh_axis_sizes.get(fsdp_axis, 1)
    if size <= 1 or not shape:
        return tuple(None for _ in shape)
    best_dim, best_len = -1, 0
    for i, d in enumerate(shape):
        if d % size == 0 and d > best_len:
            best_dim, best_len = i, d
    spec = [None] * len(shape)
    if best_dim >= 0:
        spec[best_dim] = fsdp_axis
    return tuple(spec)


def _normalize_spec(spec: SpecLike, shape: Sequence[int],
                    mesh_axis_sizes: Dict[str, int]) -> Tuple:
    if spec == FSDP_AUTO:
        return _auto_fsdp_spec(shape, mesh_axis_sizes)
    if spec in (REPLICATED, None):
        return tuple(None for _ in shape)
    if isinstance(spec, str):
        raise ValueError(
            f"string spec {spec!r} is ambiguous: use FSDP_AUTO, REPLICATED "
            "or a tuple like (None, 'fsdp')"
        )
    # tuple spec: rank must match exactly (rank-mismatched rules never
    # bind — see spec_for — so this is an internal invariant)
    spec = tuple(spec)
    if len(spec) != len(shape):
        raise ValueError(
            f"spec {spec} has rank {len(spec)} but tensor has rank "
            f"{len(shape)}"
        )
    out = []
    for dim, names in zip(shape, spec):
        if names is None:
            out.append(None)
            continue
        names_t = (names,) if isinstance(names, str) else tuple(names)
        total = 1
        for n in names_t:
            total *= mesh_axis_sizes.get(n, 1)
        if total <= 1 or dim % total != 0:
            out.append(None)  # axis collapsed or indivisible: replicate
        else:
            out.append(names if isinstance(names, str) else names_t)
    return tuple(out)


class ShardingRules:
    def __init__(self, rules: Optional[List[Rule]] = None,
                 default: SpecLike = FSDP_AUTO):
        self.rules = list(rules or [])
        self.default = default

    def spec_for(self, path: str, shape: Sequence[int],
                 mesh_axis_sizes: Dict[str, int]) -> Tuple:
        for pattern, spec in self.rules:
            if not re.search(pattern, path):
                continue
            # a tuple spec only binds at its exact rank; rank-mismatched
            # rules fall through (lets stacked [L, ...] and unstacked
            # variants of the same param coexist in one rule list)
            if isinstance(spec, (tuple, list)) and len(spec) != len(shape):
                continue
            return _normalize_spec(spec, shape, mesh_axis_sizes)
        return _normalize_spec(self.default, shape, mesh_axis_sizes)

    def tree_shardings(self, mesh, tree_shapes):
        """Map a pytree of ShapeDtypeStruct/arrays -> NamedShardings."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        flat = _flatten_with_paths(tree_shapes)
        specs = {}
        for path, leaf in flat:
            shape = getattr(leaf, "shape", ())
            specs[path] = self.spec_for(path, shape, axis_sizes)

        def to_sharding(path_leaf):
            path, leaf = path_leaf
            return NamedSharding(mesh, PartitionSpec(*specs[path]))

        shardings = [to_sharding(pl) for pl in flat]
        treedef = jax.tree_util.tree_structure(tree_shapes)
        return jax.tree_util.tree_unflatten(treedef, shardings)


def batch_sharding(mesh, spec_axes=(("data", "fsdp"),)):
    """NamedSharding for input batches: leading (batch) dim split across
    the data-parallel axes."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec_axes))


# -- canonical rule sets ----------------------------------------------------

def llama_rules() -> ShardingRules:
    """Megatron-style TP + FSDP for llama-family transformers.

    Parity map (atorch -> here):
      ColumnParallelLinear (layers.py:380)  -> kernel last dim on "tensor"
      RowParallelLinear    (layers.py:227)  -> kernel first dim on "tensor"
      VocabParallelEmbedding (layers.py:540)-> embedding vocab dim sharded
    """
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj)/kernel$", STACKED_COLUMN),
        (r"layers/.*o_proj/kernel$", STACKED_ROW),
        (r"layers/.*(gate_proj|up_proj)/kernel$", STACKED_COLUMN),
        (r"layers/.*down_proj/kernel$", STACKED_ROW),
        # MoE blocks: experts over the (data x fsdp) submesh
        (r"layers/.*experts/up/kernel$",
         (None, ("data", "fsdp"), None, "tensor")),
        (r"layers/.*experts/down/kernel$",
         (None, ("data", "fsdp"), "tensor", None)),
        (r"layers/.*router/kernel$", REPLICATED),
        # unstacked variants (per-layer module trees)
        (r"(q_proj|k_proj|v_proj)/kernel$", (None, "tensor")),
        (r"o_proj/kernel$", ("tensor", None)),
        (r"(gate_proj|up_proj)/kernel$", (None, "tensor")),
        (r"down_proj/kernel$", ("tensor", None)),
        # embeddings / head: vocab-parallel
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        # norms replicate
        (r"(norm|ln)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def llama_pp_rules() -> ShardingRules:
    """Pipeline-parallel llama: the stacked layer dim lands on "pipe" so
    each pipeline stage holds its contiguous chunk of layers
    (``parallel.pipeline`` reshapes [L, ...] -> [P, L/P, ...] in-program,
    a local reshape since L is pipe-sharded). TP stays on "tensor"."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj)/kernel$",
         ("pipe", None, "tensor")),
        (r"layers/.*o_proj/kernel$", ("pipe", "tensor", None)),
        (r"layers/.*(gate_proj|up_proj)/kernel$", ("pipe", None, "tensor")),
        (r"layers/.*down_proj/kernel$", ("pipe", "tensor", None)),
        (r"layers/.*experts/up/kernel$",
         ("pipe", ("data", "fsdp"), None, "tensor")),
        (r"layers/.*experts/down/kernel$",
         ("pipe", ("data", "fsdp"), "tensor", None)),
        (r"layers/.*router/kernel$", ("pipe", None, None)),
        (r"layers/.*(input_norm|post_norm)/scale$", ("pipe", None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"(norm|ln)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def bert_rules() -> ShardingRules:
    """BERT-family encoders: same Megatron TP layout as llama plus the
    three-table embedding block and the MLM head."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/kernel$",
         STACKED_COLUMN),
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/bias$",
         (None, "tensor")),
        (r"layers/.*(o_proj|down_proj)/kernel$", STACKED_ROW),
        (r"layers/.*(o_proj|down_proj)/bias$", (None, None)),
        (r"embeddings/word/embedding$", ("tensor", "fsdp")),
        (r"embeddings/(position|token_type)/embedding$", (None, "fsdp")),
        (r"mlm_head/kernel$", ("fsdp", "tensor")),
        (r"mlm_head/bias$", ("tensor",)),
        (r"(norm|ln)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def bert_pp_rules() -> ShardingRules:
    """Pipeline-parallel BERT: stacked layer dim on "pipe" (layer bias
    vectors and the o/down biases included); embeddings, pooler and the
    MLM head stay outside the pipe."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/kernel$",
         ("pipe", None, "tensor")),
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/bias$",
         ("pipe", "tensor")),
        (r"layers/.*(o_proj|down_proj)/kernel$", ("pipe", "tensor", None)),
        (r"layers/.*(o_proj|down_proj)/bias$", ("pipe", None)),
        (r"layers/.*(attn_norm|ffn_norm)/(scale|bias)$", ("pipe", None)),
        (r"embeddings/word/embedding$", ("tensor", "fsdp")),
        (r"embeddings/(position|token_type)/embedding$", (None, "fsdp")),
        (r"mlm_head/kernel$", ("fsdp", "tensor")),
        (r"mlm_head/bias$", ("tensor",)),
        (r"(norm|ln)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def clip_rules() -> ShardingRules:
    """CLIP dual encoder: both towers' stacked blocks reuse the llama
    TP/FSDP layout (paths are nested under text/ and vision/)."""
    return llama_rules()


def neox_rules() -> ShardingRules:
    """GPT-NeoX / GLM family: llama's Megatron column/row layout plus the
    bias vectors — a column-parallel projection's bias shards with its
    output dim; a row-parallel projection's bias replicates (it adds after
    the reduce)."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/kernel$",
         STACKED_COLUMN),
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/bias$",
         (None, "tensor")),
        (r"layers/.*(o_proj|down_proj)/kernel$", STACKED_ROW),
        (r"layers/.*(o_proj|down_proj)/bias$", (None, None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"(pos|block_pos)_embed/embedding$", (None, "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"(norm|ln|final_norm)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def glm_rules() -> ShardingRules:
    """GLM shares NeoX's biased-projection layout; the 2D position tables
    get their own fsdp rule (in neox_rules already)."""
    return neox_rules()


def neox_pp_rules() -> ShardingRules:
    """Pipeline-parallel NeoX/GLM: like ``llama_pp_rules``, the stacked
    layer dim lands on "pipe" (each stage holds its chunk locally); bias
    vectors follow their kernels' tensor split."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/kernel$",
         ("pipe", None, "tensor")),
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/bias$",
         ("pipe", "tensor")),
        (r"layers/.*(o_proj|down_proj)/kernel$", ("pipe", "tensor", None)),
        (r"layers/.*(o_proj|down_proj)/bias$", ("pipe", None)),
        (r"layers/.*(input_norm|post_norm)/(scale|bias)$", ("pipe", None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"(pos|block_pos)_embed/embedding$", (None, "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"(norm|ln|final_norm)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def glm_pp_rules() -> ShardingRules:
    """GLM pipeline layout = NeoX's (same biased-projection family)."""
    return neox_pp_rules()


def gpt2_pp_rules() -> ShardingRules:
    """Pipeline-parallel GPT-2: stacked layer dim on "pipe", Megatron
    column/row split on "tensor"; the tied embed/head table and learned
    positions stay outside the pipe (fsdp/tensor sharded)."""
    return ShardingRules(rules=[
        (r"layers/.*(q_proj|k_proj|v_proj|up_proj)/kernel$",
         ("pipe", None, "tensor")),
        (r"layers/.*up_proj/bias$", ("pipe", "tensor")),
        (r"layers/.*(o_proj|down_proj)/kernel$", ("pipe", "tensor", None)),
        (r"layers/.*down_proj/bias$", ("pipe", None)),
        (r"layers/.*(ln_1|ln_2)/(scale|bias)$", ("pipe", None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"embed_pos/embedding$", (None, "fsdp")),
        (r"(norm|ln)[^/]*/(scale|bias)$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def sambay_rules() -> ShardingRules:
    """The SambaY decoder (``models/sambay.py``): periods stacked under
    ``self_layers/`` and ``cross_layers/`` (the layout of the module
    docstring: never ``fsdp`` on the stacked axis) and the boundary pair
    unstacked under ``boundary/`` (a rank lower, so the same patterns
    bind it through the second spec of each pair). Hidden axes on
    ``fsdp``, the wide axes on ``tensor``; the two fused projections
    (``in_proj``, ``up_proj``: ``[.., hidden, 2, wide]``) keep their
    halves whole. Of a state-space layer ``tensor`` splits the channel
    axis ``d_inner`` alone: the conv, the step's projection and bias,
    ``A`` and ``D`` follow it, and ``x_proj`` contracts over it."""
    column = r"(q_proj|k_proj|v_proj|gate_proj)/kernel$"
    row = r"(o_proj|down_proj|out_proj)/kernel$"
    fused = r"(in_proj|up_proj)/kernel$"
    channels = r"(conv/(kernel|bias)|dt_proj/(kernel|bias)|d_skip)$"
    return ShardingRules(rules=[
        (column, STACKED_COLUMN), (column, STACKED_COLUMN[1:]),
        (row, STACKED_ROW), (row, STACKED_ROW[1:]),
        (fused, (None, "fsdp", None, "tensor")),
        (fused, ("fsdp", None, "tensor")),
        (r"(q_proj|k_proj|v_proj)/bias$", (None, "tensor")),
        (r"(q_proj|k_proj|v_proj)/bias$", ("tensor",)),
        # the channel axis is the last of each of these
        (channels, (None, None, "tensor")),
        (channels, (None, "tensor")),
        (channels, ("tensor",)),
        (r"(x_proj/kernel|a_log)$", (None, "tensor", None)),
        (r"(x_proj/kernel|a_log)$", ("tensor", None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        # norms, the row projections' biases, the lambda vectors
        (r"(norm|subln)/(scale|bias)$|o_proj/bias$|lambda_", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def mla_moe_rules() -> ShardingRules:
    """The latent-attention decoder with shared and routed experts
    (``models/mla_moe.py``): layers stacked under ``dense_layers/`` and
    ``moe_layers/`` (never ``fsdp`` on the stacked axis). Hidden axes on
    ``fsdp``, wide axes on ``tensor``: the head axis of ``q_b_proj``,
    ``kv_b_proj`` and ``o_proj``, the FFN width of the dense layers and
    of the shared expert. The latent projections (``q_a_proj``,
    ``kv_a_proj``) are small and shard their hidden axis alone; the
    router is whole everywhere, as its float32 scores must be. The held
    experts ``[L, held, in, out]`` stay whole on their expert axis and
    on the axes the grouped kernel reads (it is opaque to GSPMD) and
    shard the hidden axis over ``fsdp``, gathered a layer at a time.
    A hyper-connected model's ``phi`` ``[L, n * hidden, 2n + n^2]``
    shards its long axis over ``fsdp`` and its gates and biases, like
    the router's selection bias, are whole (float32 mappings of a few
    numbers); a prediction module's stack under ``mtp/`` takes the
    layers' rules, its projection ``eh_proj`` a column's. The sparse
    switches' leaves: the attention's output gate ``g_proj`` is a
    column's (its head axis on ``tensor``, beside ``o_proj``'s rows);
    the indexer is whole on ``tensor`` (the published one is replicated
    under tensor parallelism: every chip scores every key for its own
    rows) and shards its projections' input axis over ``fsdp`` like the
    latent projections, its key norm whole; a gated norm's two factors
    ``[.., hidden, rank]`` and ``[.., rank, hidden]`` are whole (a few
    hundred kilobytes that every token's norm reads). The differential
    switches' leaves: lambda's projection ``lam_proj`` ``[.., hidden,
    signal heads]`` is a latent projection's (its input axis over
    ``fsdp``); PolyNorm's ``act/weight`` and ``act/bias`` (three numbers
    and one an FFN) are whole; and the router's selection bias as a
    buffer of the training state lies under ``buffers/.../router/bias``
    and takes the parameter's rule: whole."""
    column = (r"(q_b_proj|kv_b_proj|g_proj|gate_proj|up_proj|eh_proj)"
              r"/kernel$")
    row = r"(o_proj|down_proj)/kernel$"
    return ShardingRules(rules=[
        (r"experts/(gate|up)/kernel$", (None, None, "fsdp", None)),
        (r"experts/down/kernel$", (None, None, None, "fsdp")),
        (r"router/(kernel|bias)$", REPLICATED),
        (r"act/(weight|bias)$", REPLICATED),
        (r"index/(q_proj|k_proj|w_proj)/kernel$", (None, "fsdp", None)),
        (r"index/k_norm/(scale|bias)$", REPLICATED),
        (r"norm/gate_(a|b)$", REPLICATED),
        (r"hc_(attn|ffn)/phi/kernel$", (None, "fsdp", None)),
        (r"hc_(attn|ffn)/(alpha|bias)$", REPLICATED),
        (column, STACKED_COLUMN),
        (row, STACKED_ROW),
        (r"(q_a_proj|kv_a_proj|lam_proj)/kernel$", (None, "fsdp", None)),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def gqa_moe_rules() -> ShardingRules:
    """The grouped-query decoder of window and full layers with held
    experts (``models/gqa_moe.py``): the layers at each position of the
    period stacked over the periods under ``layers/<position>/`` (never
    ``fsdp`` on the stacked axis). As ``mla_moe_rules`` treats their
    like: hidden axes on ``fsdp``, the head axis of ``q_proj``,
    ``k_proj``, ``v_proj`` and ``o_proj`` on ``tensor`` (28 query and 4
    KV heads halve and quarter; the flash kernels run under
    ``shard_map`` over it); the router is whole everywhere, as its
    float32 scores must be; the held experts ``[periods, held, in,
    out]`` stay whole on their expert axis and on the axes the grouped
    kernel reads (it is opaque to GSPMD) and shard the hidden axis over
    ``fsdp``, gathered a layer at a time."""
    return ShardingRules(rules=[
        (r"experts/(gate|up)/kernel$", (None, None, "fsdp", None)),
        (r"experts/down/kernel$", (None, None, None, "fsdp")),
        (r"router/kernel$", REPLICATED),
        (r"(q_proj|k_proj|v_proj)/kernel$", STACKED_COLUMN),
        (r"o_proj/kernel$", STACKED_ROW),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def delta_hybrid_rules() -> ShardingRules:
    """The decoder of gated-delta-rule and full attention layers
    (``models/delta_hybrid.py``): the layers at each position of the
    period stacked over the periods under ``layers/<position>/`` (never
    ``fsdp`` on the stacked axis), the two kinds with their own trees.
    Hidden axes on ``fsdp``; on ``tensor`` the head axis of both kinds
    of mixer (``q_proj``, ``k_proj``, ``v_proj``, ``g_proj`` and
    ``o_proj``; the ``gdn_*`` and flash kernels run under ``shard_map``
    over it) and what a linear layer keeps a head or a channel: the
    decay's and ``beta``'s projections ``[.., hidden, heads]``, the
    three convolutions ``[.., width, channels]``, ``a_log`` and
    ``dt_bias``; the FFN's width. The norm scales are whole everywhere
    (``q_norm`` and ``k_norm`` reduce over all the heads' columns)."""
    column = r"(q_proj|k_proj|v_proj|g_proj|a_proj|b_proj)/kernel$"
    return ShardingRules(rules=[
        (column, STACKED_COLUMN),
        (r"(gate_proj|up_proj)/kernel$", STACKED_COLUMN),
        (r"(o_proj|down_proj)/kernel$", STACKED_ROW),
        (r"(q_conv|k_conv|v_conv)/kernel$", (None, None, "tensor")),
        (r"(a_log|dt_bias)$", (None, "tensor")),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def ssd_hybrid_rules() -> ShardingRules:
    """The decoder of Mamba-2 and position-free attention layers
    (``models/ssd_hybrid.py``): the layers at each position of the
    period stacked over the periods under ``layers/<position>/`` (never
    ``fsdp`` on the stacked axis), the two kinds with their own trees.
    Hidden axes on ``fsdp``. On ``tensor``: the head axis of an
    attention layer's ``q_proj``, ``k_proj``, ``v_proj`` and ``o_proj``
    (the flash kernels run under ``shard_map`` over it), the rows of a
    Mamba layer's ``out_proj`` and what it keeps a head (``a_log``,
    ``dt_bias``, ``d_skip``; the ``ssd_*`` kernels run under
    ``shard_map`` with the heads on ``tensor``), and the MLP's width on
    ``down_proj``. A Mamba layer's ``in_proj`` is ``[z | xBC | dt]``
    side by side and the MLP's ``gate_up_proj`` ``[a | b]``: a split of
    their columns would fall inside a part, so they keep ``tensor`` off
    their columns, as do the convolution (``B`` and ``C`` are shared by
    every head) and the gated norm's scale (it reduces over all the
    heads' columns). The token table is the head too: vocabulary on
    ``tensor``, hidden on ``fsdp``."""
    return ShardingRules(rules=[
        (r"(q_proj|k_proj|v_proj)/kernel$", STACKED_COLUMN),
        (r"(o_proj|out_proj|down_proj)/kernel$", STACKED_ROW),
        (r"(in_proj|gate_up_proj)/kernel$", (None, "fsdp", None)),
        (r"(a_log|dt_bias|d_skip)$", (None, "tensor")),
        (r"conv/(kernel|bias)$", REPLICATED),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def kda_mla_moe_rules() -> ShardingRules:
    """The decoder of Kimi-delta-attention and latent-attention layers
    with held experts (``models/kda_mla_moe.py``): the leading dense
    layers stacked under ``dense_layers/`` (``[layers, ...]``), the
    expert layers by run of one mixer under ``layers/<run>/``
    (``[groups, the run's layers, ...]``: two stacked axes, never
    ``fsdp`` on either; each pattern binds the one rank through the
    first spec of its pair and the other through the second), the two
    kinds of mixer with their own trees. Hidden axes on ``fsdp``; on
    ``tensor`` the head axis of both mixers: a KDA layer's ``q_proj``,
    ``k_proj``, ``v_proj``, the decay's ``f_proj``, the output gate's
    ``g_proj``, ``beta``'s ``b_proj`` and ``o_proj`` with what it keeps
    a head or a channel (the three convolutions ``[.., taps,
    channels]``, ``a_log``, the per-channel ``dt_bias``; the ``kda_*``
    kernels run under ``shard_map`` over it), an MLA layer's ``q_proj``,
    ``kv_b_proj``, its head-wise gate ``g_proj`` ``[.., hidden, heads]``
    and ``o_proj``; its latent projection ``kv_a_proj`` shards its
    hidden axis alone. The FFNs as ``mla_moe_rules`` has them: the
    dense layers' and the shared expert's width on ``tensor``, the held
    experts whole on their expert axis with the hidden axis over
    ``fsdp``, the router whole, and its selection bias, a buffer of the
    training state under ``buffers/layers/<run>/moe/router/bias``,
    whole. The norm scales are whole everywhere."""
    column = (r"(q_proj|k_proj|v_proj|f_proj|g_proj|b_proj|kv_b_proj"
              r"|gate_proj|up_proj)/kernel$")
    row = r"(o_proj|down_proj)/kernel$"
    convs = r"(q_conv|k_conv|v_conv)/kernel$"
    return ShardingRules(rules=[
        (r"experts/(gate|up)/kernel$", (None, None, None, "fsdp", None)),
        (r"experts/down/kernel$", (None, None, None, None, "fsdp")),
        (r"router/(kernel|bias)$", REPLICATED),
        (column, (None,) + STACKED_COLUMN), (column, STACKED_COLUMN),
        (row, (None,) + STACKED_ROW), (row, STACKED_ROW),
        (r"kv_a_proj/kernel$", (None, None, "fsdp", None)),
        (convs, (None, None, None, "tensor")),
        (convs, (None, None, "tensor")),
        (r"(a_log|dt_bias)$", (None, None, "tensor")),
        (r"(a_log|dt_bias)$", (None, "tensor")),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def looped_rules() -> ShardingRules:
    """The decoder whose one stack runs several times a step
    (``models/looped.py``): llama's tree, the layers stacked under
    ``layers/`` (never ``fsdp`` on the stacked axis), with four norm
    scales a layer where llama has two and one exit gate for all passes.
    Hidden axes on ``fsdp``; on ``tensor`` the head axis of ``q_proj``,
    ``k_proj``, ``v_proj`` and ``o_proj`` (the flash kernels run under
    ``shard_map`` over it) and the FFN's width. The norm scales and the
    gate (a ``[hidden, 1]`` kernel and its bias) are whole everywhere. A
    layer's shard is gathered once a pass, not once a step."""
    return ShardingRules(rules=[
        (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel$",
         STACKED_COLUMN),
        (r"(o_proj|down_proj)/kernel$", STACKED_ROW),
        (r"embed_tokens/embedding$", ("tensor", "fsdp")),
        (r"lm_head/kernel$", ("fsdp", "tensor")),
        (r"exit_gate/(kernel|bias)$", REPLICATED),
        (r"norm/scale$", REPLICATED),
        (r".*", FSDP_AUTO),
    ])


def moe_rules() -> ShardingRules:
    """Expert-parallel MoE: expert weight blocks sharded on the expert
    (data x fsdp) submesh; router replicated."""
    rules = llama_rules().rules
    return ShardingRules(rules=[
        # leading dim = experts, sharded over the (data x fsdp) submesh
        (r"experts/.*kernel$", (("data", "fsdp"), None, "tensor")),
        (r"router/kernel$", REPLICATED),
        *rules,
    ])


def moe_ep_rules() -> ShardingRules:
    """Expert-parallel MoE for the DROPLESS ``dispatch="grouped_ep"``
    path (``ops.moe._moe_compute_grouped_ep``): expert weight blocks
    sharded on the (data x fsdp) expert submesh like ``moe_rules``, but
    the expert FFN dims stay UNSHARDED — the grouped Pallas kernel runs
    per shard inside a shard_map, so a "tensor" split of d_ff would
    force an all-gather at the shard_map boundary every layer instead
    of a partitioned matmul. Dense (attention) params keep the llama
    TP/FSDP layout."""
    rules = llama_rules().rules
    return ShardingRules(rules=[
        # stacked [L, E, D, F] layer variants first (rank-4 binds here)
        (r"layers/.*experts/(up|down)/kernel$",
         (None, ("data", "fsdp"), None, None)),
        # unstacked [E, D, F] module trees (direct moe_ffn params)
        (r"experts/(up|down)/kernel$", (("data", "fsdp"), None, None)),
        (r"router/kernel$", REPLICATED),
        *rules,
    ])
