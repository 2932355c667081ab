"""Model families, one module each (config, ``init``, ``apply``,
``make_init_fn``, ``make_loss_fn``, ``param_count``):

* ``llama``: dense and switch-MoE decoders (training and serving);
* ``sambay``: the SambaY decoder-hybrid-decoder of Phi-4-mini-flash
  (Mamba layers, window, full and cross differential attention, gated
  memory units; training);
* ``mla_moe``: the latent-attention decoder with shared and routed
  gated experts of A.X-K1 (MLA through the latent flash kernels, a
  leading dense layer, a sigmoid top-k router over all the experts and
  the set of them this chip holds; training);
* ``gqa_moe``: the grouped-query decoder of SmallThinker (window and
  full attention layers in one scanned stack, chosen by two per-layer
  lists, rotary on the one kind and no position on the other; every
  layer an expert layer of held ReGLU experts under a softmax top-k
  router that reads the attention's input; training);
* ``delta_hybrid``: the decoder of gated-delta-rule linear-attention
  layers and full, position-free attention layers of Olmo-Hybrid (two
  kinds of mixer with their own parameter trees in one scanned stack,
  chosen by a per-layer list; the rule through ``ops.gated_delta``;
  the sublayer's output normalised, then added; training);
* ``ssd_hybrid``: the decoder of Mamba-2 state-space mixers and
  position-free grouped-query attention layers of Granite-4.0-H (two
  kinds of mixer with their own parameter trees in one scanned stack,
  chosen by a per-layer list; the recurrence through ``ops.ssd``;
  pre-norm residuals, the embedding, the softmax scale and the logits
  under the family's four multipliers; the gate before the mixer's
  norm; a head tied to the table; training);
* ``kda_mla_moe``: the decoder of Kimi-delta-attention layers (the
  delta rule under a per-channel bounded decay through ``ops.kda``) and
  latent-attention layers without a query latent of Ling-3.0-flash
  (five to one in a group of six, a head-wise gate and QK norms on the
  latent layers), a leading dense layer in a stack of its own, the rest
  expert layers of ``mla_moe``'s kind under a group-limited router
  whose selection bias the step moves; training);
* ``looped``: the looped decoder of Ouro (ONE stack of rotary
  full-attention layers with four norms each run ``total_ut_steps``
  times a step on the same weights, the scan over the stack inside the
  scan over the passes; the one final norm, an exit gate and the head
  after every pass, the head's losses under the exit distribution's
  weights through ``losses.weighted_lm_head_loss``; training);
* ``gpt_neox``, ``gpt2``, ``glm``: further decoders; ``bert``, ``clip``:
  encoders; ``deepfm``, ``mnist_cnn``: the small ones.

``common`` and ``losses`` hold what they share; each trains under the
rule set of its name in ``parallel.strategy.RULE_SETS``.
"""
