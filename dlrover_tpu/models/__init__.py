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
* ``gpt_neox``, ``gpt2``, ``glm``: further decoders; ``bert``, ``clip``:
  encoders; ``deepfm``, ``mnist_cnn``: the small ones.

``common`` and ``losses`` hold what they share; each trains under the
rule set of its name in ``parallel.strategy.RULE_SETS``.
"""
