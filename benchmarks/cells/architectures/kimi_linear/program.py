"""Kimi Linear as the program under test builds it: ``tony_tpu``'s
``Transformer`` over a per-layer description in which every layer is a mixer
(KDA, the gated delta rule with a decay per channel in its chunked
``kda_fwd``/``kda_bwd`` kernels, or latent attention without RoPE through
the flash kernels at q/k width 192 and v width 128) and then a feed-forward
(the dense SwiGLU in the leading layer, after it the expert layer told which
experts it holds: sigmoid scores normalised over the chosen, a factor on
the routed weights, a shared expert beside them); every block recomputed in
the backward pass but for the flash forward's outputs, and the loss taken
in chunks of the sequence over the untied head.

``control`` ("int8") switches the program's own lower-precision path on
(``TransformerConfig.matmul_dtype``) for every block's forward matmuls: the
KDA mixer's W_q, W_k, W_v and W_o, latent attention's W_q, W_kv_a, W_kv_b and
W_o, the dense MLP's and the shared expert's gate, up and down, and the
routed experts' three grouped matmuls (int8 rows by int8 matrices in
``moe_gmm``). The gradients stay those of the unquantized products. The KDA
mixer's low-rank decay and gate projections and its β, the conv and the
scan, the router (float32), the embedding and the head stay as they are.
"""

from __future__ import annotations


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models.kda import KDASpec
    from tony_tpu.models.moe import ExpertSpec
    from tony_tpu.models.transformer import (LayerSpec, MLASpec,
                                             TransformerConfig)

    lin = cfg["linear_attn_config"]
    if not cfg["mla_use_nope"] or cfg["q_lora_rank"] is not None:
        raise ValueError("the program's latent attention has no RoPE and no "
                         "low-rank q: the configuration states "
                         f"mla_use_nope {cfg['mla_use_nope']}, q_lora_rank "
                         f"{cfg['q_lora_rank']}")
    if cfg["num_expert_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("grouped top-k over more than one group is not "
                         "written down here")
    kda = KDASpec(n_heads=lin["num_heads"], head_dim=lin["head_dim"],
                  conv=lin["short_conv_kernel_size"],
                  chunk=cfg["train"]["kda_chunk"])
    mla = MLASpec(n_heads=cfg["num_attention_heads"],
                  qk_nope=cfg["qk_nope_head_dim"],
                  qk_rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                  kv_rank=cfg["kv_lora_rank"])
    experts = ExpertSpec(
        n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_token"],
        width=cfg["moe_intermediate_size"], activation=cfg["hidden_act"],
        scoring=cfg["moe_router_activation_func"],
        held=(cfg.get("share", {}).get("first_expert_held", 0),
              cfg["num_experts"]),
        tile_rows=cfg["train"]["moe_tile_rows"],
        chunk_tokens=cfg["train"]["moe_chunk_tokens"],
        shared_width=cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"])
    if not cfg["moe_renormalize"]:
        raise ValueError("the expert layer's sigmoid scores are normalised "
                         "over the chosen; moe_renormalize is false")
    layers = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        mixer = kda if i in lin["kda_layers"] else mla
        if mixer is mla and i not in lin["full_attn_layers"]:
            raise ValueError(f"layer {i} is in neither list of "
                             f"linear_attn_config")
        dense = i <= cfg["first_k_dense_replace"]
        layers.append(LayerSpec(mixer=mixer,
                                experts=None if dense else experts))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=max(traffic["seq"], cfg["model_max_length"]),
        norm_eps=cfg["rms_norm_eps"], attn_impl="flash", remat=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None, layers=tuple(layers))


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). The step's aux metrics are what
    the layers sowed (``layer_counters``: the expert layers' counters,
    ``kda_decay_mean``, ``kda_log_decay_min`` and ``kda_beta_mean``)."""
    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import (chunked_causal_lm_loss,
                                             layer_counters)

    mcfg = model_config(cfg, traffic, control)
    model = Transformer(mcfg)
    chunk = traffic["loss_chunk"]

    def loss_fn(params, batch, rng):
        h, sown = model.apply({"params": params}, batch["tokens"],
                              return_hidden=True, mutable=["intermediates"])
        loss = chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=chunk, head_dtype=mcfg.lm_head_dtype)
        return loss, layer_counters(sown.get("intermediates", {}))

    return model, loss_fn
