"""Nemotron-H as the program under test builds it: ``tony_tpu``'s
``Transformer`` over a per-layer description in which every layer is one
part (the Mamba-2 mixer with its chunked scan kernels, full causal attention
without position embedding, or the expert layer told which experts it holds:
squared-ReLU experts of two matrices under a sigmoid router, a shared expert
of its own width beside them and the factor on the routed weights), the
flash kernels, every block recomputed in the backward pass but for the flash
forward's outputs, and the loss taken in chunks of the sequence over the
untied head.

``control`` ("int8") switches the program's own lower-precision path on
(``TransformerConfig.matmul_dtype``) for every block's forward matmuls: the
mixer's wz, wxbc, wdt and wo (all of W_in, and W_out), the attention
projections wq, wk, wv and wo, the shared expert's up and down, and the
routed experts' two grouped matmuls (int8 rows by int8 matrices in
``moe_gmm``). The gradients stay those of the unquantized products. The
mixer's conv and scan, the router (float32), the embedding and the head
stay as they are. The grouped matmuls have no fp8
path: ``control="fp8_e4m3"`` is refused by the expert layer.
"""

from __future__ import annotations


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models.moe import ExpertSpec
    from tony_tpu.models.ssm import SSMSpec
    from tony_tpu.models.transformer import LayerSpec, TransformerConfig

    mixer = SSMSpec(
        n_heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], state=cfg["ssm_state_size"],
        conv=cfg["conv_kernel"], chunk=cfg["chunk_size"])
    experts = ExpertSpec(
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"],
        activation=cfg["mlp_hidden_act"], gated=False, scoring="sigmoid",
        held=(cfg.get("share", {}).get("first_expert_held", 0),
              cfg["n_routed_experts"]),
        tile_rows=cfg["train"]["moe_tile_rows"],
        chunk_tokens=cfg["train"]["moe_chunk_tokens"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"])
    kinds = {
        "M": LayerSpec(mixer=mixer, feed_forward=False),
        "*": LayerSpec(rope=False, feed_forward=False),
        "E": LayerSpec(mixer=None, experts=experts),
    }
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=max(traffic["seq"], cfg["max_position_embeddings"]),
        norm_eps=cfg["layer_norm_epsilon"], attn_impl="flash", remat=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None,
        layers=tuple(kinds[letter]
                     for letter in cfg["hybrid_override_pattern"]))


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). The step's aux metrics are what
    the layers sowed (``layer_counters``: the expert layers' counters,
    ``ssm_dt_mean`` and ``ssm_decay_mean``)."""
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
