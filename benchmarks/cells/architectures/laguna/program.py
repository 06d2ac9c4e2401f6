"""Laguna as the program under test builds it: ``tony_tpu``'s ``Transformer``
over a per-layer description (window or full causal attention; the layer's
own count of q heads; its own RoPE, plain over the whole head or YaRN over
its first half; the per-head output gate; the dense MLP in the leading layer
and, after it, the expert layer told which experts it holds, with its shared
expert and the factor on the routed weights), the flash kernels, every block
recomputed in the backward pass but for the flash forward's outputs, and the
loss taken in chunks of the sequence over the untied head.

``control`` ("int8") switches the program's own lower-precision path on
(``TransformerConfig.matmul_dtype``) for every block's forward matmuls: the
attention projections wq, wk, wv and wo, the dense MLP's and the shared
expert's gate, up and down, and the routed experts' three grouped matmuls
(int8 rows by int8 matrices in ``moe_gmm``). The gradients stay those of the
unquantized products. The router (float32), the gate's projection wg, the
embedding and the head stay as they are. The grouped matmuls have no fp8
path: ``control="fp8_e4m3"`` is refused by the expert layer.
"""

from __future__ import annotations


def rope_spec(rope: dict):
    """One kind of layer's entry of ``rope_parameters`` as the program's
    ``RopeSpec``."""
    from tony_tpu.models.transformer import RopeSpec, Yarn

    yarn = None
    if rope["rope_type"] == "yarn":
        yarn = Yarn(factor=rope["factor"],
                    original_max_position=rope[
                        "original_max_position_embeddings"],
                    beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                    attention_factor=rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    return RopeSpec(theta=float(rope["rope_theta"]),
                    rotated=rope["partial_rotary_factor"], yarn=yarn)


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models.moe import ExpertSpec
    from tony_tpu.models.transformer import LayerSpec, TransformerConfig

    experts = ExpertSpec(
        n_experts=cfg["published"]["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        width=cfg["moe_intermediate_size"], activation="silu",
        held=(cfg.get("share", {}).get("first_expert_held", 0),
              cfg["num_experts"]),
        tile_rows=cfg["train"]["moe_tile_rows"],
        chunk_tokens=cfg["train"]["moe_chunk_tokens"],
        shared_width=cfg["shared_expert_intermediate_size"],
        routed_scale=cfg["moe_routed_scaling_factor"])
    layers = tuple(
        LayerSpec(
            window=cfg["sliding_window"] if kind == "sliding_attention"
            else None,
            rope=rope_spec(cfg["rope_parameters"][kind]),
            experts=None if mlp == "dense" else experts,
            n_heads=heads, gate=gating == "per_head")
        for kind, mlp, heads, gating in zip(
            cfg["layer_types"], cfg["mlp_layer_types"],
            cfg["num_attention_heads_per_layer"], cfg["gating_types"],
            strict=True))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=max(traffic["seq"], cfg["max_position_embeddings"]),
        norm_eps=cfg["rms_norm_eps"], attn_impl="flash", remat=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None, layers=layers)


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). The step's aux metrics are what
    the layers sowed (``layer_counters``: the expert layers' counters and
    ``attn_gate_mean``)."""
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
