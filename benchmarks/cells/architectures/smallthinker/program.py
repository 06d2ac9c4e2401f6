"""SmallThinker as the program under test builds it: ``tony_tpu``'s
``Transformer`` over a per-layer description (window or full causal, RoPE or
none, the expert layer told which experts it holds, its router reading the
block's normed pre-attention input), a stated head width, the flash kernels,
every block recomputed in the backward pass but for the flash forward's
outputs, and the loss taken in chunks of the sequence over the untied head.

``control`` ("int8") switches the program's own lower-precision path on
(``TransformerConfig.matmul_dtype``) for every block's forward matmuls: the
attention projections wq, wk, wv and wo, and the experts' three grouped
matmuls (gate, up, down: int8 rows by int8 matrices in ``moe_gmm``). The
gradients stay those of the unquantized products. The router (float32), the
embedding and the head stay as they are. The grouped matmuls have no fp8
path: ``control="fp8_e4m3"`` is refused by the expert layer.
"""

from __future__ import annotations


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models.moe import ExpertSpec
    from tony_tpu.models.transformer import LayerSpec, TransformerConfig

    experts = ExpertSpec(
        n_experts=cfg["published"]["moe_num_primary_experts"],
        top_k=cfg["moe_num_active_primary_experts"],
        width=cfg["moe_ffn_hidden_size"], activation="relu",
        held=(cfg.get("share", {}).get("first_expert_held", 0),
              cfg["moe_num_primary_experts"]),
        route_before_attention=True,
        tile_rows=cfg["train"]["moe_tile_rows"],
        chunk_tokens=cfg["train"]["moe_chunk_tokens"])
    layers = tuple(
        LayerSpec(window=cfg["sliding_window_size"] if banded else None,
                  rope=bool(rotary), experts=experts)
        for banded, rotary in zip(cfg["sliding_window_layout"],
                                  cfg["rope_layout"], strict=True))
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["moe_ffn_hidden_size"],
        max_seq_len=max(traffic["seq"], cfg["max_position_embeddings"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        attn_impl="flash", remat=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None, layers=layers)


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). The step's aux metrics are the
    expert layers' counters (``moe_counters``)."""
    from tony_tpu.models import Transformer
    from tony_tpu.models.moe import moe_counters
    from tony_tpu.models.transformer import chunked_causal_lm_loss

    mcfg = model_config(cfg, traffic, control)
    model = Transformer(mcfg)
    chunk = traffic["loss_chunk"]

    def loss_fn(params, batch, rng):
        h, sown = model.apply({"params": params}, batch["tokens"],
                              return_hidden=True, mutable=["intermediates"])
        loss = chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=chunk, head_dtype=mcfg.lm_head_dtype)
        return loss, moe_counters(sown.get("intermediates", {}))

    return model, loss_fn
