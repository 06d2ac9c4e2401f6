"""Granite 4.0-H as the program under test builds it: ``tony_tpu``'s
``Transformer`` over a per-layer description in which every layer is two
parts, a mixer (the Mamba-2 mixer with its chunked scan kernels, one group of
B and C for every head, or full causal attention without position
embedding) and the dense MLP; the four multipliers on the embedding, the
residual branches, the softmax's scale and the logits; the flash kernels,
every block recomputed in the backward pass but for the flash forward's
outputs, and the loss taken in chunks of the sequence over the tied head.

``control`` ("int8") switches the program's own lower-precision path on
(``TransformerConfig.matmul_dtype``) for every block's forward matmuls: the
mixer's wz, wxbc, wdt and wo, the attention projections wq, wk, wv and wo,
and the MLP's gate, up and down. The gradients stay those of the unquantized
products. The mixer's conv and scan, the embedding and the head stay as they
are.
"""

from __future__ import annotations


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models.ssm import SSMSpec
    from tony_tpu.models.transformer import LayerSpec, TransformerConfig

    mixer = SSMSpec(
        n_heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
        n_groups=cfg["mamba_n_groups"], state=cfg["mamba_d_state"],
        conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"])
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("the program's granite attention has no position "
                         "embedding; the configuration states "
                         f"{cfg['position_embedding_type']!r}")
    kinds = {"mamba": LayerSpec(mixer=mixer),
             "attention": LayerSpec(rope=False)}
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["shared_intermediate_size"],
        max_seq_len=max(traffic["seq"], cfg["max_position_embeddings"]),
        norm_eps=cfg["rms_norm_eps"], attn_impl="flash", remat=True,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None,
        layers=tuple(kinds[kind] for kind in cfg["layer_types"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"])


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). The step's aux metrics are what
    the mixers sowed (``layer_counters``: ``ssm_dt_mean``,
    ``ssm_decay_mean``, ``ssm_head_rms_max_over_median``)."""
    import jax

    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import (chunked_causal_lm_loss,
                                             layer_counters)

    mcfg = model_config(cfg, traffic, control)
    if not mcfg.tie_embeddings:
        raise ValueError("this architecture ties the head to the embedding; "
                         "the configuration says tie_word_embeddings false")
    model = Transformer(mcfg)
    chunk = traffic["loss_chunk"]

    def loss_fn(params, batch, rng):
        h, sown = model.apply({"params": params}, batch["tokens"],
                              return_hidden=True, mutable=["intermediates"])
        with jax.named_scope("tony.loss_head"):
            head = params["embedding"].T
        loss = chunked_causal_lm_loss(
            h, head, batch["tokens"], chunk_size=chunk,
            head_dtype=mcfg.lm_head_dtype,
            logits_scaling=mcfg.logits_scaling)
        return loss, layer_counters(sown.get("intermediates", {}))

    return model, loss_fn
