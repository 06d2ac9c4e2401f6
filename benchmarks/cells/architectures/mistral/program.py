"""The dense decoder as the program under test builds it: ``tony_tpu``'s
``Transformer`` with the flash kernels, every block recomputed in the backward
pass, and the loss taken in chunks of the sequence over the untied head.
"""

from __future__ import annotations


def model_config(cfg: dict, traffic: dict, control: str):
    from tony_tpu.models import TransformerConfig

    mcfg = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=max(traffic["seq"], cfg["max_position_embeddings"]),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        attn_impl="flash", remat=True, remat_policy=None,
        tie_embeddings=cfg["tie_word_embeddings"],
        matmul_dtype=control or None)
    head_dim = cfg.get("head_dim") or mcfg.dim // mcfg.n_heads
    if head_dim != mcfg.dim // mcfg.n_heads:
        raise ValueError("the program derives head_dim as dim / heads; "
                         "this configuration states another")
    return mcfg


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    """(the model ``init_sharded_state`` takes, the ``loss_fn(params, batch,
    rng)`` that ``jit_train_step`` takes). ``control`` is the program's own
    lower-precision matmul path, "" in every benchmark run."""
    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import chunked_causal_lm_loss

    mcfg = model_config(cfg, traffic, control)
    model = Transformer(mcfg)
    chunk = traffic["loss_chunk"]

    def loss_fn(params, batch, rng):
        h = model.apply({"params": params}, batch["tokens"],
                        return_hidden=True)
        return chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=chunk, head_dtype=mcfg.lm_head_dtype), {}

    return model, loss_fn
