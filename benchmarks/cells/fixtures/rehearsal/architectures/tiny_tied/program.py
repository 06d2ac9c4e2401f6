"""The rehearsal's second architecture as the program builds it: the
program's own ``Transformer(tie_embeddings=True)``, the chunked loss taken
over the transposed embedding table.
"""

from __future__ import annotations

import os

import arch

_dense = arch.load(os.path.join(arch.HERE, "architectures", "mistral"),
                   "program")


def build(cfg: dict, traffic: dict, control: str) -> tuple:
    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import chunked_causal_lm_loss

    mcfg = _dense.model_config(cfg, traffic, control)
    if not mcfg.tie_embeddings:
        raise ValueError("this architecture ties the head to the embedding; "
                         "the configuration says tie_word_embeddings false")
    model = Transformer(mcfg)
    chunk = traffic["loss_chunk"]

    def loss_fn(params, batch, rng):
        h = model.apply({"params": params}, batch["tokens"],
                        return_hidden=True)
        return chunked_causal_lm_loss(
            h, params["embedding"].T, batch["tokens"], chunk_size=chunk,
            head_dtype=mcfg.lm_head_dtype), {}

    return model, loss_fn
