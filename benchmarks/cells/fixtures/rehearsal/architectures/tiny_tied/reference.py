"""The rehearsal's second architecture in plain float32: the dense decoder's
layers, no head leaf, the logits taken against the embedding table. Imports
nothing of the program.
"""

from __future__ import annotations

import os

import arch
from reference import mean_over_rows, next_token_nll_sum

_dense = arch.load(os.path.join(arch.HERE, "architectures", "mistral"),
                   "reference")


def leaf_specs(cfg: dict) -> list:
    return [spec for spec in _dense.leaf_specs(cfg)
            if spec[0] != ("lm_head", "kernel")]


def loss_fn(cfg: dict, params: dict, tokens):
    """Mean next-token cross entropy of a batch of ids [B, S]."""
    return mean_over_rows(
        lambda row: next_token_nll_sum(
            _dense.hidden(cfg, params, row), params["embedding"].T, row),
        tokens)
