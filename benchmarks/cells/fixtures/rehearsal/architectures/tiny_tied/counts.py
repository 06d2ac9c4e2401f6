"""Counts of the rehearsal's second architecture: the dense decoder with the
embedding table as its head (``tie_word_embeddings``). A fixture for the CPU:
it shows that an architecture comes as new files. No JAX.
"""

from __future__ import annotations

import os

import arch

_dense = arch.load(os.path.join(arch.HERE, "architectures", "mistral"),
                   "counts")


def total_params(cfg: dict) -> int:
    """The dense decoder's, less the head [d,V] that is no leaf here."""
    return _dense.total_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """The dense decoder's: the table [V,d] is a lookup going in and
    multiplies every token coming out, as the head [d,V] did."""
    return _dense.model_flops_per_token(cfg, seq)


flash_calls = _dense.flash_calls
