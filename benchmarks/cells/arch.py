"""Where an architecture lives, and how a configuration finds it.

An architecture is a directory of three files, named by the ``model_type``
key that the configuration's published ``config.json`` carries:
``<base>/architectures/<model_type>/`` with ``<base>`` the cell table's first
path, else ``architectures/<model_type>/`` beside this file.

- ``counts.py`` imports no JAX (the run's parent loads it):
  ``total_params(cfg)``, ``model_flops_per_token(cfg, seq)`` and
  ``flash_calls(cfg, traffic)``, the list of ``(per-device shape, mask,
  layers)`` the model's attention needs a step (``counts.least_seconds``).
- ``reference.py`` is plain float32 ``jax.numpy`` and imports nothing of the
  program: ``leaf_specs(cfg)`` and ``loss_fn(cfg, params, tokens)``.
- ``program.py`` builds the system under test: ``build(cfg, traffic,
  control)`` gives the program's model and the ``loss_fn(params, batch,
  rng)`` that ``train.py`` hands to ``jit_train_step``.

A configuration that states no ``model_type``, or one whose directory is not
there, is an error with the paths in it, never a default. This module
imports no JAX either.
"""

from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
PARTS = ("counts", "reference", "program")


class NoArchitecture(Exception):
    """The configuration names no architecture that is there."""


def find(cfg: dict, config_path: str, base: str) -> str:
    """The directory of the architecture of the configuration ``cfg`` (read
    from ``config_path``), looked for under ``base`` first."""
    kind = cfg.get("model_type")
    if not isinstance(kind, str) or not kind or os.path.basename(kind) != kind:
        raise NoArchitecture(
            f"{config_path} states no model_type to find its architecture "
            f"by (found {kind!r})")
    tried = [os.path.join(os.path.abspath(root), "architectures", kind)
             for root in (base, HERE)]
    for folder in tried:
        if os.path.isdir(folder):
            return folder
    raise NoArchitecture(
        f"{config_path} states model_type {kind!r}, and there is no such "
        f"architecture: looked for {' and '.join(dict.fromkeys(tried))}")


@functools.cache
def load(folder: str, part: str):
    """The module ``<folder>/<part>.py``, loaded once a process."""
    if part not in PARTS:
        raise ValueError(f"an architecture has {PARTS}, not {part!r}")
    path = os.path.join(folder, part + ".py")
    if not os.path.isfile(path):
        raise NoArchitecture(f"the architecture lacks {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_arch_{os.path.basename(folder)}_{part}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
