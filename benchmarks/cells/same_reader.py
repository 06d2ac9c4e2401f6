"""A metric's reader under a second name.

A per-layer metric of ``BENCHMARK.json`` lists the cells it is read in, and a
PR that only adds cannot extend a list that is there. A new cell that wants
the reader of a metric there (the whole step's share of peak, the step's
median and tail, the compiled bytes, the idle and the input's share, the
parts of set-up) brings a file of ``metrics/`` under
a name of its own whose ``read`` is that reader's: ``of("mfu").read``.
"""

from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.cache
def of(name: str):
    """The module ``metrics/<name>.py``, loaded once a process."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name, os.path.join(HERE, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
