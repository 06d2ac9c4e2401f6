"""Profiler-contract script: wraps a tiny jax step in a trace window.
On the chief (TONY_PROFILE_DIR set) a trace must land there; on other
tasks the window must be a clean no-op."""
import os
import sys

import jax

import jax.numpy as jnp

from tony_tpu import profiler

with profiler.trace_window("step0") as dest:
    x = jnp.ones((64, 64))
    y = jax.jit(lambda a: a @ a)(x)
    jax.block_until_ready(y)

is_chief = os.environ.get("TONY_IS_CHIEF", "false") == "true"
if is_chief:
    if dest is None:
        print("chief had no TONY_PROFILE_DIR", file=sys.stderr)
        sys.exit(2)
    n = sum(len(fs) for _, _, fs in os.walk(dest))
    if n == 0:
        print(f"no trace files under {dest}", file=sys.stderr)
        sys.exit(3)
elif dest is not None:
    print("non-chief unexpectedly profiling", file=sys.stderr)
    sys.exit(4)
sys.exit(0)
