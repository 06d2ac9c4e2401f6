"""Sharded training-state construction and jit'd train steps.

Everything here compiles to ONE XLA program per step: forward, backward,
optimizer update, and every collective (gradient psum over dp/fsdp, weight
all_gathers for FSDP, activation all_reduces for TP) — traced once, fused by
XLA, no Python in the hot loop. This replaces the reference's entire "data
plane is someone else's problem" stance (SURVEY.md §2.4).
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import telemetry
from tony_tpu.parallel.mesh import tree_batch_shardings
from tony_tpu.parallel.sharding import DEFAULT_RULES, param_shardings
from tony_tpu.profiling import scopes

log = logging.getLogger(__name__)


@struct.dataclass
class TrainState:
    """Minimal train state (flax train_state analogue, kept dependency-light
    so checkpointing sees a plain pytree)."""
    step: jax.Array
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads: Any) -> "TrainState":
        # A barrier on each leaf's gradient keeps its update out of the
        # product that makes the gradient. Fused in as the product's
        # epilogue, the update's three f32 states in and out shrank the
        # product's tiles: on m7b.seq2k fifteen such fusions took 195.5 ms
        # a step, the same products and updates apart 145 ms (PERF.md 6,
        # PR 37). The barrier leaves no instruction in the compiled step.
        grads = jax.tree.map(jax.lax.optimization_barrier, grads)
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(step=self.step + 1,
                            params=optax.apply_updates(self.params, updates),
                            opt_state=new_opt)


@telemetry.span("user.init_state")     # host time: trace, compile, dispatch
def init_sharded_state(
    model: nn.Module,
    sample_batch: Any,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rng: Optional[jax.Array] = None,
    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES,
) -> Tuple[TrainState, TrainState]:
    """Initialize params *already sharded*: eval_shape under logical rules →
    compute NamedShardings → jit init with out_shardings so no device ever
    materializes the full model (essential at 8B+ params).

    Returns ``(state, state_shardings)``; the latter mirrors the state tree
    with a NamedSharding at every leaf (optimizer-slot shardings come from
    XLA's sharding propagation through ``tx.init`` on sharded params).
    """
    rng = rng if rng is not None else jax.random.key(0)

    def boxed_init(rng):
        # Params stay wrapped in LogicallyPartitioned metadata boxes here, so
        # tx.init's tree_maps produce *boxed optimizer slots* too — the slots
        # inherit each param's logical axes and therefore its sharding.
        params = model.init(rng, sample_batch)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), tx=tx)

    with nn.logical_axis_rules(list(rules)):
        abstract = jax.eval_shape(boxed_init, rng)
    state_sh = param_shardings(mesh, abstract, rules)

    def init_fn(rng):
        return nn.meta.unbox(boxed_init(rng))

    with jax.set_mesh(mesh), nn.logical_axis_rules(list(rules)):
        state = jax.jit(init_fn, out_shardings=state_sh)(rng)
    return state, state_sh


@contextlib.contextmanager
def _metadata_in_the_cache_key():
    """jax keys its persistent compile cache by a module WITHOUT its
    metadata, so the executable it serves may carry the ``op_name``s of
    whichever version of the program compiled it first: good enough to run,
    wrong to read scopes from. Inside, the metadata is part of the key."""
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, before)


class LoweredStep:
    """jax's ``Lowered`` of a step (every attribute is that object's), whose
    ``compile()`` also leaves the compiled module's map from instruction to
    scope (``profiling/scopes.py step_scopes``) as one closed span of the
    job's span log, ``user.step_scopes``: what joins a device trace's
    instruction names to the model's layers and the step's passes. A step
    that is only called records nothing: the map costs a pass over the
    compiled text, and whoever wants it compiles the step by this door.

    The executable the call path holds may have come from the persistent
    cache with another version's metadata (``_metadata_in_the_cache_key``),
    and jax hands a ``Lowered`` that executable back without asking the
    compiler. So this compile names a compiler option, at its default, which
    sends it to the compiler's cache under a key with the metadata in: the
    first such compile of a program's version is a whole one, every later
    one a fetch; the instructions are the call path's, name for name."""

    #: at its default: changes no compile, only jax's choice to make one
    KEEP_METADATA = {"xla_dump_disable_metadata": False}

    def __init__(self, lowered, fun_name: str):
        self._lowered, self._fun_name = lowered, fun_name

    def __getattr__(self, name: str):
        return getattr(self._lowered, name)

    def compile(self, compiler_options: Optional[dict] = None, **kwargs):
        with _metadata_in_the_cache_key():
            compiled = self._lowered.compile(
                {**self.KEEP_METADATA, **(compiler_options or {})}, **kwargs)
        start = time.time()
        try:
            record = scopes.step_scopes(compiled.as_text())
        except Exception:  # noqa: BLE001 — the map is diagnostics only
            log.exception("no step_scopes record for %s", self._fun_name)
            return compiled
        # Name lists as one string each: a span's args are a line of JSON.
        record["scopes"] = {key: " ".join(names)
                            for key, names in record["scopes"].items()}
        record["with_update"] = " ".join(record["with_update"])
        telemetry.record_span("user.step_scopes", start, time.time(),
                              keep=True, fun_name=self._fun_name, **record)
        return compiled


def jit_train_step(
    loss_fn: Callable[[Any, Any, jax.Array], Tuple[jax.Array, dict]],
    mesh: Mesh,
    state_shardings: TrainState,
    sample_batch: Any,
    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES,
    donate: bool = True,
):
    """Build the canonical step function. ``loss_fn(params, batch, rng)``
    must be pure/jit-safe and return ``(loss, aux_metrics)``.

    Returns ``step(state, batch, rng) -> (state, metrics)`` compiled with
    explicit in/out shardings: batch sharded over (dp, fsdp) on dim 0, state
    per ``state_shardings`` — XLA derives every collective from there. The
    mesh is bound (``jax.set_mesh``) around every call, which is what lets
    the Pallas kernels in the model run per shard; ``step.lower(state,
    batch, rng)`` lowers under the same binding, for ahead-of-time compiles
    (a ``LoweredStep``: its ``compile()`` records the step's scopes).
    """
    def step(state: TrainState, batch: Any, rng: jax.Array):
        # Two scopes, so that every fusion's op_name metadata says which
        # part of the step it belongs to: jax stamps jvp(...) and
        # transpose(jvp(...)) inside the first, which makes forward,
        # backward and optimizer three disjoint prefixes in a device trace.
        with nn.logical_axis_rules(list(rules)), \
                jax.named_scope("tony.loss_and_grad"):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch, rng)
        with jax.named_scope("tony.optimizer"):
            new_state = state.apply_gradients(grads)
        metrics = {"loss": loss, "step": new_state.step, **aux}
        return new_state, metrics

    # Scalar (0-d) leaves can't carry a batch dim — replicate those.
    # Shardings are memoized per (mesh, ndim) in mesh.py, so a large
    # batch pytree no longer pays one NamedSharding construction per
    # leaf per builder call on the submit path.
    batch_sh = tree_batch_shardings(mesh, sample_batch)
    jitted = jax.jit(
        step,
        in_shardings=(state_shardings, batch_sh, NamedSharding(mesh, P())),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else ())

    def wrapped(state, batch, rng):
        with jax.set_mesh(mesh):
            state, metrics = jitted(state, batch, rng)
        # The loss function's own aux metrics (a model's counters) go to
        # the task's metrics file, unread here: the step is not waited for.
        aux = {k: v for k, v in metrics.items() if k not in ("loss", "step")}
        if aux:
            telemetry.note_step_counters(aux)
        return state, metrics

    def lower(state, batch, rng):
        """``jax.jit(...).lower`` under the same bound mesh — accepts
        ``ShapeDtypeStruct``s, so the step can be compiled ahead of time
        for devices this host does not have (a TPU topology)."""
        with jax.set_mesh(mesh):
            return LoweredStep(jitted.lower(state, batch, rng),
                               step.__name__)

    wrapped.lower = lower
    return wrapped
