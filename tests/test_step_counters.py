"""A step's own counters: what the loss function returns as aux metrics
reaches the task's metrics file through ``telemetry.note_step_counters``,
unread on the step's path."""

import jax
import jax.numpy as jnp
import optax
import pytest

from tony_tpu import telemetry
from tony_tpu.models import MnistMLP
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)


@pytest.fixture(autouse=True)
def clean():
    telemetry.note_step_counters({})
    yield
    telemetry.note_step_counters({})


def test_counters_reach_the_metrics_stream():
    telemetry.note_step_counters({"moe_rows_routed": jnp.float32(12.0),
                                  "plain": 3, "a_vector": jnp.ones(3)})
    assert telemetry.step_counters() == {"moe_rows_routed": 12.0,
                                         "plain": 3.0}
    with telemetry.step():
        pass
    stats = telemetry.collect_device_stats()
    assert stats["step_counters"] == {"moe_rows_routed": 12.0, "plain": 3.0}
    telemetry.note_step_counters({})
    assert "step_counters" not in telemetry.collect_device_stats()


@pytest.mark.parametrize("with_aux", [True, False])
def test_train_step_hands_over_the_aux_metrics_alone(with_aux):
    mesh = build_mesh(MeshSpec(dp=8))
    model = MnistMLP()
    batch = {"x": jnp.ones((8, 784)), "y": jnp.zeros((8,), jnp.int32)}
    state, sh = init_sharded_state(model, batch["x"], optax.sgd(0.1), mesh)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, ({"rows": jnp.float32(batch["x"].shape[0])}
                      if with_aux else {})

    step = jit_train_step(loss_fn, mesh, sh, batch)
    state, metrics = step(state, batch, jax.random.key(0))
    assert set(metrics) == {"loss", "step"} | ({"rows"} if with_aux
                                               else set())
    assert telemetry.step_counters() == ({"rows": 8.0} if with_aux else {})


def test_an_expert_model_s_counters_reach_the_step_counters():
    """The five counters of a model with experts, the live share of its row
    buffers among them, through ``jit_train_step`` as a program hands them
    over (``moe_counters`` of what the layers sowed)."""
    from tony_tpu.models.moe import MoEConfig, moe_counters
    from tony_tpu.models.transformer import Transformer, causal_lm_loss

    mesh = build_mesh(MeshSpec(dp=8))
    model = Transformer(MoEConfig.tiny_moe(n_layers=1))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    state, sh = init_sharded_state(model, batch["tokens"], optax.sgd(0.1),
                                   mesh)

    def loss_fn(params, batch, rng):
        logits, sown = model.apply({"params": params}, batch["tokens"],
                                   mutable=["intermediates"])
        return (causal_lm_loss(logits, batch["tokens"]),
                moe_counters(sown["intermediates"]))

    step = jit_train_step(loss_fn, mesh, sh, batch)
    step(state, batch, jax.random.key(0))
    counters = telemetry.step_counters()
    assert set(counters) == {"moe_rows_routed", "moe_rows_unrouted_share",
                             "moe_expert_load_max_over_mean",
                             "moe_buffer_rows_live_share",
                             "moe_token_rows_gathered_share"}
    # Every expert is held, so the token side gathers every choice's rows.
    assert counters["moe_token_rows_gathered_share"] == 1.0
    # All four experts held, 16 tokens a device choosing 2: 32 rows and at
    # most a tile of 8 an expert in a buffer of (4 + 4) tiles.
    assert 32 / 64 <= counters["moe_buffer_rows_live_share"] <= 1.0
    assert counters["moe_rows_routed"] == 8 * 16 * 2


def test_the_token_side_s_rows_by_hand(monkeypatch):
    """``moe_token_rows_gathered_share`` on a routing small enough to count:
    experts 2 and 3 of eight held, three choices a token, chunks of eight
    tokens, segments of four. A chunk's ``_gather_sum`` gathers each slot's
    prefix in whole segments and all eight rows back, where a loop over the
    choices gathers 8 · 3."""
    from tony_tpu.models import moe

    monkeypatch.setattr(moe, "TOKEN_SEGMENT_ROWS", 4)
    spec = moe.ExpertSpec(n_experts=8, top_k=3, width=8, held=(2, 2),
                          tile_rows=8, chunk_tokens=8)
    # Five tokens with a pair here and one of them with two: slot 0 takes
    # two segments, slot 1 one. 8 + 4 + 8 back = 20 of 24.
    first = [[2, 0, 1], [0, 3, 1], [4, 5, 6], [3, 2, 7], [0, 1, 4],
             [5, 2, 0], [7, 6, 3], [1, 0, 5]]
    # No pair here: no slot runs a trip, the gather back alone. 8 of 24.
    second = [[0, 1, 4], [5, 6, 7]] * 4
    idx = jnp.array(first + second)
    got = moe.routing_counters(spec, idx, 2, 2)
    assert float(got["moe_token_rows_gathered_share"]) == pytest.approx(
        (20 + 8) / 2 / 24)
    assert float(got["moe_rows_routed"]) == 6
    # Held whole, the same routing is a loop over the choices: 1.
    whole = moe.routing_counters(
        moe.ExpertSpec(n_experts=8, top_k=3, width=8, chunk_tokens=8), idx,
        0, 8)
    assert float(whole["moe_token_rows_gathered_share"]) == 1.0
    # Two devices with four experts each, three slots: the first chunk's
    # prefixes are 7, 6, 2 and 6, 2, 1 (8 + 8 + 4 and 8 + 4 + 4 rows, and 8
    # back: 28 and 24), the second's 4, 4, 0 and 8, 4, 4 (16 and 24).
    split = moe.routing_counters(
        moe.ExpertSpec(n_experts=8, top_k=3, width=8, chunk_tokens=8), idx,
        0, 8, expert_groups=2)
    assert float(split["moe_token_rows_gathered_share"]) == pytest.approx(
        (28 + 24 + 16 + 24) / 4 / 24)
