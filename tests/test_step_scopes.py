"""The program says which of its scopes every instruction of a compiled step
belongs to: ``profiling/scopes.py`` reads an ``op_name`` and a compiled
module's text, ``jit_train_step(...).lower(...).compile()`` leaves the map as
the span ``user.step_scopes``, the executor forwards it,
``cold_start_breakdown`` hands it on (and parts the boot's compiles by
stage), and the benchmark's readers (``benchmarks/cells/scope_times.py``)
join it to a reduced device trace."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from tony_tpu import telemetry, tracing
from tony_tpu.models.moe import ExpertSpec, MoEConfig
from tony_tpu.models.ssm import SSMSpec
from tony_tpu.models.transformer import (LayerSpec, Transformer,
                                         TransformerConfig, causal_lm_loss,
                                         chunked_causal_lm_loss)
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)
from tony_tpu.profiling.scopes import scope_of, step_scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")

GRAD = "jit(step)/tony.loss_and_grad/"
BACK = GRAD + "transpose(jvp(Transformer))/tony.loss_and_grad/"


# Real ``op_name``s, as the compiled steps of the cells and of the tiny
# models below carry them.
@pytest.mark.parametrize("op_name, want", [
    # the four passes, and what lies outside both of the step's scopes
    (GRAD + "jvp(Transformer)/layer_0/mlp/tony.mlp/up/dot_general",
     ("forward", "tony.mlp")),
    (BACK + "jvp(Transformer)/checkpoint/layer_1/mlp/tony.mlp/gate/"
     "dot_general", ("backward", "tony.mlp")),
    (BACK + "jvp(Transformer)/checkpoint/rematted_computation/layer_2/"
     "attn_norm/tony.norm/reduce_sum", ("recompute", "tony.norm")),
    ("jit(step)/tony.optimizer/add", ("optimizer", "-")),
    ("jit(step)/broadcast_in_dim", ("other", "-")),
    ("state.params[\\'layer_0\\'][\\'mlp\\'][\\'up\\'][\\'kernel\\']",
     ("other", "-")),
    ("reduce_window_sum", ("other", "-")),
    # nested scopes: the innermost layer's scope is the operation's
    (GRAD + "jvp(Transformer)/layer_0/attn/tony.attn.core/tony.attn.rope/"
     "mul", ("forward", "tony.attn.rope")),
    (GRAD + "jvp(Transformer)/layer_1/moe/tony.moe.dispatch/while/body/"
     "tony.moe.experts/mul", ("forward", "tony.moe.experts")),
    # a scope right under a transform is wrapped by it
    (GRAD + "jvp(tony.loss_head)/while/body/closed_call/dot_general",
     ("forward", "tony.loss_head")),
    (GRAD + "transpose(jvp(tony.loss_head))/while/body/closed_call/"
     "checkpoint/dot_general", ("backward", "tony.loss_head")),
    # a scope inside jax.checkpoint, recomputed and transposed
    (GRAD + "transpose(jvp(tony.loss_head))/while/body/closed_call/"
     "checkpoint/rematted_computation/exp", ("recompute", "tony.loss_head")),
    ("jit(step)/tony.loss_and_grad/transpose(jvp(tony.loss_and_grad))/jvp()"
     "/checkpoint/rematted_computation/tony.norm/reduce_sum",
     ("recompute", "tony.norm")),
    # none: under the gradient and under no layer's scope
    (GRAD + "jvp(Transformer)/layer_0/add", ("forward", "-")),
    (BACK + "jvp(Transformer)/remat2", ("backward", "-")),
    # the pass and the scope are read apart
    ("jit(step)/tony.optimizer/tony.mlp/mul", ("optimizer", "tony.mlp")),
])
def test_scope_of_reads_pass_and_scope(op_name, want):
    assert scope_of(op_name) == want


HLO = """\
HloModule jit_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/tony.optimizer/add"}
}

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.5 = f32[8,8]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/tony.loss_and_grad/transpose(jvp(Transformer))/layer_0/mlp/tony.mlp/up/dot_general"}
  %sub.7 = f32[8,8]{1,0} subtract(%p0, %dot.5), metadata={op_name="jit(step)/tony.optimizer/sub"}
  ROOT %tuple.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%dot.5, %sub.7)
}

%body.3 (param.1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %param.1 = (s32[]{:T(128)}, f32[8,8]{1,0:T(8,128)}) parameter(0)
  %get-tuple-element.4 = f32[8,8]{1,0:T(8,128)} get-tuple-element(%param.1), index=1
  %fusion.30 = f32[8,8]{1,0} fusion(%get-tuple-element.4), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/tony.loss_and_grad/jvp(tony.loss_head)/while/body/closed_call/exp"}
  %copy.31 = f32[8,8]{0,1} copy(%fusion.30)
  ROOT %tuple.5 = (s32[], f32[8,8]) tuple(%get-tuple-element.4, %copy.31)
}

ENTRY %main.9 (state: f32[8,8], batch: f32[8,8]) -> f32[8,8] {
  %state = f32[8,8]{1,0} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %batch = f32[8,8]{1,0} parameter(1), metadata={op_name="batch[\\'x\\']"}
  %copy.1 = f32[8,8]{0,1} copy(%state)
  %fusion.10 = f32[8,8]{1,0} fusion(%batch, %copy.1), kind=kLoop, calls=%fused_computation.0, metadata={op_name="jit(step)/tony.loss_and_grad/jvp(Transformer)/layer_0/mlp/tony.mlp/up/dot_general"}
  %bitcast.11 = f32[64]{0} bitcast(%fusion.10)
  %copy.12 = f32[64]{0} copy(%bitcast.11)
  %reduce.13 = f32[] reduce(%copy.12, %state), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/tony.loss_and_grad/jvp(Transformer)/reduce_sum"}
  %cumsum.14 = f32[64]{0} fusion(%copy.12), kind=kLoop, calls=%fused_computation.3, metadata={op_name="reduce_window_sum"}
  %while.15 = (s32[], f32[8,8]) while(%tuple.0), condition=%cond.2, body=%body.3, metadata={op_name="jit(step)/tony.loss_and_grad/jvp(tony.loss_head)/while"}
  %fusion.16 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%fusion.10, %state), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/tony.loss_and_grad/transpose(jvp(Transformer))/layer_0/mlp/tony.mlp/up/dot_general"}
  %add.17 = f32[8,8]{1,0} add(%fusion.10, %while.15)
  ROOT %fusion.18 = f32[8,8]{1,0} fusion(%state), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/tony.optimizer/mul"}
}
"""


def test_step_scopes_reads_a_compiled_module_s_text():
    """Every computation but the fusions' bodies and the reducers; an
    instruction that its own ``op_name`` places nowhere takes the key of
    its one operand that has a place; parameters, tuples, their elements
    and bitcasts are kept out of the record and still hand their key on."""
    record = step_scopes(HLO)
    assert record["module"] == "jit_step"
    assert record["scopes"] == {
        "other/-": ["copy.1", "add.17"],    # no place, or two operands' places
        "forward/tony.mlp": ["fusion.10", "copy.12", "cumsum.14"],
        "forward/-": ["reduce.13"],
        "forward/tony.loss_head": ["fusion.30", "copy.31", "while.15"],
        "backward/tony.mlp": ["fusion.16"],
        "optimizer/-": ["fusion.18"]}
    assert record["instructions"] == 11
    # copy.12 through a bitcast, cumsum.14 past its own op_name, copy.31
    assert record["inherited"] == 3
    assert record["unscoped"] == 1
    # a gradient's product that holds its leaf's update is the backward's
    assert record["with_update"] == ["fusion.16"]


def dense(remat):
    cfg = TransformerConfig.tiny(n_layers=2, remat=remat)
    model = Transformer(cfg)

    def loss_fn(params, batch, rng):
        hidden = model.apply({"params": params}, batch["tokens"],
                             return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], batch["tokens"],
            chunk_size=16), {}
    return model, loss_fn


def whole_logits(cfg):
    model = Transformer(cfg)

    def loss_fn(params, batch, rng):
        return causal_lm_loss(model.apply({"params": params},
                                          batch["tokens"]),
                              batch["tokens"]), {}
    return model, loss_fn


def sparse():
    experts = ExpertSpec(n_experts=4, top_k=2, width=32, tile_rows=8,
                         shared_width=32)
    return whole_logits(MoEConfig.tiny_moe(
        n_layers=1, layers=(LayerSpec(experts=experts),)))


def state_space():
    mixer = SSMSpec(n_heads=4, head_dim=8, n_groups=2, state=16, chunk=16)
    return whole_logits(TransformerConfig.tiny(
        n_layers=2, remat=True,
        layers=(LayerSpec(mixer=mixer, feed_forward=False),
                LayerSpec(rope=False, gate=True, feed_forward=False))))


EVERYWHERE = {"tony.embed", "tony.norm", "tony.loss_head",
              "tony.attn.proj", "tony.attn.core"}
MOE = {"tony.moe.route", "tony.moe.dispatch", "tony.moe.experts",
       "tony.moe.combine", "tony.moe.shared"}
SSM = {"tony.ssm.in_proj", "tony.ssm.conv", "tony.ssm.scan",
       "tony.ssm.gate_norm", "tony.ssm.out_proj"}
# (the model; its layers' scopes, each of which has a gradient and so shows
# in the backward pass; those the forward pass shows as well, where XLA fuses
# a model this small's RoPE into the kernel's operands and the combine into
# the residual; those the blocks' remat runs again)
STEPS = {
    "dense": (lambda: dense(remat=True),
              EVERYWHERE | {"tony.attn.rope", "tony.mlp"},
              EVERYWHERE | {"tony.mlp"},
              {"tony.attn.proj", "tony.attn.core", "tony.attn.rope",
               "tony.mlp", "tony.norm", "tony.loss_head"}),
    "sparse": (sparse, EVERYWHERE | MOE | {"tony.attn.rope"},
               EVERYWHERE | MOE - {"tony.moe.combine"}, set()),
    "state-space": (state_space, EVERYWHERE | SSM | {"tony.attn.gate"},
                    EVERYWHERE | SSM | {"tony.attn.gate"},
                    SSM - {"tony.ssm.out_proj"} | {"tony.attn.gate"}),
}


@pytest.fixture
def spans():
    telemetry._reset_span_state()
    yield lambda: [s for s in telemetry.span_stats().get("spans", [])
                   if s["name"] == "user.step_scopes"]
    telemetry._reset_span_state()


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_a_step_compiled_ahead_of_time_records_its_scopes(kind, spans):
    build, layers, forward, recomputed = STEPS[kind]
    model, loss_fn = build()
    mesh = build_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32)}
    state, sh = init_sharded_state(model, batch["tokens"],
                                   optax.adamw(1e-3), mesh)
    step = jit_train_step(loss_fn, mesh, sh, batch, donate=False)
    rng = jax.random.key(0)

    # A plain call of the step records nothing.
    step(state, batch, rng)
    assert spans() == []

    compiled = step.lower(state, batch, rng).compile()
    (span,) = spans()
    args = span["args"]
    assert args["fun_name"] == "step" and args["module"] == "jit_step"
    assert span["end"] >= span["start"]
    # The record is the compiled text's, name lists as strings.
    again = step_scopes(compiled.as_text())
    assert args["scopes"] == {key: " ".join(names)
                              for key, names in again["scopes"].items()}
    assert args["instructions"] == again["instructions"] \
        == sum(len(v.split()) for v in args["scopes"].values())
    # Every scope in the pass it belongs to.
    found = {which: set() for which in ("forward", "backward", "recompute",
                                        "optimizer", "other")}
    for key in args["scopes"]:
        which, _, scope = key.partition("/")
        found[which].add(scope)
    assert layers <= found["backward"], layers - found["backward"]
    assert forward <= found["forward"], forward - found["forward"]
    assert recomputed <= found["recompute"], recomputed - found["recompute"]
    assert found["optimizer"] == {"-"}
    assert args["unscoped"] < 0.05 * args["instructions"], args
    # The compiled step is jax's own, and runs.
    compiled.memory_analysis()
    new_state, metrics = compiled(state, batch, rng)
    assert jnp.isfinite(metrics["loss"])
    # A second compile by the door is a second record; the cap that drops
    # the compile spans of a storm does not drop it.
    for _ in range(telemetry.SPAN_CAP):
        telemetry.record_span("user.compile", 1.0, 2.0, stage="trace")
    dropped = telemetry.span_stats()["spans_dropped"]
    assert dropped > 0
    step.lower(state, batch, rng).compile()
    assert len(spans()) == 2
    assert telemetry.span_stats()["spans_kept"] == telemetry.SPAN_CAP + 1


def test_the_door_compiles_with_the_metadata_in_the_cache_s_key():
    """jax keys its persistent cache without a module's metadata: the door's
    compile asks for a key with it, and leaves the option as it found it."""
    from tony_tpu.parallel.train import _metadata_in_the_cache_key

    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    with _metadata_in_the_cache_key():
        assert getattr(jax.config, name) is True
    assert getattr(jax.config, name) == before


# ---------------------------------------------------------------------------
# Executor → span log → cold_start_breakdown, on a recorded span log
# ---------------------------------------------------------------------------
RECORDED = os.path.join(REPO, "tests", "fixtures", "cell_spans",
                        "trace.spans.jsonl")


@pytest.fixture(scope="module")
def recorded():
    """The span log of a traced run of ``m7b.seq2k`` on the chip (PR 36)."""
    return tracing.load_records(RECORDED)


def test_cold_start_breakdown_hands_the_record_on(recorded):
    bd = tracing.cold_start_breakdown(recorded)
    record = bd["step_scopes"]
    assert record["fun_name"] == "step" and record["module"] == "jit_step"
    names = [n for v in record["scopes"].values() for n in v.split()]
    assert len(names) == len(set(names)) == record["instructions"]
    assert {"forward/tony.mlp", "backward/tony.mlp", "recompute/tony.mlp",
            "optimizer/-", "forward/tony.loss_head"} <= set(record["scopes"])
    # The span lies outside the boot window: nothing else moves.
    without = tracing.cold_start_breakdown(
        [r for r in recorded if r.get("name") != "user.step_scopes"])
    assert "step_scopes" not in without
    for key in ("total_s", "task", "phases", "span_durations", "user_boot",
                "user_boot_compile"):
        assert bd[key] == without[key], key
    # The newest record of the anchor task is the one handed on.
    older = [dict(r, ts_us=r["ts_us"] - 10, args=dict(r["args"],
                                                      module="older"))
             for r in recorded if r.get("name") == "user.step_scopes"]
    assert tracing.cold_start_breakdown(recorded + older)[
        "step_scopes"]["module"] == "jit_step"


def test_the_boot_s_compiles_part_by_stage(recorded):
    bd = tracing.cold_start_breakdown(recorded)
    stages = bd["user_boot_compile"]
    assert list(stages) == ["trace", "lower", "backend"]
    assert all(v > 0 for v in stages.values())
    assert sum(stages.values()) == pytest.approx(
        bd["user_boot"]["user.compile"], abs=2e-4)
    assert sum(bd["user_boot"].values()) == pytest.approx(
        bd["phases"]["user_boot"], abs=1e-3)


def test_executor_forwards_the_record_once(tmp_path, monkeypatch):
    from test_tracing import _executor, _user_span, _write_user_metrics

    ex = _executor(tmp_path, monkeypatch, trace_id="feedfacefeedface")
    record = {"fun_name": "step", "module": "jit_step", "instructions": 3,
              "inherited": 1, "unscoped": 0, "with_update": "fusion.3",
              "scopes": {"forward/tony.mlp": "fusion.1 copy.2",
                         "backward/tony.mlp": "fusion.3"}}
    listed = [_user_span(1, "user.pre_import", 100.0, 102.5),
              _user_span(2, "user.step_scopes", 200.0, 200.4, **record)]
    _write_user_metrics(ex, 4242, listed)
    ex._progress_beacon()
    ex._progress_beacon()
    _write_user_metrics(ex, 4242, listed + [
        _user_span(3, "user.compile", 300.0, 301.0, stage="backend")])
    ex._progress_beacon()
    got = [r for r in ex.tracer.drain() if r["name"] == "user.step_scopes"]
    assert len(got) == 1
    assert got[0]["args"] == record and got[0]["dur_us"] == 400_000
    assert got[0]["parent"] == ex._run_span.span_id


# ---------------------------------------------------------------------------
# The benchmark's readers, over a recorded reduced trace and its map
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def readers():
    sys.path.insert(0, CELLS)
    try:
        import run as harness
        yield {m.NAME: m for m in harness.load_metrics()}
    finally:
        sys.path.remove(CELLS)


@pytest.fixture(scope="module")
def scoped():
    """What ``run.drive`` hands the readers: the fixture's reduced trace as
    the worker's, its map as ``cold_start_breakdown`` hands it on."""
    with open(os.path.join(CELLS, "fixtures", "scoped_trace.json"),
              encoding="utf-8") as f:
        fixture = json.load(f)
    return {"worker": {"trace": fixture["trace"]},
            "spans": {"step_scopes": fixture["step_scopes"],
                      "user_boot_compile": fixture["user_boot_compile"]}}


# The fixture's ops by hand, seconds over its busy_s of 0.100 s.
HAND = {
    "backward_share_of_busy": 16.0 + 6.0 + 4.0 + 2.0 + 1.0 + 3.0 + 6.0,
    "recompute_share_of_busy": 5.0 + 2.0 + 1.5,
    "optimizer_share_of_busy": 4.0,
    "loss_head_share_of_busy": 6.0 + 6.0 + 2.0,
    "attn_proj_share_of_busy": 3.0 + 4.0,
    "mlp_share_of_busy": 10.0 + 16.0 + 5.0,
    "rope_share_of_busy": 1.0 + 1.5,
    "moe_route_share_of_busy": 1.0,
    "moe_dispatch_share_of_busy": 2.0 + 2.0,
    "moe_combine_share_of_busy": 0.5 + 0.25,    # the feed's fusion.9 too
    "moe_experts_xla_share_of_busy": 1.5,       # moe_gmm.7's 3.0 left out
    "ssm_proj_share_of_busy": 2.5 + 1.0,
    "ssm_conv_share_of_busy": 0.75,
    "ssm_gate_norm_share_of_busy": 1.25 + 0.5,  # the inherited copy's 0.5
    "ssm_scan_xla_share_of_busy": 0.5,          # ssd_fwd.2's 2.0 left out
    "unscoped_share_of_busy": 3.0 + 0.25 + 0.75,
    "boot_compile_trace_s": 9.5,
    "boot_compile_lower_s": 8.25,
    "boot_compile_backend_s": 2.5,
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_a_reader_gives_the_hand_summed_share(readers, scoped, name):
    assert readers[name].read(scoped) == pytest.approx(HAND[name])
    # Without a map (the parent's program), without a trace: nothing.
    no_map = dict(scoped, spans={"phases": {}, "user_boot": {
        "user.compile": 20.25}})
    assert readers[name].read(no_map) is None
    if not name.startswith("boot_compile_"):
        no_trace = dict(scoped, worker={"trace": {}})
        assert readers[name].read(no_trace) is None


def test_the_passes_account_for_all_the_map_holds(readers, scoped):
    sys.path.insert(0, CELLS)
    try:
        import scope_times
    finally:
        sys.path.remove(CELLS)
    rows = scope_times.table(scoped)
    total = sum(row[1] for row in rows.values())
    assert total == pytest.approx(scoped["worker"]["trace"]["busy_s"])
    by_pass = sum(scope_times.share(scoped, passes=(which,))
                  for which in ("forward", "backward", "recompute",
                                "optimizer", "other"))
    assert by_pass + 100 * rows["unmapped/-"][1] / total == pytest.approx(100)
    # Mosaic calls in, then out.
    assert scope_times.share(scoped, scopes=("tony.moe.experts",)) \
        == pytest.approx(4.5)
    assert scope_times.with_update(scoped) == (3.0, pytest.approx(0.016))
    # fusion.9 shows under two result types: the feed's is the shorter.
    assert scope_times.met(scoped) == (1, pytest.approx(0.00025))
    note = readers["unscoped_share_of_busy"].note(scoped)
    assert "backward/tony.mlp: 5.333 ms a step, 16.00 %, 1 calls" in note
    assert "held the names of 99.25 % of the traced device time" in note
    assert "1 names met under two result types" in note
    assert "16.00 % of busy in 1 fusions a step" in \
        readers["optimizer_share_of_busy"].note(scoped)


def test_every_scope_reader_answers_to_its_table_entry(readers):
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        entries = {e["name"]: e for e in json.load(f)["per_layer"]}
    every = ["m7b.seq2k", "m7b.seq32k", "st21b.seq16k", "lagS.seq8k",
             "nem30b.seq8k", "kimiL.seq32k"]
    for name in HAND:
        entry, reader = entries[name], readers[name]
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
        assert entry["better"] == "lower"
        assert set(entry["workloads"]) <= set(every)
    # one block in this order, appended after the entries there were (a
    # later cell's entries may follow it)
    names = list(entries)
    first = names.index("backward_share_of_busy")
    assert names[first:first + len(HAND)] == [
        "backward_share_of_busy", "recompute_share_of_busy",
        "optimizer_share_of_busy", "loss_head_share_of_busy",
        "attn_proj_share_of_busy", "mlp_share_of_busy",
        "rope_share_of_busy", "moe_route_share_of_busy",
        "moe_dispatch_share_of_busy", "moe_combine_share_of_busy",
        "moe_experts_xla_share_of_busy", "ssm_proj_share_of_busy",
        "ssm_conv_share_of_busy", "ssm_gate_norm_share_of_busy",
        "ssm_scan_xla_share_of_busy", "unscoped_share_of_busy",
        "boot_compile_trace_s", "boot_compile_lower_s",
        "boot_compile_backend_s"]
    for name in ("mlp_share_of_busy", "rope_share_of_busy",
                 "ssm_conv_share_of_busy"):
        assert entries[name]["workloads"] != every
