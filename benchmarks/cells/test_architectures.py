"""Tests of the seam between the harness and an architecture. Not collected
by ``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_architectures.py -q

- a configuration finds its architecture by ``model_type``: under the table's
  own path first, beside the harness otherwise; one that states none, or one
  that is not there, fails with the paths named;
- an architecture's ``counts.py`` loads without JAX (the run's parent loads
  it), and the fixture architecture's counts equal a hand count;
- a kernel's operations under a window against a hand count, and the split
  of the calls a trace shows over more than one kind of layer;
- the harness's own files name no architecture.

That the fixture architecture's cell runs, is ``correct``, and is not under
the control and a planted fault, is in ``test_cells.py`` beside the dense
decoder's.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import arch  # noqa: E402
import counts  # noqa: E402

REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal")
DENSE = os.path.join(HERE, "architectures", "mistral")
TIED = os.path.join(REHEARSAL, "architectures", "tiny_tied")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def test_a_configuration_finds_its_architecture():
    tiny = os.path.join(REHEARSAL, "configs", "tiny.json")
    tied = os.path.join(REHEARSAL, "configs", "tiny_tied.json")
    # the table's own path first; beside the harness where it has none
    assert arch.find(load(tied), tied, REHEARSAL) == TIED
    assert arch.find(load(tiny), tiny, REHEARSAL) == DENSE
    for name in os.listdir(os.path.join(HERE, "configs")):
        path = os.path.join(HERE, "configs", name)
        assert arch.find(load(path), path, HERE) == DENSE
    for folder in (DENSE, TIED):
        for part in arch.PARTS:
            assert os.path.isfile(os.path.join(folder, part + ".py"))


@pytest.mark.parametrize("model_type, says", [
    (None, "states no model_type"), ("", "states no model_type"),
    ("../mistral", "states no model_type"),
    ("no_such", "there is no such architecture")],
    ids=["absent", "empty", "a_path", "unknown"])
def test_no_architecture_is_an_error_with_the_path_in_it(model_type, says):
    cfg = {} if model_type is None else {"model_type": model_type}
    with pytest.raises(arch.NoArchitecture) as e:
        arch.find(cfg, "/somewhere/configs/odd.json", REHEARSAL)
    assert says in str(e.value)
    assert "/somewhere/configs/odd.json" in str(e.value)
    if model_type == "no_such":     # both places it looked in
        assert os.path.join(REHEARSAL, "architectures", "no_such") in str(
            e.value)
        assert os.path.join(HERE, "architectures", "no_such") in str(e.value)
    with pytest.raises(arch.NoArchitecture, match="lacks"):
        arch.load(os.path.join(HERE, "metrics"), "counts")
    with pytest.raises(ValueError):
        arch.load(DENSE, "kernels")


@pytest.mark.parametrize("folder", [DENSE, TIED], ids=["mistral", "tiny_tied"])
def test_the_parent_loads_the_counts_without_jax(folder):
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import arch, run; "
            "c = arch.load(sys.argv[2], 'counts'); "
            "assert callable(c.total_params) and callable(c.flash_calls) "
            "and callable(c.model_flops_per_token); "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'tony_tpu'))]; assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code, HERE, folder],
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_the_tied_fixture_counts_against_a_hand_count():
    cfg = load(os.path.join(REHEARSAL, "configs", "tiny_tied.json"))
    traffic = load(os.path.join(REHEARSAL, "traffic", "b4.json"))
    tied, dense = arch.load(TIED, "counts"), arch.load(DENSE, "counts")
    # one layer: wq 128*128 + wk, wv 2 * 128*64 + wo 128*128 + gate, up, down
    # 3 * 128*256 = 147,456; the table 512*128 = 65,536 is embedding and head
    layer, table = 16384 + 2 * 8192 + 16384 + 3 * 32768, 65536
    assert layer == 147456
    assert tied.total_params(cfg) == table + 2 * layer + 5 * 128 == 361088
    assert dense.total_params(cfg) == 361088 + table     # a head of its own
    # forward per token: 2 * (2 layers + the table as head) + 2 * 2*256*128
    fwd = 2 * (2 * layer + table) + 2 * 2 * 256 * 128
    assert tied.model_flops_per_token(cfg, 256) == 3 * fwd == 2555904
    assert tied.flash_calls(cfg, traffic) == [
        ((4, 4, 2, 256, 32), {"window": None}, 2)]


def test_flash_flops_under_a_window_against_a_hand_count():
    # 28 query heads of 128 over 8192 positions, keys i-4096 < j <= i: the
    # triangle 8192*8192/2 = 33,554,432 less the one the window cuts off,
    # 4096*4096/2 = 8,388,608, leaves 25,165,824 pairs a head.
    shape = (1, 28, 4, 8192, 128)
    assert counts.causal_pairs(8192, 4096) == 33554432 - 8388608 == 25165824
    assert counts.flash_call_flops("fwd", shape, 4096) == \
        2 * 2 * 28 * 25165824 * 128 == 360777252864
    assert counts.flash_call_flops("dkv", shape, 4096) == 2 * 360777252864
    full = 2 * 2 * 28 * 33554432 * 128
    for window in (None, 8192, 10000):      # no window, or none that binds
        assert counts.flash_call_flops("fwd", shape, window) == full
        assert counts.flash_call_flops("fwd", shape, window) == \
            counts.flash_call_flops("fwd", shape)
    # a window leaves the bytes alone; the least time falls with the flops
    peak = counts.peaks("TPU v5 lite")
    assert counts.flash_call_min_seconds("fwd", shape, peak, 4096) == (
        360777252864 / 197e12, "flops")
    assert counts.flash_call_min_seconds("fwd", shape, peak) == (
        full / 197e12, "flops")


def test_calls_split_over_the_kinds_of_layer():
    peak = counts.peaks("TPU v5 lite")
    shape = (1, 28, 4, 8192, 128)
    full = counts.flash_call_min_seconds("fwd", shape, peak)[0]
    band = counts.flash_call_min_seconds("fwd", shape, peak, 4096)[0]
    # one kind of layer: the calls seen times the call's least time, whatever
    # the number of layers says
    for layers in (1, 2, 7):
        assert counts.least_seconds(
            "fwd", 12, [(shape, {"window": None}, layers)], peak) == (
                12 * full, "flops")
    # one full layer to three windowed: 12 calls are 3 and 9
    mixed = [(shape, {"window": None}, 1), (shape, {"window": 4096}, 3)]
    least, binds = counts.least_seconds("fwd", 12, mixed, peak)
    assert least == pytest.approx(3 * full + 9 * band)
    assert binds == "flops"
    assert band == pytest.approx(0.75 * full)


def test_the_harness_names_no_architecture():
    named = re.compile(
        r"Transformer|intermediate_size|num_key_value_heads|lm_head|wq"
        r"|mlp_dim|mistral|tiny_tied")
    files = ["run.py", "train.py", "reference.py", "counts.py",
             "calibrate.py", "kernel_roofline.py", "arch.py",
             "trace_reduce.py"]
    files += [os.path.join("metrics", n)
              for n in sorted(os.listdir(os.path.join(HERE, "metrics")))
              if n.endswith(".py")]
    for name in files:
        with open(os.path.join(HERE, name), encoding="utf-8") as f:
            hits = [line for line in f if named.search(line)]
        assert not hits, (name, hits)
