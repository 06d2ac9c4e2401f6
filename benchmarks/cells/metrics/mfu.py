"""The whole step's share of the chip's bf16 peak: the operations the
configuration's architecture needs per token (``model_flops_per_token`` of its
own ``counts.py``) × the window's tokens a second a chip."""
import arch
import counts

NAME, UNIT, SOURCE = "mfu", "%", "host_clock"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    window, device = run["worker"]["window"], run["worker"]["device"]
    rate = window["tokens"] / window["seconds"] / device["count"]
    flops = arch.load(run["architecture"], "counts").model_flops_per_token(
        run["config"], run["traffic"]["seq"])
    return 100.0 * flops * rate / counts.peaks(
        device["kind"])["bf16_flops_per_s"]
