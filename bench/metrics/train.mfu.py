"""train.mfu: the whole step's share of the chips' bf16 peak.

train_tokens_per_s of the run's untimed-trace window x the model FLOPs a
trained token requires (``bench/flops/model.py``: forward and backward,
causal attention, no remat, no capacity padding) / (chips x peak).
Moves train_tokens_per_s; a kernel taken off the path leaves its
roofline silent, and this still bounds the step."""

from bench.flops.model import train_flops_per_token


def read(run):
    m = run["cell"].config["model"]
    L = run["cell"].traffic["seq_len"]
    flops = run["tokens_per_s"] * train_flops_per_token(m, L)
    return 100.0 * flops / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
