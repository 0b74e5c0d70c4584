"""train.mfu: the whole step's share of the chips' bf16 peak.

train_tokens_per_s of the run's untimed-trace window x the model FLOPs a
trained token requires (the configuration's family's
``bench/flops/<family>.py``: forward and backward, causal attention, no
remat, no capacity padding) / (chips x peak).  Moves train_tokens_per_s;
a kernel taken off the path leaves its roofline silent, and this still
bounds the step."""

from bench.harness.spec import family_part


def read(run):
    conf = run["cell"].config
    flops = run["tokens_per_s"] * family_part("flops", conf).\
        train_flops_per_token(conf["model"], run["cell"].traffic["seq_len"])
    return 100.0 * flops / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
