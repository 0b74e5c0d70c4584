"""host.expert_load_read_idle_ms: device idle time per step inside the
host spans ``train.expert_load_read`` (``Trainer.run``'s blocking read
of each step's routed-row counts), per chip, averaged over chips."""

from bench.harness.scopes import idle_in_span_ms


def read(run):
    return idle_in_span_ms(run, "train.expert_load_read")
