"""host.input_idle_ms: device idle time per step inside the host spans
``train.input`` (``Trainer.run`` building the next batch), per chip,
averaged over chips."""

from bench.harness.scopes import idle_in_span_ms


def read(run):
    return idle_in_span_ms(run, "train.input")
