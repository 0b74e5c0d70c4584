"""step.unnamed_device_ms: device time per step of the leaf ops under
none of the layer scopes (``attn``, ``ffn``, ``norm``, ``head``,
``adamw``) and no MoE plan scope: the embedding, the loss's glue, ops
with an empty scope path.  With the six layer readers (attention
forward and backward, FFN, norms, head, optimizer) and moe.device_ms
it partitions the device's busy time."""

from bench.harness.scopes import unnamed_ms


def read(run):
    return unnamed_ms(run)
