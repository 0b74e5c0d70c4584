"""expert_ffn_grouped_glu_roofline: the fused grouped expert kernel's
share of its roofline on its SwiGLU path (three expert matrices; the
counter tells it by the operand count), over the held experts' routed
rows of each traced step."""

from bench.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "expert_ffn_grouped")
