"""Model operations of the window's decode and prefill tokens (two per
multiply-add of every projection and the output head, plus attention over
each token's live context), over the window's seconds, as a share of the
chip's peak: the whole engine step's share, which bounds any kernel's."""
from chipbench.harness.readers import share_of_peak


def read(layer):
    return share_of_peak(layer, "rollout", "flops", "flops_bf16")
