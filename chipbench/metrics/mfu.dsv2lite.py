"""Model operations of the DeepSeek-V2-Lite cell's decode and prefill
tokens (absorbed latent attention for decode, expanded for prefill, the
routed experts at their expected top_k * held / E passes a token;
``adapters/deepseek_v2.Shapes``), over the window's seconds, as a share of
the chip's peak: the whole engine step's share."""
from chipbench.harness.readers import share_of_peak


def read(layer):
    return share_of_peak(layer, "rollout", "flops", "flops_bf16")
