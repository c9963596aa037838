"""Least bytes the window's engine calls must move, over the window's
seconds, as a share of the chip's HBM bandwidth, for the DeepSeek-V2-Lite
cell: the weights outside the experts once per jitted call and the held
experts its rows reach (``adapters/deepseek_v2.Shapes.weight_bytes_per_call``),
the live latent cache each token reads and the latent it writes (31,104 B
a token); counted by ``drivers/rollout.Progress``."""
from chipbench.harness.readers import share_of_peak


def read(layer):
    return share_of_peak(layer, "rollout", "hbm_bytes", "hbm_bytes_per_s")
