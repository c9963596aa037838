"""Least bytes the window's engine calls must move, over the window's
seconds, as a share of the chip's HBM bandwidth: the weights once per
jitted call, the live KV each token reads and the KV it writes
(the architecture's ``Shapes`` in ``adapters/<architecture>.py``;
counted by ``drivers/rollout.Progress``)."""
from chipbench.harness.readers import share_of_peak


def read(layer):
    return share_of_peak(layer, "rollout", "hbm_bytes", "hbm_bytes_per_s")
