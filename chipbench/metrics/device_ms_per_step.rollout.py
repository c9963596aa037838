"""Device busy milliseconds per engine ``step()`` in the traced window
(profiler trace; steps counted by the driver's step annotations)."""
from chipbench.harness.readers import device_ms_per_step


def read(layer):
    return device_ms_per_step(layer, "rollout")
