"""Device busy milliseconds per engine ``step()`` in the traced window of
the DeepSeek-V2-Lite cell (profiler trace; steps counted by the driver's
step annotations): latent attention and the expert layer do nearly all of
it."""
from chipbench.harness.readers import device_ms_per_step


def read(layer):
    return device_ms_per_step(layer, "rollout")
