"""Share of the traced window in which no operation ran on the chip, in the
rollout stage of the DeepSeek-V2-Lite cell (profiler trace; uploads of
arguments count as idle)."""
from chipbench.harness.readers import idle_share


def read(layer):
    return idle_share(layer, "rollout")
