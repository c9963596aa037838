"""``EngineStats.slot_occupancy`` over the DeepSeek-V2-Lite cell's window,
from the change of its counters: kept decode slot-steps over decode steps
times slots."""


def read(layer):
    if layer.get("stage") != "rollout" or layer.get("slot_occupancy") is None:
        return None
    return 100.0 * layer["slot_occupancy"]
