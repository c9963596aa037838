"""Record the small engine trace that the idle-attribution test reads.

    python3 chipbench/tools/record_engine_trace.py OUT_FILE [STEPS]

Runs on a machine with one TPU.  A 2-layer cut of the ``qd1_5b``
configuration (published widths and vocabulary, random weights) serves
four 200-token prompts in the program's ``PagedEngine``, with its
parameters published to the program's ``WeightStore`` as the rollout
driver does (host copies, passed into every jitted call).  Once every
request decodes and the programs are compiled, ``STEPS`` (default 2)
``step()`` calls run under the profiler inside ``chipbench.window``, each
in ``chipbench.step``, as the driver's window does.  The ``.xplane.pb``, less its
``/host:metadata`` plane (the programs' HLO, which no reader here uses and
which is most of the file), is written to ``OUT_FILE``, and its reduction
(``harness/xtrace.py``) and idle split (``harness/phases.py``) are printed.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, payload of a length-delimited field or None, raw
    bytes) of each field of a serialized protobuf message."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire, payload = key & 7, None
        if wire == 2:
            n, i = _varint(buf, i)
            payload, i = buf[i:i + n], i + n
        elif wire == 0:
            _, i = _varint(buf, i)
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {start}")
        yield key >> 3, payload, buf[start:i]


def drop_plane(space: bytes, name: str) -> bytes:
    """A serialized ``XSpace`` (``planes`` = field 1) without the planes
    whose ``XPlane.name`` (field 2) is ``name``."""
    name_b = name.encode()
    return b"".join(
        raw for field, plane, raw in _fields(space)
        if not (field == 1 and any(f == 2 and v == name_b
                                   for f, v, _ in _fields(plane))))


def main(argv) -> int:
    out = argv[1]
    steps = int(argv[2]) if len(argv) > 2 else 2
    import jax
    import numpy as np

    from chipbench.harness import phases, program, xtrace
    from repro.data.tasks import MathTask
    from repro.models.api import get_model
    from repro.rl.rollout import GenConfig
    from repro.rl.weight_sync import WeightStore
    from repro.serve import PagedEngine, ServeConfig

    if jax.devices()[0].platform != "tpu":
        print("record_engine_trace: needs a TPU", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "chipbench", "configs", "qd1_5b.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2)
    mcfg = program.adapter(config).model_config(config)
    store = WeightStore()
    store.publish(get_model(mcfg).init(jax.random.PRNGKey(0), mcfg))
    engine = PagedEngine(
        mcfg, store, GenConfig(max_new_tokens=64, eos_id=-1),
        ServeConfig(max_slots=4, max_len=512, page_size=128,
                    prefill_chunk=256), rng_seed=0)
    rng = np.random.default_rng(0)
    engine.submit([MathTask(prompt="", answer=0,
                            prompt_ids=rng.integers(0, mcfg.vocab, 200).tolist())
                   for _ in range(4)])
    pools = lambda: jax.block_until_ready((engine.kv.k_pages,
                                           engine.kv.v_pages))
    while program.queued(engine) or program.prefilling(engine):
        engine.step()
    for _ in range(2):
        engine.step()
    pools()

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(steps):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                engine.step()
        pools()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(path, "rb") as f, open(out, "wb") as g:
        g.write(drop_plane(f.read(), "/host:metadata"))
    shutil.rmtree(tmp, ignore_errors=True)

    s = xtrace.summarize(out)
    split = phases.attribute(out)
    print(json.dumps({
        "bytes": os.path.getsize(out), "window_s": s.window_s,
        "busy_s": s.busy_s, "idle_share": s.idle_share, "steps": s.steps,
        "device_ops": s.device_ops[:5], "idle_gaps": s.idle_gaps[:5],
        "idle_by_scope_s": {str(k): v for k, v in split.by_scope.items()},
        "shares": {k: split.share(k) for k in ("wait", "host", "outside")},
        "host_arg_bytes": engine.stats.host_arg_bytes}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
