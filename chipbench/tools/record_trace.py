"""Record the small device trace that the trace-reduction test reads.

    python3 chipbench/tools/record_trace.py OUT_DIR [--dump]

Runs on a machine with one TPU.  The traced program is small on purpose:
inside a ``chipbench.window`` annotation, three times a host->device
upload of a numpy array and a jitted matmul pair under ``chipbench.step``,
each followed by a host pause.  So the trace holds device busy time,
transfers, idle gaps that the host annotations label, and (the first
step's eager cast is not warmed up) a compile inside the window.
``--dump`` prints every plane, line and the first events of each line,
which is how the reduction in ``chipbench/harness/xtrace.py`` was written
against a real trace.
"""
from __future__ import annotations

import glob
import os
import sys
import time


def main(argv) -> int:
    out = argv[1]
    dump = "--dump" in argv
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    host = np.ones((2048, 2048), np.float32)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    f(jnp.asarray(host, jnp.bfloat16), b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                a = jnp.asarray(host).astype(jnp.bfloat16)
                f(a, b).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.host_pause"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    print("trace files:", [(p, os.path.getsize(p)) for p in paths])
    if dump:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(paths[0])
        for plane in pd.planes:
            lines = list(plane.lines)
            print(f"PLANE {plane.name!r} lines={len(lines)} "
                  f"stats={[s for s in plane.stats][:8]}")
            for line in lines:
                evs = list(line.events)
                print(f"  LINE {line.name!r} events={len(evs)}")
                for e in evs[:12]:
                    print(f"    {e.name[:100]!r} start={e.start_ns:.0f} "
                          f"dur={e.duration_ns:.0f} "
                          f"stats={[(k, str(v)[:40]) for k, v in e.stats][:6]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
