#!/usr/bin/env python3
"""Record the small chip trace ``test_trace.py`` reduces: a few runs of
two jitted programs inside a ``chipbench.window`` span, with a host
``chipbench.gap`` span over a pause in which the device idles.

    python3 chipbench/tests/record_trace.py   # on a TPU, writes data/
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: (a @ a).astype(jnp.bfloat16))
    norm = jax.jit(lambda a: a / jnp.max(jnp.abs(a)))
    jax.block_until_ready(norm(mm(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            x = jax.block_until_ready(norm(mm(x)))
        with jax.profiler.TraceAnnotation("chipbench.gap"):
            time.sleep(0.05)
        for _ in range(3):
            x = jax.block_until_ready(norm(mm(x)))
    jax.profiler.stop_trace()
    from harness.trace import xplane_file
    src = xplane_file(tmp)
    out = os.path.join(HERE, "data", "v5e-two-programs.xplane.pb")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    from harness.trace import reduce
    s = reduce(os.path.dirname(out), "window")
    print(s.window_s, s.busy_s, s.ops[:5], s.idle[:3],
          {k: len(v) for k, v in s.modules.items()})
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        print("plane", plane.name,
              [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines])
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))
    sys.exit(main())
