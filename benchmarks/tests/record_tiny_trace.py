#!/usr/bin/env python3
"""Records the small trace ``tests/data/tiny.xplane.pb`` that the trace
reduction's test reads: three jitted matmuls with a pause between them,
under a ``bench.sync`` annotation. Run on the chip; writes into
``chiprun_out/`` from where it was copied into ``tests/data/``."""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/tiny_trace"
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda x: (x @ x).sum())
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.sync"):
    pass
for _ in range(3):
    f(x).block_until_ready()
    time.sleep(0.01)
jax.profiler.stop_trace()
p = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(p, os.path.join(out, "tiny.xplane.pb"))
print(p, os.path.getsize(p), jax.devices())
