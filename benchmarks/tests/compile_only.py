#!/usr/bin/env python3
"""Compile-only rehearsal at the real sizes for the v5e, with no chip
attached: the train step of a ``fit_cycle`` cell, lowered for a described
``v5e:2x2`` topology, with the compiler's ``memory_analysis()``. Nothing
runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python benchmarks/tests/compile_only.py gpt2m-train-1k [batch]
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the kernels pick themselves by the backend's name; this process is on the CPU
os.environ.setdefault("DL4J_TPU_FUSED_ATTN_INTERPRET", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import importlib

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import run as harness
    from deeplearning4j_tpu.ops import pallas_attention
    cell, _, _ = harness.find_cell(sys.argv[1])
    cfg = harness.load_json(harness.HERE, "configs", cell["config"], "config.json")
    tr = harness.load_json(harness.HERE, "traffic", cell["traffic"] + ".json")
    if len(sys.argv) > 2:
        tr["batch"] = int(sys.argv[2])
    tr["host_batches"] = 1
    build = importlib.import_module(f"benchmarks.families.{cfg['family']}.build")
    # this process's backend is the CPU; the step is compiled for the chip,
    # so the attention layer must lower its Mosaic kernels, not interpret
    pallas_attention._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    net = build.build(cfg, cfg["hyperparameters"], "train")
    shapes = jax.eval_shape(lambda: (net.init().params, net.state, net.opt_state))

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=dev), tree)
    x, y = build.make_batches(cfg, tr, np.random.default_rng(0))[0]
    feed = lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype if a.dtype.kind in "iu" else np.dtype(build.feed_dtype(cfg)),
        sharding=dev)
    t = time.perf_counter()
    compiled = net._solver()._get_step(False, False).lower(
        on(shapes[0]), on(shapes[1]), on(shapes[2]),
        jax.ShapeDtypeStruct((), np.int32, sharding=dev),
        jax.ShapeDtypeStruct((2,), np.uint32, sharding=dev),
        feed(x), feed(y)).compile()
    m = compiled.memory_analysis()
    out = {"cell": cell["name"], "batch": tr["batch"],
           "compile_s": round(time.perf_counter() - t, 1),
           "argument_gb": m.argument_size_in_bytes / 1e9,
           "output_gb": m.output_size_in_bytes / 1e9,
           "alias_gb": m.alias_size_in_bytes / 1e9,
           "temp_gb": m.temp_size_in_bytes / 1e9,
           "peak_estimate_gb": (m.argument_size_in_bytes + m.output_size_in_bytes
                                - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9,
           "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
