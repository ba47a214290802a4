"""Performance observability end to end (ISSUE 15): run an instrumented
fused-window fit, read the live MFU/roofline gauges the cost index
folded, snapshot the memory profiler, write a perf dump and render the
offline one-page report (roofline table, step-time decomposition,
memory top-K).

Run: python examples/perf_report.py [out_dir]
"""
import os
import sys
import tempfile

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.listeners import PerformanceListener
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.telemetry import memprof
from deeplearning4j_tpu.telemetry.perf import get_cost_index, write_perf_dump
from tools.perf_report import load_dump, render


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else tempfile.mkdtemp()
    os.makedirs(out_dir, exist_ok=True)
    telemetry.reset()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 2048)]
    conf = (NeuralNetConfiguration(seed=7, updater=Adam(3e-3),
                                   dtype="float32")
            .list(DenseLayer(n_in=16, n_out=64, activation="tanh"),
                  OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    perf_l = PerformanceListener(frequency=16)
    net.set_listeners(perf_l)

    # 64 batches/epoch fused K=8 -> 64 steps/epoch: the cost capture
    # lands once the program crosses the 256-step warm-up threshold
    # (DL4J_TPU_PERF_CAPTURE_AFTER, epoch 4 here), and the final epoch's
    # fold reads a clean steady-state timing delta
    it = ListDataSetIterator(features=x, labels=y, batch_size=32)
    net.fit(iterator=it, epochs=5, steps_per_dispatch=8,
            async_prefetch=False)

    # --- live gauges the epoch-boundary fold published -------------------
    reg = telemetry.get_registry()
    print("== live perf gauges (cost index fold) ==")
    for name, g in sorted(reg.gauges_matching("perf.")):
        print(f"  {name} = {g.value:.6g}")
    cost = get_cost_index().get("fit/epoch/window")
    print(f"\ncaptured train-step program: {cost.flops_per_step:.0f} "
          f"flops/step, {cost.bytes_per_step:.0f} bytes/step "
          f"(source={cost.source}, K={cost.steps_per_call})")
    # no MFU on a device DEVICE_PEAKS does not know (the CPU, unless
    # BENCH_PEAK_TFLOPS / BENCH_HBM_GBPS say what to measure against)
    last = [r for r in perf_l.history if r.get("mfu") is not None]
    if last:
        print(f"PerformanceListener history mfu={last[-1]['mfu']:.3e} "
              f"achieved_tflops={last[-1]['achieved_tflops']:.3e}")

    # --- memory profiler -------------------------------------------------
    snap = memprof.snapshot(top_k=5)
    print(f"\n== memory: {snap['live_arrays']} live arrays, "
          f"{snap['total_live_bytes']} bytes ==")
    for row in snap["top"]:
        print(f"  {tuple(row['shape'])!s:>16} {row['dtype']:<9} "
              f"owner={row['owner']:<12} {row['total_bytes']}B")

    # --- offline report --------------------------------------------------
    dump_path = os.path.join(out_dir, "perf_dump.json")
    write_perf_dump(dump_path)
    print(f"\nwrote perf dump: {dump_path}\n")
    print(render(load_dump(dump_path)))


if __name__ == "__main__":
    main()
