#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json`` (or, for the CPU rehearsal only, ``rehearsal.json``),
its configuration in ``configs/<config>/config.json`` (which names its
model family under ``families/``), its traffic in ``traffic/<traffic>.json``
(which names its generator kind under ``kinds/``), and each per-layer
metric in ``layer_metrics/<name>.py``. A new cell, configuration, traffic
mix or per-layer metric is new files and new entries; no file here changes.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``). A cell of
``BENCHMARK.json`` runs on a TPU only: on any other platform the run exits
non-zero, naming the platform, and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")
TRACE_SECONDS = 4.0                    # the traced part of a --trace 1 window


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str):
    """(cell, BENCHMARK.json, whether it is a rehearsal cell)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell, bench, False
    rehearsal = load_json(HERE, "rehearsal.json")
    for cell in rehearsal["workloads"]:
        if cell["name"] == name:
            return cell, bench, True
    known = [c["name"] for c in bench["workloads"] + rehearsal["workloads"]]
    raise SystemExit(f"run.py: no workload {name!r}; known: {known}")


def setup_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the machine comes with ``JAX_COMPILATION_CACHE_DIR`` set. The variable
    is exported so that the program's own ``ensure_compile_cache`` takes
    the same directory."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def device_record() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, from the allocator."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class Tracer:
    """Profiles ``TRACE_SECONDS`` of the window from a thread of its own, so
    that the thread driving the program never waits for the profiler."""

    def __init__(self, enabled: bool, directory: str, rehearsal: bool = False):
        self.enabled, self.dir, self.rehearsal = enabled, directory, rehearsal
        self._thread = None
        self.window_wall_ns = None        # (start, end) of the traced part
        self.sync_wall_ns = None
        self.stop_s = None                # what stop_trace took, in the thread
        self.error = None

    def begin(self) -> None:
        if not self.enabled:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = threading.Thread(target=self._run, name="bench-tracer",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.sync_wall_ns = time.time_ns()
            with jax.profiler.TraceAnnotation("bench.sync"):
                pass
            t0 = time.time_ns()
            time.sleep(TRACE_SECONDS)
            t1 = time.time_ns()
            jax.profiler.stop_trace()
            self.stop_s = (time.time_ns() - t1) / 1e9
            self.window_wall_ns = (t0, t1)
        except Exception as e:           # reported by finish(), never hidden
            self.error = e

    def finish(self, host_spans, blocking: bool = False):
        """Join the tracer and reduce the trace. ``host_spans``: (name,
        start wall ns, end wall ns) of the program's spans; ``blocking``:
        they block on the device's results (the serving spans do)."""
        if not self.enabled:
            return None
        self._thread.join(timeout=300.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop within 300 s")
        if self.error is not None:
            raise self.error
        from benchmarks.lib import xplane
        began = time.perf_counter()
        trace = xplane.load(xplane.find_xplane(self.dir))
        load_s = time.perf_counter() - began
        if trace["sync_ns"] is None:
            raise RuntimeError("bench.sync is not in the trace")
        if self.rehearsal:      # a CPU trace has no device plane to reduce
            shutil.rmtree(self.dir, ignore_errors=True)
            return None
        off = self.sync_wall_ns - trace["sync_ns"]
        w = tuple(t - off for t in self.window_wall_ns)
        spans = [(n, s - off, e - off) for n, s, e in host_spans]
        out = xplane.reduce(trace, w, spans, blocking=blocking)
        cost = dict(out["cost"], load_s=load_s, profiler_stop_s=self.stop_s)
        log("trace reduced: profiler's stop {profiler_stop_s:.1f}s (in its "
            "thread), xplane.load {load_s:.1f}s, clock fit "
            "{clock_fit_s:.1f}s, split {split_s:.1f}s; {device_ops} device "
            "operations, {gaps} gaps, {spans_kept} of {spans_given} spans "
            "kept".format(**cost))
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(OUT, exist_ok=True)
        with open(self.dir + ".ops.json", "w") as f:     # for a reader's eyes
            json.dump({"by_op_s": out["by_op_s"], "by_category_s":
                       out["by_category_s"], "lines": trace["lines"],
                       "device_clock_shift_ms": out["device_clock_shift_ms"],
                       "window_ns": w, "reduction_cost": cost}, f, indent=1)
        return out


def load_reader(name: str):
    """The reader of one per-layer metric: ``layer_metrics/<name>.py``."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_layer_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer_metrics(bench: dict, cell_name: str, obs: dict,
                       rehearsal: bool = False) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell (or
    lists none): its reader is ``layer_metrics/<name>.py`` ``read(obs)``. A
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        try:
            value = load_reader(m["name"]).read(obs)
        except KeyError:
            if not rehearsal:     # a CPU has no row in the table of peaks
                raise
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def prepare(workload: str, seed: int, seconds: float, trace: bool):
    """(ctx, bench, kind module) of one run, or a SystemExit naming the
    platform when the cell's chips are not there."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    cell, bench, rehearsal = find_cell(workload)
    config = load_json(HERE, "configs", cell["config"], "config.json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    # a rehearsal cell is held to the limits of the cell it stands for
    limits = load_json(HERE, "limits", _stands_for(cell) + ".json")

    cache = setup_compile_cache()
    device = device_record()
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want or device["count"] < cell["chips"]:
        print(f"run.py: cell {cell['name']!r} needs {cell['chips']} {want} "
              f"device(s); JAX started on platform {device['platform']!r} "
              f"({device['kind']}, {device['count']} device(s))",
              file=sys.stderr)
        raise SystemExit(3)
    log(f"cell {cell['name']} seed {seed} seconds {seconds} "
        f"trace {int(trace)} device {device} cache {cache}")

    from benchmarks.lib.correct import Checks
    fam = "benchmarks.families." + config["family"]
    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "limits": limits,
        "seed": seed, "seconds": seconds, "trace": trace,
        "rehearsal": rehearsal, "device": device, "t_start": T_START,
        "log": log, "checks": Checks(), "control": False,
        "family": {k: importlib.import_module(f"{fam}.{k}")
                   for k in ("build", "weights", "reference", "flops")},
        "tracer": Tracer(trace, os.path.join(
            OUT, "trace", f"{cell['name']}-{seed}"), rehearsal),
        "memory_peak_bytes": memory_peak_bytes,
        # the program's spans carry wall-clock microseconds; this puts the
        # harness's perf_counter stamps on the same clock
        "epoch_ns": time.time_ns() - time.perf_counter_ns(),
    }
    kind = importlib.import_module("benchmarks.kinds." + traffic["kind"])
    return ctx, bench, kind


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx, bench, kind = prepare(args.workload, args.seed, args.seconds,
                               bool(args.trace))
    cell, device, rehearsal = ctx["cell"], ctx["device"], ctx["rehearsal"]
    res = kind.run(ctx)
    res["obs"]["epoch_ns"] = ctx["epoch_ns"]

    checks = ctx["checks"]
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": checks.correct, "attempted": res["attempted"],
            "failed": res["failed"], "device": dev}
    e2e_names = {m["name"]: m for m in bench["end_to_end"]}
    if rehearsal:
        # a CPU run reports counts only, never under a device metric's name
        dev["platform"] = "cpu"
        line["metrics"] = {"rehearsal." + k: {"value": v, "unit": "count"}
                           for k, v in res["counts"].items()}
        if args.trace:
            obs = dict(res["obs"], trace=None)
            line["rehearsal_layer_metrics_read"] = sorted(
                read_layer_metrics(bench, _stands_for(cell), obs, True))
    elif args.trace:
        obs = res["obs"]
        reduced = obs["trace"]
        dev["busy_s"], dev["window_s"] = reduced["busy_s"], reduced["window_s"]
        line["metrics"] = read_layer_metrics(bench, cell["name"], obs)
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    else:
        line["metrics"] = {
            k: {"value": float(v), "unit": e2e_names[k]["unit"]}
            for k, v in res["metrics"].items() if k in e2e_names
            and cell["name"] in e2e_names[k].get("workloads", [cell["name"]])}
    line["checks"] = checks.rows
    log(f"wall {time.perf_counter() - T_START:.1f}s; threads left: "
        f"{[t.name for t in threading.enumerate() if t is not threading.current_thread() and not t.daemon]}")
    print(json.dumps(line), flush=True)
    return 0


def _stands_for(cell: dict) -> str:
    """The real cell a rehearsal cell rehearses (for its metric readers)."""
    return cell.get("stands_for", cell["name"])


if __name__ == "__main__":
    sys.exit(main())
