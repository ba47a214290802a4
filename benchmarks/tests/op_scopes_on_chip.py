#!/usr/bin/env python3
"""Device time by named scope over a few traced steps of a training cell,
and WHICH stat of the trace's ``XLA Ops`` events held the scope. Run on
the chip; nothing reads it: its output is for the ``benchmark`` issue that
will split ``by_op_s``'s anonymous ``fusion`` families by scope.

    python benchmarks/tests/op_scopes_on_chip.py --workload gpt2m-train-1k --seed 7 --steps 6

Since ISSUE 42 ``ComputationGraph``'s forward runs each vertex under
``jax.named_scope(<vertex name>)`` and ``train_step_math`` its loss and
its updater under ``loss`` and ``updater``; XLA carries the scope into
each instruction's ``op_name`` metadata (a fusion takes its root's). This
script builds the cell's net as ``kinds/fit_cycle.py`` does, warms it,
profiles ``--steps`` steps and reads the raw ``.xplane.pb``: for every
event of the ``XLA Ops`` line it looks for the scope's path first in the
event's stats, then in the event's own name (the instruction's HLO text
with its ``metadata={op_name="..."}``), and sums the device time by the
scope's top level (``jvp(loss)``, ``transpose(jvp(loss))``, ``updater``)
and by vertex; ``raw_samples`` keeps a few events as the trace has them.
Where neither holds it (``jax.profiler.ProfileData`` hands out an event's
own stats, not those of its metadata record), ``raw`` reads the file's
protocol buffers with TensorFlow's copy of the schema, if installed.
Writes
``chiprun_out/op_scopes/<cell>-<seed>.json`` and prints it.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmarks import run as harness  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402

SCOPE = re.compile(r"jit\([^)]*\)/(.+)")
OP_NAME = re.compile(r'op_name="([^"]+)"')


def split_scope(op_name: str):
    """``jit(step)/jvp(loss)/b0_attn/dot_general`` -> (``jvp(loss)``,
    ``b0_attn``); an operation outside every scope -> (None, None)."""
    m = SCOPE.search(op_name)
    if not m:
        return None, None
    parts = m.group(1).split("/")
    # the step may sit inside a window's loop: the first component that is
    # one of the step's two scopes, wherever it stands
    for i, part in enumerate(parts):
        if part == "updater":
            return part, None
        if "(loss)" in part or part == "loss":
            return part, parts[i + 1] if len(parts) > i + 2 else None
    return None, None


def analyse(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    held_by = collections.Counter()          # stat name -> events it named
    stat_names = collections.Counter()
    by_top = collections.Counter()
    by_vertex = collections.Counter()
    by_family_unscoped = collections.Counter()
    total = 0.0
    events = 0
    samples = []
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                events += 1
                dur = float(e.duration_ns) / 1e9
                total += dur
                top = vertex = None
                for key, value in e.stats:
                    stat_names[key] += 1
                    if isinstance(value, str) and "jit(" in value and \
                            top is None:
                        t, v = split_scope(value)
                        if t is not None:
                            top, vertex = t, v
                            held_by[key] += 1
                if top is None:
                    m = OP_NAME.search(e.name)
                    if m:
                        top, vertex = split_scope(m.group(1))
                        if top is not None:
                            held_by["(the event's name: HLO text)"] += 1
                if len(samples) < 6 and events % 997 == 1:
                    samples.append({"name": e.name[:600],
                                    "stats": [[k, str(v)[:200]]
                                              for k, v in e.stats]})
                if top is None:
                    by_family_unscoped[xplane.op_family(e.name)] += dur
                else:
                    by_top[top] += dur
                    by_vertex[vertex or "(none)"] += dur
    return {"device_events": events, "device_s": total,
            "raw_samples": samples,
            "scope_held_by_stat": dict(held_by),
            "stat_names_seen": dict(stat_names.most_common(12)),
            "s_by_top_level_scope": dict(by_top.most_common()),
            "s_by_vertex_top20": dict(by_vertex.most_common(20)),
            "s_unscoped_by_family_top10":
                dict(by_family_unscoped.most_common(10))}


def analyse_raw(path: str, device: str = "TPU") -> dict:
    """The same sums from the file's own protocol buffers, where the
    profiler's ``ProfileData`` shows no stat that holds the scope: an
    event's METADATA (one record an instruction) has stats of its own
    that ``ProfileData`` does not hand out. Needs TensorFlow's copy of the
    ``xplane`` schema; says so where that is not installed."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:        # not part of this repository's needs
        return {"unavailable": repr(e)}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    held_by = collections.Counter()
    seen = collections.Counter()
    by_top = collections.Counter()
    by_vertex = collections.Counter()
    unscoped = collections.Counter()
    sample = None
    for plane in space.planes:
        if not (plane.name.startswith("/device:") and device in plane.name):
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        scope_of = {}
        for mid, md in plane.event_metadata.items():
            for st in md.stats:
                seen[stat_name.get(st.metadata_id, "?")] += 1
                kind = st.WhichOneof("value")
                value = getattr(st, kind) if kind else None
                if kind == "ref_value":
                    value = stat_name.get(value, "")
                if isinstance(value, bytes):
                    value = value.decode(errors="replace")
                if isinstance(value, str) and "jit(" in value \
                        and mid not in scope_of:
                    top, vertex = split_scope(value)
                    if top is not None:
                        scope_of[mid] = (top, vertex)
                        held_by[stat_name.get(st.metadata_id, "?")] += 1
                        if sample is None:
                            sample = {"name": md.name[:200],
                                      "display_name": md.display_name[:200],
                                      "value": value[:300]}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                dur = ev.duration_ps / 1e12
                if ev.metadata_id in scope_of:
                    top, vertex = scope_of[ev.metadata_id]
                    by_top[top] += dur
                    by_vertex[vertex or "(none)"] += dur
                else:
                    unscoped[xplane.op_family(
                        plane.event_metadata[ev.metadata_id].name)] += dur
    return {"scope_held_by_metadata_stat": dict(held_by),
            "metadata_stat_names_seen": dict(seen.most_common(16)),
            "sample": sample,
            "s_by_top_level_scope": dict(by_top.most_common()),
            "s_by_vertex_top20": dict(by_vertex.most_common(20)),
            "s_unscoped_by_family_top10": dict(unscoped.most_common(10))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2m-train-1k")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args(argv)
    ctx, _, _ = harness.prepare(args.workload, args.seed, 0.0, False)
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator
    cfg, tr, fam = ctx["config"], ctx["traffic"], ctx["family"]
    build, weights = fam["build"], fam["weights"]
    batches = build.make_batches(cfg, tr, np.random.default_rng(args.seed))
    net = build.build(cfg, cfg["hyperparameters"], "train")
    build.install(net, weights.make(cfg, args.seed, "train"))

    class Few(DataSetIterator):
        def __init__(self, n):
            self.n = n

        def __iter__(self):
            for i in range(self.n):
                yield DataSet(*batches[i % len(batches)])

    net.fit(iterator=Few(4))                         # compile and warm
    jax.block_until_ready(net.params)
    out_dir = os.path.join(harness.OUT, "trace", f"scopes-{args.seed}")
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    t0 = time.perf_counter()
    net.fit(iterator=Few(args.steps))
    jax.block_until_ready(net.params)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    res = analyse(xplane.find_xplane(out_dir))
    if not res["scope_held_by_stat"]:
        try:
            res["raw"] = analyse_raw(xplane.find_xplane(out_dir))
        except Exception as e:           # a hand script reports, never dies
            res["raw"] = {"error": repr(e)}
    res.update(workload=args.workload, seed=args.seed, steps=args.steps,
               wall_s=wall, device=ctx["device"])
    shutil.rmtree(out_dir, ignore_errors=True)
    dest = os.path.join(ROOT, "chiprun_out", "op_scopes")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"{args.workload}-{args.seed}.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
