"""Training traffic: ``fit`` with its default arguments over seeded host
batches, fed by an iterator that stops yielding when the window's clock
runs out. Set-up builds ONE net, drives it through its first three steps
with the window's own call and feed (the readings ``correct`` compares),
warms it, and hands the same net to the window."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

CHECK_STEPS = 3
WARM_STEPS = 4


def run(ctx: Dict) -> Dict:
    import jax.numpy as jnp

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator
    from deeplearning4j_tpu.optimize.listeners import IterationListener

    log, cfg, tr, fam = ctx["log"], ctx["config"], ctx["traffic"], ctx["family"]
    hp = cfg["hyperparameters"]
    build, weights, reference = fam["build"], fam["weights"], fam["reference"]
    limits = ctx["limits"]

    class Cycle(DataSetIterator):
        """Cycles the host batches from ``start``; stops at ``max_steps``
        or once the clock passes ``deadline`` (``time.perf_counter``)."""

        def __init__(self, batches, start, max_steps=None, deadline=None):
            self.batches, self.start = batches, start
            self.max_steps, self.deadline = max_steps, deadline
            self.yielded = 0

        def __iter__(self):
            while True:
                if self.max_steps is not None and self.yielded >= self.max_steps:
                    return
                if self.deadline is not None and \
                        time.perf_counter() >= self.deadline:
                    return
                x, y = self.batches[(self.start + self.yielded) % len(self.batches)]
                self.yielded += 1
                yield DataSet(x, y)

    class Losses(IterationListener):
        """Keeps each step's loss on the device: nothing is read back
        inside the dispatch loop."""

        def __init__(self):
            self.scores: List = []

        def iteration_done(self, model, iteration, score):
            self.scores.append(score)

    rng = np.random.default_rng(ctx["seed"])
    batches = build.make_batches(cfg, tr, rng)
    net = build.build(cfg, hp, "train")
    build.install(net, weights.make(cfg, ctx["seed"], "train"))
    losses = Losses()
    net.set_listeners(losses)
    log(f"net built, {len(batches)} host batches of {tr['batch']}")

    # -- the first three steps, through the window's own call and feed
    net.fit(iterator=Cycle(batches, 0, max_steps=1))
    first = build.first_gradient(net, hp)
    prog = {"grad_norms": reference.leaf_norms(first),
            "grad_small": reference.small_leaves(first)}
    del first
    net.fit(iterator=Cycle(batches, 1, max_steps=CHECK_STEPS - 1))
    w0 = weights.make(cfg, ctx["seed"], "train")
    named = build.named(net, net.params)
    prog["change_norms"] = reference.leaf_norms(
        {k: a.astype(jnp.float32) - w0[k].astype(jnp.float32)
         for k, a in named.items()})
    del w0, named
    prog["losses"] = [float(s) for s in losses.scores]
    log(f"first {CHECK_STEPS} steps: losses {prog['losses']}")
    net.fit(iterator=Cycle(batches, CHECK_STEPS, max_steps=WARM_STEPS))
    float(losses.scores[-1])
    losses.scores.clear()

    reg = telemetry.get_registry()
    compiles0 = telemetry.xla_compile_count()
    reg_before = reg.raw_metrics()
    seq0 = reg.last_seq
    ctx["tracer"].begin()
    wall0 = time.time_ns()
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_start"]
    feed = Cycle(batches, CHECK_STEPS + WARM_STEPS, deadline=t0 + ctx["seconds"])
    net.fit(iterator=feed)
    last = float(losses.scores[-1])          # the read-back closes the window
    t1 = time.perf_counter()
    steps, elapsed = feed.yielded, t1 - t0
    compiles = telemetry.xla_compile_count() - compiles0
    peak = ctx["memory_peak_bytes"]()
    reg_after = reg.raw_metrics()
    events = reg.trace_events_since(seq0)
    rate = steps * tr["batch"] / elapsed
    log(f"window: {steps} steps" + ("" if ctx["rehearsal"] else
        f" in {elapsed:.3f}s -> {rate:.4f} samples/s") +
        f"; last loss {last}; compiles {compiles}; peak {peak / 1e9:.3f} GB")
    spans = [(e["args"].get("path", e["name"]), e["ts"] * 1000,
              (e["ts"] + e["dur"]) * 1000) for e in events
             if e.get("ph") == "X" and e.get("cat") == "span"]
    trace = ctx["tracer"].finish(spans)

    # -- free the program, then the reference follows the same three steps
    net.set_listeners()
    net.params = net.opt_state = net.state = None
    del net
    t_ref = time.perf_counter()
    ref = reference.train_reference(
        weights.make(cfg, ctx["seed"], "train"), batches[:CHECK_STEPS], cfg, hp,
        rows=tr.get("reference_rows", 1))
    log(f"reference: {time.perf_counter() - t_ref:.1f}s, losses {ref['losses']}")
    from benchmarks.lib.correct import worst_leaf_gap
    checks = ctx["checks"]
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        checks.at_most(f"loss_step{i + 1}_rel_gap", abs(a - b) / abs(b),
                       limits["loss_rel_gap"])
    checks.at_most("first_gradient_worst_leaf_norm_gap",
                   worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
                   limits["grad_norm_gap"])
    checks.at_most("first_gradient_vector_leaves_rel_error",
                   reference.vectors_rel_error(prog["grad_small"],
                                               ref["grad_small"]),
                   limits["grad_vectors_rel_error"])
    checks.at_most("param_change_worst_leaf_norm_gap",
                   worst_leaf_gap(prog["change_norms"], ref["change_norms"]),
                   limits["change_norm_gap"])
    checks.exactly("compiles_in_window", compiles, 0)
    checks.exactly("last_loss_is_finite", bool(np.isfinite(last)), True)

    flops = fam["flops"].train_flops_per_sample(cfg, tr.get("seq_len", 0))
    return {
        "attempted": steps, "failed": 0, "memory_peak_bytes": peak,
        "metrics": {"train_samples_per_s": rate, "setup_s": setup_s},
        "counts": {"steps": steps, "compiles_in_window": compiles},
        "obs": {"kind": "fit_cycle", "rate": rate, "steps": steps,
                "elapsed_s": elapsed, "compiles_in_window": compiles,
                "flops_per_sample": flops, "reg_before": reg_before,
                "reg_after": reg_after, "trace": trace, "peak_bytes": peak,
                "device": ctx["device"], "traffic": tr, "config": cfg},
    }
