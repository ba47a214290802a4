"""StatsListener: per-iteration training statistics -> StatsStorage.

Reference: deeplearning4j-ui-parent/deeplearning4j-ui-model/src/main/java/org/
deeplearning4j/ui/stats/BaseStatsListener.java:234-406 (iterationDone collects
score, timings, memory, param/update/activation stats + histograms keyed by
sessionID/typeID/workerID) configured via StatsUpdateConfiguration.

TPU-first reshape: all tensor statistics for a report are computed ON DEVICE
in one jitted program over the whole param pytree (mean/stdev/mean-magnitude/
min/max/histogram per named leaf) and fetched with a single host transfer —
the reference's per-array host loops would serialize against the TPU stream.
Update stats are the param delta since the previous report (normalized per
iteration); the jitted train step donates its input buffers, so a cheap
on-device snapshot is taken at each report boundary.
"""
from __future__ import annotations

import os
import socket
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..optimize.listeners import TrainingListener
from .storage import InMemoryStatsStorage, StatsStorage


@dataclass
class StatsUpdateConfiguration:
    """What to collect, how often (reference StatsUpdateConfiguration /
    DefaultStatsUpdateConfiguration)."""
    report_frequency: int = 1
    collect_score: bool = True
    collect_timing: bool = True
    collect_memory: bool = True
    collect_param_stats: bool = True
    collect_update_stats: bool = True
    collect_activation_stats: bool = False
    collect_histograms: bool = False
    histogram_bins: int = 20
    collect_learning_rates: bool = True


def _named_leaves(params) -> List[Any]:
    """Flatten a param pytree into [(name, leaf)] with stable readable names
    (e.g. '0/W', 'conv1/b')."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:
                parts.append(str(p))
        out.append(("/".join(parts), leaf))
    return out


class StatsListener(TrainingListener):
    """Collects training stats into a StatsStorage every `report_frequency`
    iterations. Attach with `net.set_listeners(StatsListener(storage))`, then
    render with `deeplearning4j_tpu.ui.render_dashboard(storage, path=...)`
    or serve live with `TrainingUIServer`.
    """

    def __init__(self, storage: Optional[StatsStorage] = None,
                 config: Optional[StatsUpdateConfiguration] = None,
                 session_id: Optional[str] = None,
                 worker_id: str = "worker_0",
                 activation_sample=None):
        self.storage = storage if storage is not None else InMemoryStatsStorage()
        self.config = config or StatsUpdateConfiguration()
        self.session_id = session_id or uuid.uuid4().hex[:12]
        self.worker_id = worker_id
        # Optional sample batch: when collect_activation_stats is on, a jitted
        # forward over this batch yields per-layer activation mean-magnitudes.
        # (The training pass itself is one fused XLA program; its
        # intermediates are not observable without re-running the forward.)
        self.activation_sample = activation_sample
        self._static_posted = False
        self._stats_fn = None
        self._act_fn = None
        self._upd_fn = None
        self._prev_snapshot = None
        self._prev_snapshot_iter = None
        self._last_report_time = None
        self._iters_since_report = 0

    # ---------------------------------------------------------------- helpers
    def _build_stats_fn(self, params):
        bins = self.config.histogram_bins
        with_hist = self.config.collect_histograms

        def stats(p):
            out = {}
            for name, leaf in _named_leaves(p):
                x = leaf.astype(jnp.float32).reshape(-1)
                d = {"mean": jnp.mean(x), "stdev": jnp.std(x),
                     "meanmag": jnp.mean(jnp.abs(x)),
                     "min": jnp.min(x), "max": jnp.max(x)}
                if with_hist:
                    counts, edges = jnp.histogram(x, bins=bins)
                    d["hist_counts"] = counts
                    d["hist_lo"] = edges[0]
                    d["hist_hi"] = edges[-1]
                out[name] = d
            return out

        return jax.jit(stats)

    def _param_stats(self, params) -> Dict[str, Dict[str, Any]]:
        if self._stats_fn is None:
            self._stats_fn = self._build_stats_fn(params)
        dev = self._stats_fn(params)
        host = jax.device_get(dev)
        out = {}
        for name, d in host.items():
            rec = {k: float(v) for k, v in d.items() if not k.startswith("hist")}
            if "hist_counts" in d:
                rec["histogram"] = {"counts": np.asarray(d["hist_counts"]).tolist(),
                                    "lo": float(d["hist_lo"]),
                                    "hi": float(d["hist_hi"])}
            out[name] = rec
        return out

    def _update_stats(self, params, iteration) -> Optional[Dict[str, Any]]:
        """Mean-magnitude of (params - snapshot)/iters since the last report —
        the per-iteration update scale the reference reports from updater
        output (BaseStatsListener.java:383-394)."""
        if self._prev_snapshot is None:
            return None
        iters = max(iteration - self._prev_snapshot_iter, 1)

        if self._upd_fn is None:
            # Built once and cached; ``iters`` is a traced argument so the
            # compiled program is reused across reports (a fresh closure per
            # report would force an XLA recompile every iteration).
            def upd(p, prev, n_iters):
                out = {}
                named_now = _named_leaves(p)
                named_prev = dict(_named_leaves(prev))
                for name, leaf in named_now:
                    d = (leaf.astype(jnp.float32) - named_prev[name].astype(jnp.float32))
                    d = d.reshape(-1) / n_iters
                    out[name] = {"meanmag": jnp.mean(jnp.abs(d)),
                                 "mean": jnp.mean(d), "stdev": jnp.std(d)}
                return out
            self._upd_fn = jax.jit(upd)

        host = jax.device_get(self._upd_fn(params, self._prev_snapshot,
                                           jnp.float32(iters)))
        return {n: {k: float(v) for k, v in d.items()} for n, d in host.items()}

    def _snapshot(self, params):
        # Copy so the solver's buffer donation can't invalidate the snapshot.
        self._prev_snapshot = jax.tree.map(
            lambda x: jnp.array(x, copy=True), params)

    def _activation_stats(self, model) -> Optional[Dict[str, Any]]:
        x = self.activation_sample
        if x is None or not hasattr(model, "feed_forward"):
            return None
        if self._act_fn is None:
            def act(params, state, xx):
                acts, _ = model.apply_fn(params, state, xx, train=False)
                return [jnp.mean(jnp.abs(a.astype(jnp.float32))) for a in acts]
            self._act_fn = jax.jit(act)
        try:
            mags = jax.device_get(self._act_fn(model.params, model.state,
                                               jnp.asarray(x)))
        except TypeError:  # model without (params, state, x) apply signature
            return None
        return {f"layer_{i}": float(m) for i, m in enumerate(mags)}

    @staticmethod
    def _memory_stats() -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        try:
            import resource
        except ImportError:   # non-POSIX platform
            pass
        else:
            out["host_rss_mb"] = resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1024.0
        try:
            ms = jax.local_devices()[0].memory_stats()
        except (AttributeError, NotImplementedError, RuntimeError):
            # backends without PJRT memory stats (e.g. CPU) either raise or
            # have no memory_stats(); anything else is a real bug — surface it
            ms = None
        if ms:
            out["device_bytes_in_use"] = int(ms.get("bytes_in_use", 0))
            out["device_bytes_limit"] = int(ms.get("bytes_limit", 0))
        return out

    def _post_static(self, model):
        dev = jax.devices()
        info = {
            "model_class": type(model).__name__,
            "num_params": int(getattr(model, "num_params", lambda: 0)()),
            "backend": dev[0].platform if dev else "unknown",
            "device_kind": getattr(dev[0], "device_kind", "?") if dev else "?",
            "device_count": len(dev),
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "start_time": time.time(),
            "param_names": [n for n, _ in _named_leaves(model.params)],
        }
        # config JSON powers the dashboard's model-graph view (reference
        # TrainModule model tab renders from the stored config)
        conf = getattr(model, "conf", None)
        if conf is not None and hasattr(conf, "to_json"):
            info["model_config_json"] = conf.to_json()
        self.storage.put_static_info(self.session_id, self.worker_id, info)
        self._static_posted = True

    # ----------------------------------------------------------- listener API
    def iteration_done(self, model, iteration: int, score):
        if not self._static_posted:
            self._post_static(model)
        self._iters_since_report += 1
        if iteration % self.config.report_frequency != 0:
            return
        now = time.time()
        update: Dict[str, Any] = {"iteration": int(iteration), "timestamp": now}
        if self.config.collect_score:
            update["score"] = float(score)
        if self.config.collect_timing and self._last_report_time is not None:
            dt = max(now - self._last_report_time, 1e-9)
            update["iterations_per_sec"] = self._iters_since_report / dt
            update["ms_per_iteration"] = 1000.0 * dt / self._iters_since_report
        if self.config.collect_memory:
            update["memory"] = self._memory_stats()
        if self.config.collect_param_stats:
            update["params"] = self._param_stats(model.params)
        if self.config.collect_update_stats:
            us = self._update_stats(model.params, iteration)
            if us is not None:
                update["updates"] = us
            self._snapshot(model.params)
            self._prev_snapshot_iter = iteration
        if self.config.collect_activation_stats:
            acts = self._activation_stats(model)
            if acts is not None:
                update["activations"] = acts
        if self.config.collect_learning_rates:
            upd = getattr(model, "updater", None)
            if upd is not None and hasattr(upd, "layer_confs"):
                lrs = {}
                for i, c in enumerate(upd.layer_confs):
                    rule = upd.rule_for(c)
                    # rules without a schedule surface (e.g. NoOp) are skipped;
                    # a broken schedule raising inside lr() must propagate
                    if hasattr(rule, "lr"):
                        lrs[str(i)] = float(rule.lr(iteration))
                if lrs:
                    update["learning_rates"] = lrs
        self.storage.put_update(self.session_id, self.worker_id, update)
        self._last_report_time = now
        self._iters_since_report = 0
