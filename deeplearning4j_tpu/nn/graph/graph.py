"""ComputationGraph: DAG executor.

Reference: nn/graph/ComputationGraph.java (3200 LoC) — topological-order
forward (:1302,1369), reverse-order backward with epsilon accumulation
(:1570), multi-input/multi-output fit (:793-1079), evaluate (:2784).

TPU-first: forward in fixed topo order traced once; backward IS jax.grad of
the traced graph (fan-out epsilon accumulation is what reverse-mode autodiff
does by construction — the reference's hand-rolled accumulation machinery
disappears). Multi-output losses sum per the reference's
score += each output layer's computeScore.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..conf.graph_conf import ComputationGraphConfiguration
from ..layers.base import LayerConf
from ..layers.core import BaseOutputLayerMixin
from ..graph.vertices import (DuplicateToTimeSeriesVertex, LastTimeStepVertex,
                              LayerVertex)
from ...optimize.updaters import MultiLayerUpdater


def _as_list(x):
    if x is None:
        return None
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.vertex_names = list(conf.vertex_names)
        self.vertices = [conf.vertices[n] for n in self.vertex_names]
        layer_confs = [(v.layer if v.layer is not None else LayerConf())
                       for v in self.vertices]
        self.layers = tuple(layer_confs)
        self.updater = MultiLayerUpdater(
            layer_confs, conf.updater, conf.gradient_normalization,
            conf.gradient_normalization_threshold)
        self.params = None
        self.state = None
        self.opt_state = None
        self.iteration_count = 0
        self.listeners: List[Any] = []
        self._rnn_state: Optional[list] = None
        self._jit_cache: Dict[Any, Any] = {}

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None):
        rng = jax.random.PRNGKey(self.conf.seed if seed is None else seed)
        dtype = jnp.dtype(self.conf.dtype)
        itypes: Dict[str, Any] = {}
        if self.conf.input_types is not None:
            itypes.update(zip(self.conf.network_inputs, self.conf.input_types))
        params, state = [], []
        for name, v in zip(self.vertex_names, self.vertices):
            in_types = [itypes.get(i) for i in self.conf.vertex_inputs[name]]
            rng, sub = jax.random.split(rng)
            p, s = v.init(sub, in_types, dtype)
            params.append(p)
            state.append(s)
            if all(t is not None for t in in_types):
                # Eager per-vertex shape validation (reference
                # nn/conf/layers/LayerValidation.java): a config whose shapes
                # don't line up must fail HERE naming the vertex, not as an
                # opaque trace-time error inside the first jitted step.
                try:
                    itypes[name] = v.output_type(in_types)
                except Exception as e:
                    raise ValueError(
                        f"Shape inference failed at vertex {name!r} "
                        f"(inputs {self.conf.vertex_inputs[name]} -> "
                        f"{in_types}): {e}") from e
            else:
                itypes[name] = None
        self.params = tuple(params)
        self.state = tuple(state)
        self.opt_state = self.updater.init(self.params)
        return self

    # ------------------------------------------------------------- functional
    def apply_fn(self, params, state, inputs, *, train=False, rng=None,
                 features_masks=None, rnn_states=None,
                 collect_rnn_states: bool = False):
        """Forward in topo order. Returns (activations: dict name->array,
        new_state tuple) — or (acts, new_state, rnn_states_out) when
        ``collect_rnn_states`` (the tBPTT/streaming carry; reference
        ComputationGraph.rnnTimeStep :2301, tBPTT state sync :908).

        Per-timestep feature masks propagate vertex-to-vertex: a vertex's mask
        is its first input's mask, dropped once the time dimension collapses
        (reference MaskState flow through GraphVertex.setMaskArrays).
        """
        inputs = _as_list(inputs)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # mixed precision (see MultiLayerNetwork.apply_fn): master params stay
        # conf.dtype; compute runs in compute_dtype
        cd = getattr(self.conf, "compute_dtype", None)
        if cd:
            # state deliberately NOT cast — see MultiLayerNetwork.apply_fn
            from ..multilayer import cast_floats
            params = cast_floats(params, cd)
            inputs = cast_floats(inputs, cd)
            if rnn_states is not None:
                rnn_states = cast_floats(rnn_states, cd)
        acts: Dict[str, Any] = dict(zip(self.conf.network_inputs, inputs))
        masks: Dict[str, Any] = {}
        if features_masks is not None:
            masks.update({k: m for k, m in zip(self.conf.network_inputs,
                                               _as_list(features_masks)) if m is not None})
        new_state = []
        rnn_out = [None] * len(self.vertices)
        for idx, (name, v) in enumerate(zip(self.vertex_names, self.vertices)):
            in_names = self.conf.vertex_inputs[name]
            vin = [acts[i] for i in in_names]
            in_mask = next((masks[i] for i in in_names if i in masks), None)
            rng, sub = jax.random.split(rng)
            # each vertex under its own name: the name rides every
            # operation's metadata into the compiled program, where a
            # device trace reads it (the kernels open theirs inside)
            with jax.named_scope(name):
                if isinstance(v, LastTimeStepVertex):
                    mask = masks.get(v.mask_input) if v.mask_input \
                        else in_mask
                    if mask is not None and getattr(vin[0], "ndim", 0) == 3 \
                            and mask.shape[1] != vin[0].shape[1]:
                        mask = None   # sequence length changed upstream
                    out, s = v.apply(params[idx], state[idx], vin, train=train,
                                     rng=sub, mask=mask)
                elif isinstance(v, DuplicateToTimeSeriesVertex):
                    t = None
                    if v.reference_input is not None:
                        t = acts[v.reference_input].shape[1]
                    out, s = v.apply(params[idx], state[idx], vin, train=train,
                                     rng=sub, timesteps=t)
                elif isinstance(v, LayerVertex) and v.recurrent and \
                        (collect_rnn_states
                         or (rnn_states is not None
                             and rnn_states[idx] is not None)):
                    init = rnn_states[idx] if rnn_states is not None else None
                    out, final = v.apply_with_final_state(
                        params[idx], state[idx], vin, train=train, rng=sub,
                        mask=in_mask, initial_state=init)
                    s = state[idx]
                    rnn_out[idx] = final
                elif isinstance(v, LayerVertex) and \
                        getattr(self.conf, "gradient_checkpointing", False):
                    fn = jax.checkpoint(
                        lambda p, s_, xx, key, _v=v, _m=in_mask:
                        _v.apply(p, s_, xx, train=train, rng=key, mask=_m))
                    out, s = fn(params[idx], state[idx], vin, sub)
                elif isinstance(v, LayerVertex):
                    out, s = v.apply(params[idx], state[idx], vin, train=train,
                                     rng=sub, mask=in_mask)
                else:
                    out, s = v.apply(params[idx], state[idx], vin, train=train, rng=sub)
            acts[name] = out
            new_state.append(s)
            # propagate only while the time axis is unchanged — a vertex that
            # alters sequence length (e.g. strided Convolution1D) invalidates
            # the [B,T] mask for its consumers
            if in_mask is not None and getattr(out, "ndim", 0) == 3 and \
                    out.shape[1] == in_mask.shape[1]:
                masks[name] = in_mask
        if cd:
            from ..multilayer import cast_floats
            new_state = cast_floats(new_state, self.conf.dtype)
            rnn_out = cast_floats(rnn_out, self.conf.dtype)
            acts = cast_floats(acts, self.conf.dtype)
        if collect_rnn_states:
            return acts, tuple(new_state), rnn_out
        return acts, tuple(new_state)

    def loss_fn(self, params, state, x, labels, *, train=True, rng=None,
                labels_mask=None, features_mask=None, rnn_states=None,
                collect_rnn_states: bool = False):
        """Sum of output-layer losses + regularization (reference
        ComputationGraph.computeGradientAndScore :1245). With
        ``collect_rnn_states`` the aux also carries each recurrent vertex's
        final state — the tBPTT chunk carry (reference tBPTT branch :908)."""
        inputs = _as_list(x)
        labels = _as_list(labels)
        lmasks = _as_list(labels_mask) or [None] * len(labels)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        rng, fwd = jax.random.split(rng)
        rnn_out = None
        res = self.apply_fn(params, state, inputs, train=train,
                            rng=fwd, features_masks=features_mask,
                            rnn_states=rnn_states,
                            collect_rnn_states=collect_rnn_states)
        if collect_rnn_states:
            acts, new_state, rnn_out = res
        else:
            acts, new_state = res
        cd = getattr(self.conf, "compute_dtype", None)
        if cd:
            from ..multilayer import cast_floats
        total = 0.0
        for k, out_name in enumerate(self.conf.network_outputs):
            vi = self.vertex_names.index(out_name)
            v = self.vertices[vi]
            if not (isinstance(v, LayerVertex)
                    and isinstance(v.layer_conf, BaseOutputLayerMixin)):
                # The reference allows any vertex as a network output
                # (ComputationGraph.java: outputs need not be IOutputLayer);
                # only SCORING against labels requires a loss-bearing layer.
                if k < len(labels) and labels[k] is not None:
                    raise ValueError(
                        f"Network output {out_name!r} is not an output layer; "
                        f"it can be predicted via output() but not scored "
                        f"against labels")
                continue
            feed_name = self.conf.vertex_inputs[out_name][0]
            feed = (acts[feed_name] if feed_name not in self.conf.network_inputs
                    else inputs[self.conf.network_inputs.index(feed_name)])
            if v.preprocessor is not None:
                feed = v.preprocessor.apply(feed)
            rng, sub = jax.random.split(rng)
            head_params = params[vi]
            if cd:
                head_params = cast_floats(head_params, cd)
                feed = cast_floats(feed, cd)
            with jax.named_scope(out_name):
                per_ex = v.layer_conf.compute_loss_per_example(
                    head_params, feed, labels[k], lmasks[k], train=train,
                    rng=sub)
            if cd:
                per_ex = per_ex.astype(jnp.dtype(self.conf.dtype))
            lm = lmasks[k]
            if lm is not None and per_ex.ndim == 1 and lm.ndim >= 2:
                total = total + jnp.sum(per_ex) / jnp.maximum(jnp.sum(lm), 1.0)
            else:
                total = total + jnp.mean(per_ex)
        for layer, p in zip(self.layers, params):
            total = total + layer.regularization(p)
        if collect_rnn_states:
            return total, (new_state, rnn_out)
        return total, new_state

    # ------------------------------------------------------------- inference
    def _jitted(self, key, fn):
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(fn)
        return self._jit_cache[key]

    def output(self, *inputs, train: bool = False):
        inputs = [jnp.asarray(i) for i in inputs]
        fn = self._jitted(("output", train, len(inputs)),
                          functools.partial(self._output_pure, train=train))
        outs = fn(self.params, self.state, inputs)
        return outs[0] if len(outs) == 1 else outs

    def _output_pure(self, params, state, inputs, *, train=False):
        acts, _ = self.apply_fn(params, state, inputs, train=train)
        return [acts[o] for o in self.conf.network_outputs]

    def feed_forward(self, *inputs, train: bool = False):
        acts, _ = self.apply_fn(self.params, self.state,
                                [jnp.asarray(i) for i in inputs], train=train)
        return acts

    def score(self, x=None, y=None, dataset=None) -> float:
        if dataset is not None:
            x, y = dataset.features, dataset.labels
        fn = self._jitted(("score",),
                          lambda p, s, xx, yy: self.loss_fn(p, s, xx, yy,
                                                            train=False)[0])
        x = [jnp.asarray(v) for v in _as_list(x)]
        y = [jnp.asarray(v) for v in _as_list(y)]
        return float(fn(self.params, self.state, x, y))

    # -------------------------------------------------------------- streaming
    def rnn_time_step(self, *inputs):
        """Stateful streaming inference (reference
        ComputationGraph.rnnTimeStep :2301): feed [B,F] one step (or [B,T,F]
        a chunk) per network input; recurrent vertex state is carried between
        calls. Returns the network output(s) for the fed step(s)."""
        dtype = jnp.dtype(self.conf.dtype)
        xs = [jnp.asarray(i, dtype) for i in inputs]
        single = all(x.ndim == 2 for x in xs)
        if single:
            xs = [x[:, None, :] for x in xs]

        def fn(params, state, rnn_states, xx):
            acts, _, rnn_out = self.apply_fn(params, state, xx, train=False,
                                             rnn_states=rnn_states,
                                             collect_rnn_states=True)
            return [acts[o] for o in self.conf.network_outputs], rnn_out

        key = ("rnn_time_step", tuple(x.shape[1] for x in xs),
               self._rnn_state is None)
        jfn = self._jitted(key, fn)
        outs, self._rnn_state = jfn(self.params, self.state, self._rnn_state, xs)
        if single:
            outs = [o[:, -1] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    # ------------------------------------------------------------ flat params
    def params_flat(self) -> jnp.ndarray:
        leaves = []
        for v, p in zip(self.vertices, self.params):
            layer = v.layer
            order = layer.param_order if layer is not None else sorted(p)
            for name in order:
                if name in p:
                    leaves.append(jnp.ravel(p[name]))
        if not leaves:
            return jnp.zeros((0,), jnp.dtype(self.conf.dtype))
        return jnp.concatenate(leaves)

    def set_params_flat(self, flat):
        flat = jnp.asarray(flat)
        expected = self.num_params()
        if flat.shape != (expected,):
            raise ValueError(f"Expected flat parameter vector of length {expected}, "
                             f"got shape {flat.shape}")
        new_params, off = [], 0
        for v, p in zip(self.vertices, self.params):
            layer = v.layer
            order = layer.param_order if layer is not None else sorted(p)
            np_ = dict(p)
            for name in order:
                if name in p:
                    n = int(np.prod(p[name].shape)) if p[name].ndim else 1
                    np_[name] = flat[off:off + n].reshape(p[name].shape).astype(p[name].dtype)
                    off += n
            new_params.append(np_)
        self.params = tuple(new_params)

    def num_params(self) -> int:
        return int(sum(int(np.prod(v.shape)) for p in self.params for v in p.values()))

    # ------------------------------------------------------------------ train
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def _solver(self):
        if not hasattr(self, "_solver_inst"):
            from ...optimize.solver import Solver
            self._solver_inst = Solver(self)
        return self._solver_inst

    def fit(self, data=None, labels=None, *, epochs: int = 1,
            batch_size: Optional[int] = None, iterator=None, dataset=None,
            async_prefetch: bool = True, prefetch_depth: int = 2,
            steps_per_dispatch: int = 1, skip_first_batches: int = 0):
        """``async_prefetch``/``prefetch_depth``: iterator feeds (incl.
        MultiDataSet multi-input batches) run through a
        DevicePrefetchIterator — see MultiLayerNetwork.fit.

        ``steps_per_dispatch=K`` fuses K-step windows into one lax.scan
        program (see MultiLayerNetwork.fit); multi-input MultiDataSet
        batches are not stackable and run per-step.

        ``skip_first_batches=S``: mid-epoch resume — see
        MultiLayerNetwork.fit."""
        self._solver().fit(data=data, labels=labels, epochs=epochs,
                           batch_size=batch_size, iterator=iterator,
                           dataset=dataset, async_prefetch=async_prefetch,
                           prefetch_depth=prefetch_depth,
                           steps_per_dispatch=steps_per_dispatch,
                           skip_first_batches=skip_first_batches)
        return self

    def pretrain(self, iterator, epochs: int = 1):
        """Layerwise unsupervised pretraining of pretrainable layer
        vertices (reference ComputationGraph.pretrain)."""
        self._solver().pretrain(iterator, epochs=epochs)
        return self

    # ------------------------------------------------------------------ eval
    def evaluate(self, iterator_or_x, y=None):
        """Per-output classification evaluation. Single-output graphs return
        ONE Evaluation (reference ComputationGraph.evaluate :2784);
        multi-output graphs return a list of Evaluations, one per network
        output in declaration order."""
        from ...eval.evaluation import Evaluation
        n_out = len(self.conf.network_outputs)
        evals = [Evaluation() for _ in range(n_out)]

        def eval_batch(features, labels, lmask, metadata=None):
            outs = self.output(*_as_list(features))
            outs = outs if isinstance(outs, list) else [outs]
            labels_l = _as_list(labels)
            if len(labels_l) != n_out:
                raise ValueError(
                    f"evaluate() got {len(labels_l)} label array(s) for a "
                    f"{n_out}-output graph ({self.conf.network_outputs}); "
                    f"pass one per output (None to skip an output)")
            masks_l = _as_list(lmask) if lmask is not None else [None] * n_out
            if len(masks_l) != n_out:
                raise ValueError(
                    f"evaluate() got {len(masks_l)} label mask(s) for a "
                    f"{n_out}-output graph; pass one per output (None for "
                    f"unmasked outputs)")
            for e, o, l, m in zip(evals, outs, labels_l, masks_l):
                if l is not None:
                    # per-example metadata only applies to 2D outputs; a
                    # time-series output evaluates without records
                    md = metadata if np.asarray(l).ndim != 3 else None
                    e.eval(l, np.asarray(o), mask=m, record_meta_data=md)

        if y is not None:
            eval_batch(iterator_or_x, y, None)
        else:
            for ds in iterator_or_x:
                eval_batch(ds.features, ds.labels, ds.labels_mask,
                           metadata=getattr(ds, "metadata", None))
        return evals[0] if n_out == 1 else evals

    def clone(self) -> "ComputationGraph":
        import copy
        other = ComputationGraph(copy.deepcopy(self.conf))
        if self.params is not None:
            # REAL copies: the trained clone's jitted steps donate their
            # buffers; sharing arrays would invalidate the source network
            copy = lambda a: jnp.array(a, copy=True) if a is not None else None
            other.params = jax.tree.map(copy, self.params)
            other.state = jax.tree.map(copy, self.state)
            other.opt_state = jax.tree.map(copy, self.opt_state)
        return other
