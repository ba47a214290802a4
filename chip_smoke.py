#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two entry points the ROADMAP's north star names, once, in ONE
process, through the calls a user would make, at the full width of the one
language model the repo has (``models.transformer_lm`` V4096 d512 H8,
12 blocks, T1024, bf16, token-id input; random weights from a seed):

- train leg: ``net.fit`` for a few steps on one repeated batch, per-step and
  as ``steps_per_dispatch=4`` windows — finite, falling loss, no compile
  after the first dispatch of each form;
- serve leg: the same net behind ``GenerationEngine`` + ``ServingHTTPServer``
  on localhost — concurrent streaming and blocking ``POST /generate``
  clients (more than decode slots), a repeated block-aligned prompt (prefix
  hit + copy-on-write), every token checked against the public full forward,
  zero compiles after warm-up, ``stop()`` drains;
- kernels: every registered Pallas kernel's parity pin on the device;
- mesh leg (only when JAX shows >= 4 devices): ``ParallelWrapper`` fits on a
  ``(4,)`` and a ``(2, 2)`` mesh and a head-sharded ``GenerationEngine``.

``ops.kernels.active_impl`` answers from the backend's NAME; this script
looks at the compiled programs instead and fails when the train step or a
flash-eligible prefill carries no ``tpu_custom_call``.

There is no CPU mode and no size switch: without a TPU it exits non-zero in
seconds, naming the platform it found, and prints no result. On success the
last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The legs are importable functions that take the model sizes, so
``tests/test_chip_smoke.py`` drives them at toy width on the CPU and the
script cannot rot between chip runs.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# The zoo's language model (``models/zoo_extra.transformer_lm``) at d=512.
LM = dict(vocab_size=4096, d_model=512, n_heads=8, n_blocks=12,
          max_length=1024, dtype="bfloat16")
TRAIN = dict(batch=8, steps=6, window=4)
SERVE = dict(prompt_rungs=(256, 1024), block_len=16, decode_slots=4,
             prefill_batches=(1, 4), max_tokens=8,
             # an emitted token must carry at least this share of the
             # reference forward's top probability at its position. bf16
             # softmax outputs step by 2^-8 and the cached decode sums in
             # another order than the full forward, so near-ties between
             # the top few of 4096 near-uniform logits flip; a token picked
             # any other way lands here with probability ~1e-3.
             near_argmax=0.85)
_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(AssertionError):
    """A leg produced something wrong (as opposed to raising)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def build_lm(*, vocab_size: int, d_model: int, n_heads: int, n_blocks: int,
             max_length: int, dtype: str, seed: int = 12345):
    from deeplearning4j_tpu.models import transformer_lm
    return transformer_lm(vocab_size=vocab_size, d_model=d_model,
                          n_heads=n_heads, n_blocks=n_blocks,
                          max_length=max_length, dtype=dtype, seed=seed,
                          token_input=True).init()


def custom_calls(compiled) -> int:
    """Pallas kernels in a compiled program, read off the program text."""
    return compiled.as_text().count(_CUSTOM_CALL)


def _batch(vocab_size: int, batch: int, seq_len: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab_size, (batch, seq_len)).astype(np.int32)
    onehot_next = np.eye(vocab_size, dtype=np.float32)[np.roll(ids, -1, 1)]
    return ids, onehot_next


# ------------------------------------------------------------------ train
def train_leg(net, *, vocab_size: int, seq_len: int, batch: int, steps: int,
              window: int) -> dict:
    """``fit`` on one repeated batch: ``steps`` per-step dispatches, then
    two ``steps_per_dispatch=window`` windows. Returns the losses, the
    cold first-dispatch seconds of each form and the train step's
    custom-call count."""
    import jax

    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from deeplearning4j_tpu.serving import xla_compile_count

    ids, onehot = _batch(vocab_size, batch, seq_len)
    scores = CollectScoresIterationListener()
    net.set_listeners(scores)

    def losses() -> List[float]:          # reading the scores blocks
        return [float(s) for _, s in scores.scores]

    t0 = time.perf_counter()
    net.fit(ids, onehot, batch_size=batch, epochs=1)
    first = losses()
    cold_step_s = time.perf_counter() - t0
    c0 = xla_compile_count()
    net.fit(ids, onehot, batch_size=batch, epochs=steps - 1)
    per_step = losses()
    check(xla_compile_count() == c0,
          f"per-step fit compiled {xla_compile_count() - c0} program(s) "
          f"after its first step")
    check(len(per_step) == steps and np.all(np.isfinite(per_step)),
          f"per-step losses not finite: {per_step}")
    check(per_step[-1] < first[0],
          f"loss did not fall over {steps} steps on one batch: {per_step}")

    ids_w, onehot_w = np.tile(ids, (window, 1)), np.tile(onehot,
                                                         (window, 1, 1))
    t0 = time.perf_counter()
    net.fit(ids_w, onehot_w, batch_size=batch, epochs=1,
            steps_per_dispatch=window)
    losses()
    cold_window_s = time.perf_counter() - t0
    c1 = xla_compile_count()
    net.fit(ids_w, onehot_w, batch_size=batch, epochs=1,
            steps_per_dispatch=window)
    windowed = losses()[steps:]
    check(xla_compile_count() == c1,
          f"windowed fit compiled {xla_compile_count() - c1} program(s) "
          f"after its first window")
    check(len(windowed) == 2 * window and np.all(np.isfinite(windowed)),
          f"windowed losses not finite: {windowed}")
    check(windowed[-1] < per_step[-1],
          f"loss did not keep falling through the windows: "
          f"{per_step} then {windowed}")

    # the program itself: lower the Solver's own jitted step at the shapes
    # fit just ran (served from the compilation cache, not recompiled)
    def avals(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    compiled = net._solver()._get_step(False, False).lower(
        avals(net.params), avals(net.state), avals(net.opt_state),
        jax.ShapeDtypeStruct((), np.int32), avals(jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct(ids.shape, ids.dtype),
        jax.ShapeDtypeStruct(onehot.shape, net.conf.dtype)).compile()
    net.set_listeners()
    return {"per_step_losses": per_step, "windowed_losses": windowed,
            "cold_step_s": round(cold_step_s, 2),
            "cold_window_s": round(cold_window_s, 2),
            "train_step_custom_calls": custom_calls(compiled)}


# ------------------------------------------------------------------ serve
def _post_generate(client, base: str, prompt: Sequence[int], max_tokens: int,
                   stream: bool) -> Tuple[List[int], Optional[str]]:
    """One ``POST /generate``; returns (tokens, finish reason)."""
    payload = {"prompt": [int(t) for t in prompt], "max_tokens": max_tokens,
               "stream": stream, "timeout_ms": 120_000}
    if not stream:
        status, body = client.request_json("POST", base + "/generate",
                                           payload=payload, timeout=180.0)
        check(status == 200, f"blocking /generate -> {status}: {body}")
        return body["tokens"], body["reason"]
    tokens, done = [], None
    with client.stream("POST", base + "/generate",
                       body=json.dumps(payload).encode(),
                       headers={"Content-Type": "application/json"},
                       timeout=180.0) as resp:
        check(resp.status == 200, f"streaming /generate -> {resp.status}")
        for raw in resp:
            if not raw.strip():
                continue
            line = json.loads(raw)
            if "token" in line:
                tokens.append(line["token"])
            elif line.get("done"):
                done = line
            else:
                raise SmokeFailure(f"unexpected stream line {line}")
    check(done is not None, "stream ended without a done line")
    check(done["tokens"] == len(tokens),
          f"done line counts {done['tokens']} tokens, stream carried "
          f"{len(tokens)}")
    return tokens, done["reason"]


def reference_shares(net, replies, *, capacity: int) -> List[float]:
    """For every emitted token, its probability under the PUBLIC full
    forward (``net.output`` over prompt + reply, teacher-forced, padded to
    the cache capacity like ``models.decode.naive_generate``) as a share
    of that position's top probability. 1.0 = the reference's argmax."""
    buf = np.zeros((len(replies), capacity), np.int32)
    for i, (prompt, tokens) in enumerate(replies):
        seq = list(prompt) + list(tokens)
        buf[i, :len(seq)] = seq
    probs = np.asarray(net.output(buf), np.float32)     # [n, capacity, V]
    shares = []
    for i, (prompt, tokens) in enumerate(replies):
        for j, tok in enumerate(tokens):
            row = probs[i, len(prompt) + j - 1]
            shares.append(float(row[tok] / row.max()))
    return shares


def serve_leg(net, *, vocab_size: int, max_seq_len: int,
              prompt_rungs: Sequence[int], block_len: int, decode_slots: int,
              prefill_batches: Sequence[int], max_tokens: int,
              near_argmax: float, mesh=None) -> dict:
    """``GenerationEngine`` behind ``ServingHTTPServer`` on localhost."""
    from deeplearning4j_tpu.serving import (GenerationEngine,
                                            ServingHTTPServer,
                                            xla_compile_count)
    from deeplearning4j_tpu.util.httpjson import HTTPClient

    t0 = time.perf_counter()
    eng = GenerationEngine(net, model_name="lm", max_seq_len=max_seq_len,
                           prompt_rungs=tuple(prompt_rungs),
                           block_len=block_len, decode_slots=decode_slots,
                           prefill_batches=tuple(prefill_batches), mesh=mesh)
    warm_s = time.perf_counter() - t0
    ps = eng._get("lm").active_ps
    cfg = ps.config
    out = {"warm_s": round(warm_s, 2), "programs": eng.trace_count,
           "prefill_custom_calls": {
               f"b{P}xp{L}": custom_calls(ps._compiled[("prefill", P, L)])
               for P in cfg.prefill_batches for L in cfg.prompt_rungs},
           "decode_custom_calls": custom_calls(ps._compiled[("decode",)])}

    srv = ServingHTTPServer(generation=eng)
    base = f"http://127.0.0.1:{srv.start()}"
    n_clients = decode_slots + 2
    client = HTTPClient(max_per_host=n_clients + 1, timeout=180.0)
    rng = np.random.default_rng(1)
    replies: List[Tuple[List[int], List[int]]] = []
    lock = threading.Lock()
    errors: List[BaseException] = []

    def ask(prompt, stream):
        try:
            tokens, reason = _post_generate(client, base, prompt, max_tokens,
                                            stream)
            check(reason == "length" and len(tokens) == max_tokens,
                  f"asked for {max_tokens} tokens, got {len(tokens)} "
                  f"(reason {reason!r})")
            check(all(isinstance(t, int) and 0 <= t < vocab_size
                      for t in tokens), f"token outside the vocab: {tokens}")
            with lock:
                replies.append((list(prompt), tokens))
        except BaseException as e:      # re-raised on the main thread
            errors.append(e)

    stopped = False
    try:
        c0 = xla_compile_count()
        # one block-aligned prompt, twice: the repeat is a prefix hit whose
        # last shared block is copied on write
        aligned = rng.integers(0, vocab_size, 2 * block_len).tolist()
        ask(aligned, stream=False)
        ask(aligned, stream=True)
        # more concurrent clients than decode slots, streaming and
        # blocking, one prompt past the first rung
        lengths = [5, block_len + 3, 2 * block_len, 41, 7,
                   min(cfg.prompt_rungs[0] + 9, max_seq_len - max_tokens)]
        lengths = (lengths * n_clients)[:n_clients]
        threads = [threading.Thread(
            target=ask, args=(rng.integers(0, vocab_size, n).tolist(),
                              i % 2 == 0))
            for i, n in enumerate(lengths)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
            check(not t.is_alive(), "a /generate client hung")
        if errors:
            raise errors[0]
        check(xla_compile_count() == c0,
              f"serving compiled {xla_compile_count() - c0} program(s) "
              f"after warm-up")
        m = eng.metrics()["lm"]
        out["prefix"] = {k: m["prefix"][k]
                         for k in ("hits", "misses", "cow_copies")}
        check(m["prefix"]["hits"] >= 1 and m["prefix"]["cow_copies"] >= 1,
              f"repeated block-aligned prompt did not hit + COW: "
              f"{m['prefix']}")
        # stop() drains: a request in flight when stop is called finishes
        # with all of its tokens
        last = threading.Thread(target=ask, args=(
            rng.integers(0, vocab_size, 9).tolist(), True))
        last.start()
        deadline = time.monotonic() + 60.0
        while not eng.models()["lm"]["in_flight"] \
                and not eng.models()["lm"]["queue_depth"] \
                and last.is_alive() and time.monotonic() < deadline:
            time.sleep(0.001)
        srv.stop(drain=True)
        stopped = True
        last.join(timeout=120.0)
        check(not last.is_alive(), "stop(drain=True) left a client hanging")
        if errors:
            raise errors[0]
        check(len(replies) == n_clients + 3,
              f"{len(replies)} replies for {n_clients + 3} requests")
    finally:
        client.close()
        if not stopped:
            srv.stop(drain=False)

    shares = reference_shares(net, replies, capacity=cfg.capacity)
    out.update(requests=len(replies), tokens=len(shares),
               argmax_tokens=sum(s >= 1.0 for s in shares),
               min_reference_share=round(min(shares), 4))
    check(min(shares) >= near_argmax,
          f"an emitted token carries {min(shares):.3f} of the reference "
          f"forward's top probability (< {near_argmax}); "
          f"{out['argmax_tokens']}/{len(shares)} tokens are its argmax")
    return out


# ---------------------------------------------------------------- kernels
def kernels_leg() -> Dict[str, dict]:
    """Every registered kernel's parity pin, on this device."""
    from deeplearning4j_tpu.ops import kernels
    rows = {}
    for name in kernels.names():
        tol = kernels.get(name).parity.tol
        err = kernels.parity_error(name)
        rows[name] = {"impl": kernels.active_impl(name), "max_abs_err": err,
                      "tol": tol}
        check(err <= tol, f"kernel {name}: parity error {err:.3g} exceeds "
                          f"its pin's tolerance {tol:.3g}")
    return rows


# ------------------------------------------------------------------- mesh
def mesh_leg(lm: dict, *, batch: int, steps: int, serve: dict) -> dict:
    """Four devices: data-parallel and (data, model) ``ParallelWrapper``
    fits whose parameters and batch really live on four distinct devices,
    and a head-sharded ``GenerationEngine``."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    seq_len = lm["max_length"]
    ids, onehot = _batch(lm["vocab_size"], batch, seq_len)
    out = {}
    for shape in ((4,), (2, 2)):
        net = build_lm(**lm)
        scores = CollectScoresIterationListener()
        net.set_listeners(scores)
        pw = ParallelWrapper(net, mesh_shape=shape)
        pw.fit(ListDataSetIterator(features=ids, labels=onehot,
                                   batch_size=batch), epochs=steps)
        losses = [float(s) for _, s in scores.scores]
        check(len(losses) == steps and np.all(np.isfinite(losses))
              and losses[-1] < losses[0],
              f"mesh {shape}: losses {losses}")
        on = {d for leaf in jax.tree.leaves(net.params)
              for d in leaf.sharding.device_set}
        check(len(on) == 4, f"mesh {shape}: parameters live on "
                            f"{len(on)} device(s), not 4")
        feed = jax.device_put(ids, jax.sharding.NamedSharding(
            pw.mesh, jax.sharding.PartitionSpec("data")))
        check(len({s.device for s in feed.addressable_shards}) == 4,
              f"mesh {shape}: a batch does not spread over 4 devices")
        sharded = sum(not leaf.sharding.is_fully_replicated
                      for leaf in jax.tree.leaves(net.params))
        check((sharded > 0) == (len(shape) == 2),
              f"mesh {shape}: {sharded} model-sharded parameter leaves")
        out["x".join(map(str, shape))] = {
            "losses": losses, "model_sharded_leaves": sharded}
    mesh = make_mesh((1, 4), ("data", "model"), jax.devices()[:4])
    out["sharded_decode"] = serve_leg(
        build_lm(**lm), vocab_size=lm["vocab_size"], max_seq_len=seq_len,
        mesh=mesh, **serve)
    return out


# ------------------------------------------------------------------- main
def main() -> int:
    t_main = time.perf_counter()
    import jax
    import jaxlib

    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.ops import pallas_attention
    from deeplearning4j_tpu.ops.kernels import autotune
    from deeplearning4j_tpu.util.compile_cache import ensure_compile_cache
    from deeplearning4j_tpu.util.device import device_record

    cache_dir = ensure_compile_cache()
    device = device_record()
    if device["platform"] != "tpu":
        # jax falls back to the CPU with only a warning when the chip is
        # absent or held by another process — never carry on there
        print(f"chip_smoke: no TPU — JAX started on platform "
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} device(s)); this script runs on the chip "
              f"only (chiprun -- python3 chip_smoke.py)", file=sys.stderr)
        return 1
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except Exception:           # bundled differently: a label, not a gate
        libtpu_version = "unknown"
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version}")
    log(f"compile cache: {cache_dir}")
    T = LM["max_length"]
    log(f"attention tiles (BQ, BK) at T={T}: "
        f"{pallas_attention._blocks(T, causal=True)} causal (visited, masked,"
        f" total = {pallas_attention.tile_schedule(T, True)}), "
        f"{pallas_attention._blocks(T)} otherwise; "
        f"autotune file {autotune.cache_path()} "
        f"{'READ' if autotune.get_cache().loaded_from_file else 'not read'}")

    report = {"kernels": kernels_leg()}     # seconds: fail fast
    log(f"kernels: {json.dumps(report['kernels'])}")
    net = build_lm(**LM)
    report["train"] = train_leg(net, vocab_size=LM["vocab_size"], seq_len=T,
                                **TRAIN)
    log(f"train: {json.dumps(report['train'])}")
    check(report["train"]["train_step_custom_calls"] > 0,
          "the compiled train step carries no tpu_custom_call: the fused "
          "attention kernel is not in the program")
    mem = telemetry.device_memory_gauges()
    fallback = telemetry.get_registry().gauge_if_exists(
        "device0.live_arrays_fallback")
    check("device0.bytes_limit" in mem and fallback is None,
          f"device memory comes from the live-array fallback, not the "
          f"allocator: {sorted(mem)}")
    log(f"memory: {json.dumps(mem)}")

    report["serve"] = serve_leg(net, vocab_size=LM["vocab_size"],
                                max_seq_len=T, **SERVE)
    log(f"serve: {json.dumps(report['serve'])}")
    flash = {k: n for k, n in report["serve"]["prefill_custom_calls"].items()
             if int(k.split("xp")[1]) >= 256}
    check(flash and all(n > 0 for n in flash.values()),
          f"a flash-eligible prefill carries no tpu_custom_call: "
          f"{report['serve']['prefill_custom_calls']}")

    if device["count"] >= 4:
        report["mesh"] = mesh_leg(LM, batch=TRAIN["batch"],
                                  steps=TRAIN["steps"], serve=SERVE)
        log(f"mesh: {json.dumps(report['mesh'])}")
    else:
        log(f"mesh: skipped, {device['count']} device(s) (needs >= 4)")

    compiles = telemetry.xla_compile_count()
    hits = telemetry.xla_cache_hit_count()
    log(f"compiles={compiles} cache_hits={hits} "
        f"fresh={max(0, compiles - hits)} "
        f"wall_s={time.perf_counter() - t_main:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
