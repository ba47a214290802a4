#!/usr/bin/env python3
"""What a launch of the decode step costs the host, split into its parts
(hand script, on the chip; ISSUE 45, PERF.md section 5).

    python benchmarks/tests/launch_cost_on_chip.py [--workload gpt2m-serve-chat] [--calls 300] [--tag parent]

Builds the cell's model and its decode program (16 slots for chat) and
times a call of the compiled step, one call at a time as the serving loop
makes them, with its per-row arguments brought

  (a) ``seven``   as seven numpy arrays (tokens, host_known, pos, tables,
                  active, temp, topk), a transfer each: PR 41 - PR 44's
                  launch;
  (b) ``packed``  as ONE int32 array ``[S, 6 + blocks_per_seq]``: this
                  PR's launch;
  (c) ``packed_no_table``  as one ``[S, 6]`` array with the table on the
                  device already: what a device-resident table would save;
  (d) ``on_device``  with every argument on the device already: what is
                  left of the call when nothing is transferred (the
                  parameters' pytree, the executable's own dispatch).

It runs on a tree whose decode program takes the seven arrays and on one
whose program takes the packed array: the form the tree lacks is wrapped
around the one it has (an unpacking, or a packing, of a few slices inside
the program), so the two trees can be compared form by form. Beside them:
one ``jax.device_put`` of a 64-byte and of a 4 KB array, and a call of an
empty program that takes the parameters' pytree (every leaf kept as an
argument) against one that takes two arrays: what the leaves alone cost.

``call_ms`` is the wall until the call has returned and the copy of its
first result to the host is asked for (what ``generation.dispatch``
covers), ``done_ms`` until that result is on the host. Prints one JSON
line a measurement and writes them to ``chiprun_out/launch_cost_<tag>.json``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COLS = 6    # tokens, host_known, pos, active, temp's bits, topk; then the table


def pack(xp, tokens, host_known, pos, tables, active, temp, topk):
    """The per-row arguments as one int32 array, on the host (``xp`` numpy)
    or inside a program (``xp`` jax.numpy)."""
    if xp.__name__ == "numpy":
        bits = temp.view(xp.int32)
    else:
        from jax import lax
        bits = lax.bitcast_convert_type(temp, xp.int32)
    cols = [tokens, host_known.astype(xp.int32), pos, active.astype(xp.int32),
            bits, topk]
    return xp.concatenate([c[:, None] for c in cols] + [tables], axis=1)


def unpack(packed, tables=None):
    import jax.numpy as jnp
    from jax import lax
    tokens, host_known, pos, active, temp, topk = (
        packed[:, c] for c in range(COLS))
    if tables is None:
        tables = packed[:, COLS:]
    return (tokens, host_known != 0, pos, tables, active != 0,
            lax.bitcast_convert_type(temp, jnp.float32), topk)


def p50(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2m-serve-chat")
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--calls", type=int, default=300)
    ap.add_argument("--tag", default="run")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import run as harness
    from deeplearning4j_tpu.serving.generation import programs
    from deeplearning4j_tpu.serving.generation.programs import (
        GenerationConfig, GenerationProgramSet)

    ctx, _, _ = harness.prepare(args.workload, args.seed, 0.0, False)
    cfg, fam, e = ctx["config"], ctx["family"], ctx["traffic"]["engine"]
    net = fam["build"].build(cfg, cfg["hyperparameters"], "serve")
    fam["build"].install(net, fam["weights"].make(cfg, args.seed, "serve"))
    ps = GenerationProgramSet(net, config=GenerationConfig(
        block_len=e["block_len"], max_seq_len=e["max_seq_len"],
        decode_slots=e["decode_slots"],
        prompt_rungs=tuple(e["prompt_rungs"]),
        prefill_batches=tuple(e["prefill_batches"])))
    S, mb = ps.config.decode_slots, ps.config.blocks_per_seq
    native = ps._decode_fn()
    takes_packed = len(inspect.signature(native).parameters) == 6
    leaves = len(jax.tree.leaves((ps.params, ps.state)))
    out = {"tag": args.tag, "device": ctx["device"], "slots": S,
           "blocks_per_seq": mb, "param_leaves": leaves,
           "tree_takes": "packed" if takes_packed else "seven",
           "calls": args.calls, "rows": []}

    def say(row):
        out["rows"].append(row)
        print("LAUNCH_COST " + json.dumps(row), flush=True)

    if takes_packed:
        def seven_fn(params, state, cache, tokens, prev, host_known, pos,
                     tables, active, key, temp, topk):
            return native(params, state, cache,
                          pack(jnp, tokens, host_known, pos, tables, active,
                               temp, topk), prev, key)

        packed_fn = native

        def split_fn(params, state, cache, small, tables, prev, key):
            return native(params, state, cache,
                          jnp.concatenate([small, tables], axis=1), prev, key)
    else:
        seven_fn = native

        def packed_fn(params, state, cache, packed, prev, key):
            tokens, host_known, pos, tables, active, temp, topk = \
                unpack(packed)
            return native(params, state, cache, tokens, prev, host_known,
                          pos, tables, active, key, temp, topk)

        def split_fn(params, state, cache, small, tables, prev, key):
            tokens, host_known, pos, tables, active, temp, topk = \
                unpack(small, tables)
            return native(params, state, cache, tokens, prev, host_known,
                          pos, tables, active, key, temp, topk)

    # the step's arguments: every slot live at a context of 200-700, its
    # table the pages it would hold
    rng = np.random.default_rng(args.seed)
    host = dict(
        tokens=rng.integers(1, 1000, S).astype(np.int32),
        host_known=np.zeros(S, np.bool_),
        pos=rng.integers(200, 700, S).astype(np.int32),
        tables=(1 + np.arange(S * mb, dtype=np.int32).reshape(S, mb)
                % (ps.config.num_blocks - 1)),
        active=np.ones(S, np.bool_),
        temp=np.zeros(S, np.float32), topk=np.zeros(S, np.int32))
    order = ("tokens", "host_known", "pos", "tables", "active", "temp",
             "topk")
    packed = pack(np, *(host[k] for k in order))
    if takes_packed:
        # the script's layout is the program's
        assert np.array_equal(packed, programs.pack_decode(
            *(host[k] for k in order)))
    prev = jnp.zeros(S + ps.stats_len, jnp.int32)
    sds = jax.ShapeDtypeStruct
    spec = lambda a: sds(a.shape, a.dtype)
    cache_spec, key_spec = ps._cache_spec(), ps._key_spec()

    def compile_(fn, *host_avals):
        t = time.perf_counter()
        exe = ps._aot(fn, (2,), ps.params, ps.state, cache_spec, *host_avals)
        return exe, time.perf_counter() - t

    def run(name, exe, make_args, host_arrays):
        """``make_args(prev, key)`` -> the call's arguments behind
        (params, state, cache); the first result feeds the next call's
        ``prev`` on the device, as in the loop."""
        cache, key, last = ps.make_cache(), ps.fresh_key(), prev
        call_ms, done_ms = [], []
        for i in range(args.calls + 20):
            a = make_args(last, key)
            t0 = time.perf_counter()
            first, cache, key = exe(ps.params, ps.state, cache, *a)
            first.copy_to_host_async()
            t1 = time.perf_counter()
            np.asarray(first)
            t2 = time.perf_counter()
            last = first
            if i >= 20:
                call_ms.append((t1 - t0) * 1e3)
                done_ms.append((t2 - t0) * 1e3)
        n, b = len(host_arrays), sum(x.nbytes for x in host_arrays)
        say({"what": name, "host_arrays": n, "host_bytes": b,
             "call_ms_p50": p50(call_ms), "call_ms_p10": sorted(call_ms)[
                 len(call_ms) // 10], "call_ms_p90": sorted(call_ms)[
                 len(call_ms) * 9 // 10], "done_ms_p50": p50(done_ms)})

    def fresh(src):
        # a new array a call, as the loop hands the launch (untimed)
        return {k: v.copy() for k, v in src.items()}

    exe7, s7 = compile_(
        seven_fn, spec(host["tokens"]), spec(prev), spec(host["host_known"]),
        spec(host["pos"]), spec(host["tables"]), spec(host["active"]),
        key_spec, spec(host["temp"]), spec(host["topk"]))
    exe1, s1 = compile_(packed_fn, spec(packed), spec(prev), key_spec)
    exe2, s2 = compile_(split_fn, sds((S, COLS), np.int32),
                        spec(host["tables"]), spec(prev), key_spec)
    say({"what": "compile_s", "seven": s7, "packed": s1,
         "packed_no_table": s2})

    def seven_call(src):
        def make(last, key):
            a = fresh(src) if src is host else src
            return (a["tokens"], last, a["host_known"], a["pos"],
                    a["tables"], a["active"], key, a["temp"], a["topk"])
        return make

    dev = {k: jax.device_put(v) for k, v in host.items()}
    dev_packed = jax.device_put(packed)
    jax.block_until_ready((dev, dev_packed))
    # every form twice, in turn: a drift of the machine shows
    for rep in range(2):
        run("seven", exe7, seven_call(host), list(host.values()))
        run("packed", exe1, lambda last, key: (packed.copy(), last, key),
            [packed])
        run("packed_no_table", exe2,
            lambda last, key: (packed[:, :COLS].copy(), dev["tables"], last,
                               key), [packed[:, :COLS]])
        run("on_device", exe1 if takes_packed else exe7,
            (lambda last, key: (dev_packed, last, key)) if takes_packed
            else seven_call(dev), [])

    # one transfer alone
    for name, a in (("device_put_64B", np.zeros(16, np.int32)),
                    ("device_put_4KB", np.zeros((16, 64), np.int32)),
                    ("device_put_packed", packed)):
        put_ms, done_ms = [], []
        for i in range(args.calls + 20):
            b = a.copy()
            t0 = time.perf_counter()
            d = jax.device_put(b)
            t1 = time.perf_counter()
            d.block_until_ready()
            t2 = time.perf_counter()
            if i >= 20:
                put_ms.append((t1 - t0) * 1e3)
                done_ms.append((t2 - t0) * 1e3)
        say({"what": name, "bytes": a.nbytes, "call_ms_p50": p50(put_ms),
             "done_ms_p50": p50(done_ms)})

    # the parameters' leaves alone: an empty program that keeps every leaf
    # as an argument against one that takes two arrays
    x = jax.device_put(np.zeros(16, np.int32))
    with_leaves = jax.jit(lambda params, state, x: x + 1,
                          keep_unused=True).lower(
        ps.params, ps.state, x).compile()
    two = jax.jit(lambda y, x: x + 1, keep_unused=True).lower(x, x).compile()
    for name, call in (("empty_program_param_leaves",
                        lambda: with_leaves(ps.params, ps.state, x)),
                       ("empty_program_two_args", lambda: two(x, x))):
        call_ms = []
        for i in range(args.calls + 20):
            t0 = time.perf_counter()
            r = call()
            t1 = time.perf_counter()
            r.block_until_ready()
            if i >= 20:
                call_ms.append((t1 - t0) * 1e3)
        say({"what": name, "leaves": leaves if "leaves" in name else 2,
             "call_ms_p50": p50(call_ms)})

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"launch_cost_{args.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
