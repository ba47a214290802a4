"""chip_smoke.py's legs at toy width on the CPU, so the script cannot rot
between chip runs. The script's own ``__main__`` has no size or platform
switch — that path is exercised only for its refusal to run off the chip."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TOY_LM = dict(vocab_size=64, d_model=16, n_heads=2, n_blocks=1,
              max_length=64, dtype="float32")
TOY_SERVE = dict(prompt_rungs=(32, 64), block_len=8, decode_slots=2,
                 prefill_batches=(1,), max_tokens=4,
                 # f32 on the CPU: every emitted token IS the reference
                 # forward's argmax (the token-identity pin of
                 # test_generation.py, here through HTTP)
                 near_argmax=1.0)


def test_train_and_serve_legs_at_toy_width():
    net = chip_smoke.build_lm(**TOY_LM)
    train = chip_smoke.train_leg(net, vocab_size=64, seq_len=64, batch=4,
                                 steps=3, window=2)
    assert len(train["per_step_losses"]) == 3
    assert len(train["windowed_losses"]) == 4
    assert train["train_step_custom_calls"] == 0      # CPU: no Mosaic call
    serve = chip_smoke.serve_leg(net, vocab_size=64, max_seq_len=64,
                                 **TOY_SERVE)
    assert serve["requests"] == TOY_SERVE["decode_slots"] + 2 + 3
    assert serve["argmax_tokens"] == serve["tokens"] == \
        serve["requests"] * TOY_SERVE["max_tokens"]
    assert serve["prefix"]["hits"] >= 1 and serve["prefix"]["cow_copies"] >= 1
    assert set(serve["prefill_custom_calls"]) == {"b1xp32", "b1xp64"}
    # the reference check has teeth: a reply that is not what the model
    # would say scores far below any near-argmax bound
    prompt = [3, 1, 4, 1, 5]
    buf = np.zeros((1, 64), np.int32)
    buf[0, :5] = prompt
    row = np.asarray(net.output(buf))[0, 4]
    good, bad = chip_smoke.reference_shares(
        net, [(prompt, [int(row.argmax())]), (prompt, [int(row.argmin())])],
        capacity=64)
    assert good == 1.0 and bad < 0.9


@pytest.mark.slow
def test_mesh_leg_at_toy_width():
    """The four-device leg on four of the virtual CPU devices (slow lane:
    three more model compiles; the chip run is its real check)."""
    lm = dict(vocab_size=512, d_model=16, n_heads=4, n_blocks=1,
              max_length=64, dtype="bfloat16")
    out = chip_smoke.mesh_leg(lm, batch=8, steps=3,
                              serve={**TOY_SERVE, "near_argmax": 0.5})
    assert out["4"]["model_sharded_leaves"] == 0
    assert out["2x2"]["model_sharded_leaves"] > 0
    assert out["sharded_decode"]["requests"] == 7


def test_main_refuses_to_run_off_the_chip(tmp_path):
    """No CPU mode: off the chip the script exits non-zero in seconds,
    names the platform it found on stderr and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    run = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=ROOT)
    assert run.returncode != 0
    assert "no TPU" in run.stderr and "'cpu'" in run.stderr
    assert run.stdout.strip() == ""
