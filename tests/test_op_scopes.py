"""Names for the XLA fusions, the program's half (ISSUE 42): the graph's
forward, the decode specification's walk and the training step run under
``jax.named_scope``, so every operation's ``op_name`` metadata says which
vertex it belongs to, and a device trace can be split by it. The scopes
are metadata only: the program without them is the parent's, to the
byte."""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.models.zoo_extra import transformer_lm
from deeplearning4j_tpu.optimize.solver import train_step_math
from deeplearning4j_tpu.serving.generation.programs import (
    GenerationConfig, GenerationProgramSet)

# sha256 (first 16 hex digits) of the lowered text WITHOUT its locations
# of ``_train_step`` below, taken with this very function under this
# suite's conftest on the commit before the scopes (0d6c173)
PARENT_TRAIN_STEP = "51f622fea96073ef"


@pytest.fixture(scope="module")
def tiny():
    return transformer_lm(vocab_size=31, d_model=16, n_heads=2, n_blocks=1,
                          max_length=8, seed=3, token_input=True).init()


def _train_step(net):
    x = jnp.zeros((2, 8), jnp.int32)
    y = jax.nn.one_hot(jnp.zeros((2, 8), jnp.int32), 31)

    def step(params, state, opt, it, rng, x, y):
        return train_step_math(net, params, state, opt, it, rng, x, y)
    return jax.jit(step).lower(net.params, net.state, net.opt_state,
                               jnp.int32(0), jax.random.PRNGKey(0), x, y)


def _op_names(lowered, primitive):
    """The name stacks of the lowered program's ``primitive`` operations."""
    return sorted(set(re.findall(r'loc\("([^"]*/%s)"' % primitive,
                                 lowered.as_text(debug_info=True))))


def test_a_training_steps_matmuls_carry_their_vertex_names(tiny):
    dots = _op_names(_train_step(tiny), "dot_general")
    # forward and backward, each matmul under ``loss`` and its vertex
    for vertex in ("b0_attn", "b0_ff1", "b0_ff2", "head"):
        assert f"jit(step)/jvp(loss)/{vertex}/dot_general" in dots
        assert f"jit(step)/transpose(jvp(loss))/{vertex}/dot_general" in dots
    assert all("(loss)" in d for d in dots), dots
    # the updater's arithmetic is under its own scope, not the loss's
    adds = _op_names(_train_step(tiny), "add")
    assert any(a.startswith("jit(step)/updater/") for a in adds)


def test_the_scopes_are_metadata_the_program_is_the_parents(tiny):
    text = _train_step(tiny).as_text()
    assert "b0_attn" not in text and "updater" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_TRAIN_STEP


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_the_serving_programs_matmuls_carry_their_vertex_names(tiny, which):
    cfg = GenerationConfig(block_len=8, max_seq_len=8, decode_slots=2,
                           prompt_rungs=(8,), prefill_batches=(1,))
    ps = GenerationProgramSet(tiny, config=cfg)
    cache = ps._cache_spec()
    if which == "prefill":
        traced = ps._traced(ps._prefill_fn(), (2,), ps.params, ps.state,
                            cache, *ps._prefill_avals(1, 8))
    else:
        traced = ps._traced(ps._decode_fn(), (2,), ps.params, ps.state,
                            cache, *ps._decode_avals())
    dots = _op_names(traced.lower(), "dot_general")
    for vertex in ("b0_attn", "b0_ff1", "b0_ff2", "head"):
        assert any(f"/{vertex}/" in d for d in dots), (vertex, dots)
