"""The step program of a decoder WITHOUT a recurrent state is what it was
before the engine learnt to keep one (PR 37): the lowered text of
``_engine_step`` for a GPT-2-shaped and a LongCat-Flash decoder at a tiny
size, location metadata aside, hashes to what was recorded below by this
file's ``program_hash``. A later PR that means to change those programs
records new hashes and says so; one that does not, finds out here. PR 38
changed them on purpose: the program samples every row and takes the rows
whose token is still on the device from the step before (``prev``,
``feed``), and hands back one int32 array instead of the logits."""
import hashlib

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import _decoder_for
from paddle_tpu.serving import engine as E

PARENT = {                               # recorded in PR 38
    "gpt":
        "3d33c416def4085d721bc13457ca1c14e2b442edd6b0a43e4b753fe4ecfa1a60",
    "longcat":
        "4469b5e0ba898e67a0dfae10a41c662da4cc6a10b44d94b0df04ac415dad2b3a",
}


def _gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig.tiny())


def _longcat():
    from paddle_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                 LongcatFlashForCausalLM)
    return LongcatFlashForCausalLM(LongcatFlashConfig.tiny())


def program_hash(model, rows=16, slots=4, pages=24, table=6, bs=8):
    """sha256 of the step program's lowered text for ``model``'s decoder at
    fixed shapes (``as_text()`` prints no location)."""
    dec = _decoder_for(model)
    w = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
        dec.weights(model))
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    kp = sds((dec.cache_entries, pages, dec.n_kv, bs, dec.hd), jnp.float32)
    vp = sds(kp.shape[:-1] + (dec.v_dim,), jnp.float32)
    row = sds((rows,), i32)
    prev = sds((rows + dec.beside_width(rows),), i32)
    text = E._engine_step.lower(
        dec, None, E._argmax_rows, None, w, row, prev, row, row, row,
        sds((rows,), jnp.bool_), sds((slots, table), i32), kp, vp).as_text()
    assert "loc(" not in text
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name,build", [("gpt", _gpt), ("longcat", _longcat)])
def test_a_stateless_decoders_step_program_is_the_parents(name, build):
    paddle.seed(0)
    assert program_hash(build()) == PARENT[name]


def test_a_stateless_step_takes_no_argument_for_a_state():
    paddle.seed(0)
    model = _gpt()
    from paddle_tpu.serving import EngineConfig, ServingEngine
    eng = ServingEngine(model, EngineConfig(max_seqs=2, token_budget=8,
                                            block_size=4, num_blocks=16))
    assert eng._state == []
    assert eng._step_call.func is E._engine_step      # the parent's jit
    assert eng._step_call.args == (eng.dec, None, E._argmax_rows, None)
