"""Continuous batching engine (models/batch_engine.py): slot custody.

A full engine refuses a submit, a request past ``max_seq`` is never
admissible, freed slots admit again, and a stream stops AT its EOS
token. (Mid-flight joins against the serial reference:
tests/test_paged_engine.py.)
"""

import numpy as np
import pytest
import torch

from dora_tpu.models.hf.qwen2 import (
    Qwen2Config as OurCfg,  # noqa: F401 (import sanity)
)


@pytest.fixture(scope="module")
def tiny_qwen2(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rope_theta=10000.0,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Qwen2ForCausalLM(config).eval()
    path = tmp_path_factory.mktemp("qwen2-batch")
    model.save_pretrained(path, safe_serialization=True)
    return path


@pytest.fixture(scope="module")
def quantized(tiny_qwen2):
    import os

    from dora_tpu.models.hf import qwen2

    cfg, params = qwen2.load(tiny_qwen2, max_seq=64)
    os.environ["DORA_INT8_DECODE"] = "1"
    try:
        qparams = qwen2.quantize_decode(params, cfg)
    finally:
        os.environ.pop("DORA_INT8_DECODE", None)
    return cfg, qparams


def _engine(quantized, **kw):
    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    return qwen2.make_paged_engine(
        qparams, cfg, max_slots=2, page_size=8, chunk=8, **kw
    )


def test_slot_reuse_and_admission(quantized):
    engine = _engine(quantized)
    assert not engine.can_admit(60, 10)  # exceeds max_seq
    engine.submit("a", [1, 2, 3], 3)
    engine.submit("b", [4, 5], 3)
    assert engine.free_slots == 0
    with pytest.raises(RuntimeError):
        engine.submit("c", [6], 3)
    while engine.active:
        engine.step()
    # freed slots admit again and emit exactly max_new tokens
    assert engine.free_slots == 2
    engine.submit("c", [6, 7, 8, 9], 4)
    out = []
    while engine.active:
        out += engine.step()
    cfg = quantized[0]
    assert [rid for rid, _t, _d in out] == ["c"] * 4
    assert all(0 <= t < cfg.vocab for _r, t, _d in out)
    assert [d for _r, _t, d in out] == [False, False, False, True]


def test_eos_frees_slot(quantized):
    import jax.numpy as jnp

    from dora_tpu.models.hf import qwen2

    cfg, qparams = quantized
    prompt = [9, 8, 7]
    ref = np.asarray(
        qwen2.generate(qparams, cfg, jnp.asarray([prompt], jnp.int32), 8)
    )[0].tolist()
    eos = ref[3]  # pretend the 4th emitted token is EOS
    engine = _engine(quantized, eos=eos)
    engine.submit("x", prompt, 8)
    stream = []
    while engine.active:
        for _rid, token, _done in engine.step():
            stream.append(token)
    assert stream == ref[:4]  # stops AT the eos token
    assert engine.free_slots == 2
