"""Numeric parity of the pretrained-checkpoint serving path against the
upstream torch/transformers implementations.

No network: tiny checkpoints are fabricated locally with transformers
(random weights, real architectures), saved as safetensors, loaded through
``dora_tpu.models.hf``, and the JAX forward is compared against the torch
forward. This proves the weight mapping + compute graph are exact — with
real downloaded weights the models produce the reference's outputs.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


# ---------------------------------------------------------------------------
# Qwen2 causal LM
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen2_checkpoint(tmp_path_factory):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    config = Qwen2Config(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Qwen2ForCausalLM(config).eval()
    path = tmp_path_factory.mktemp("qwen2-tiny")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def test_qwen2_logits_match_torch(qwen2_checkpoint):
    from dora_tpu.models.hf import qwen2

    path, torch_model = qwen2_checkpoint
    cfg, params = qwen2.load(path, max_seq=64)
    assert cfg.dim == 64 and cfg.kv_heads == 2

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab, size=(2, 11)).astype(np.int32)
    ours = np.asarray(qwen2.forward(params, cfg, tokens))
    with torch.no_grad():
        theirs = torch_model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_qwen2_greedy_generation_matches_torch(qwen2_checkpoint):
    from dora_tpu.models.hf import qwen2

    path, torch_model = qwen2_checkpoint
    cfg, params = qwen2.load(path, max_seq=64)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, size=(1, 7)).astype(np.int32)

    ours = np.asarray(qwen2.generate(params, cfg, prompt, 12))
    with torch.no_grad():
        theirs = torch_model.generate(
            torch.tensor(prompt, dtype=torch.long),
            max_new_tokens=12,
            do_sample=False,
            use_cache=True,
            pad_token_id=0,
        ).numpy()[:, prompt.shape[1] :]
    np.testing.assert_array_equal(ours, theirs)


def test_qwen2_tied_embeddings(tmp_path):
    from transformers import Qwen2Config, Qwen2ForCausalLM

    from dora_tpu.models.hf import qwen2

    config = Qwen2Config(
        vocab_size=128,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=1,
        num_attention_heads=2,
        num_key_value_heads=2,
        max_position_embeddings=64,
        tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(3)
    model = Qwen2ForCausalLM(config).eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg, params = qwen2.load(tmp_path, max_seq=32)
    assert cfg.tie_embeddings and "lm_head" not in params
    tokens = np.arange(10, dtype=np.int32)[None]
    ours = np.asarray(qwen2.forward(params, cfg, tokens))
    with torch.no_grad():
        theirs = model(torch.tensor(tokens, dtype=torch.long)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# Whisper ASR
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def whisper_checkpoint(tmp_path_factory):
    from transformers import WhisperConfig, WhisperForConditionalGeneration

    config = WhisperConfig(
        vocab_size=200,
        num_mel_bins=32,
        d_model=64,
        encoder_layers=2,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=128,
        decoder_ffn_dim=128,
        max_source_positions=50,
        max_target_positions=32,
        decoder_start_token_id=3,
        eos_token_id=2,
        bos_token_id=1,
        pad_token_id=0,
        suppress_tokens=[],
        begin_suppress_tokens=[],
        attn_implementation="eager",
    )
    torch.manual_seed(4)
    model = WhisperForConditionalGeneration(config).eval()
    path = tmp_path_factory.mktemp("whisper-tiny")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def test_whisper_encoder_matches_torch(whisper_checkpoint):
    from dora_tpu.models.hf import whisper

    path, torch_model = whisper_checkpoint
    cfg, params = whisper.load(path)
    rng = np.random.default_rng(5)
    feats = rng.normal(size=(2, cfg.n_mels, 2 * cfg.max_source)).astype(np.float32)

    ours = np.asarray(whisper.encode(params, cfg, feats))
    with torch.no_grad():
        theirs = (
            torch_model.model.encoder(torch.tensor(feats)).last_hidden_state.numpy()
        )
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_whisper_decoder_logits_match_torch(whisper_checkpoint):
    from dora_tpu.models.hf import whisper

    path, torch_model = whisper_checkpoint
    cfg, params = whisper.load(path)
    rng = np.random.default_rng(6)
    feats = rng.normal(size=(1, cfg.n_mels, 2 * cfg.max_source)).astype(np.float32)
    dec_ids = rng.integers(0, cfg.vocab, size=(1, 9)).astype(np.int32)

    enc = whisper.encode(params, cfg, feats)
    ours = np.asarray(whisper.decoder_logits(params, cfg, enc, dec_ids))
    with torch.no_grad():
        theirs = torch_model(
            input_features=torch.tensor(feats),
            decoder_input_ids=torch.tensor(dec_ids, dtype=torch.long),
        ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_whisper_greedy_matches_torch(whisper_checkpoint):
    from dora_tpu.models.hf import whisper

    path, torch_model = whisper_checkpoint
    cfg, params = whisper.load(path)
    rng = np.random.default_rng(7)
    feats = rng.normal(size=(1, cfg.n_mels, 2 * cfg.max_source)).astype(np.float32)

    ours = np.asarray(whisper.transcribe_tokens(params, cfg, feats, 10))
    with torch.no_grad():
        theirs = torch_model.generate(
            input_features=torch.tensor(feats),
            max_new_tokens=10,
            do_sample=False,
            use_cache=True,
        ).numpy()
    # HF prepends decoder_start_token; compare the generated continuation.
    theirs = theirs[:, 1 : 1 + ours.shape[1]]
    np.testing.assert_array_equal(ours[:, : theirs.shape[1]], theirs)


def test_whisper_log_mel_matches_feature_extractor():
    from transformers import WhisperFeatureExtractor

    from dora_tpu.models.hf import whisper

    fe = WhisperFeatureExtractor(feature_size=80)
    rng = np.random.default_rng(8)
    audio = (rng.normal(size=16000 * 2) * 0.1).astype(np.float32)

    theirs = fe(audio, sampling_rate=16000, return_tensors="np").input_features
    ours = whisper.log_mel_features(audio[None], n_mels=80)
    np.testing.assert_allclose(ours, theirs, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# byte-level BPE tokenizer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained_bpe(tmp_path_factory):
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    corpus = [
        "the quick brown fox jumps over the lazy dog",
        "pack my box with five dozen liquor jugs",
        "sphinx of black quartz, judge my vow",
        "Hello, world! Numbers: 123 456.789 — and unicode: héllo über 日本語",
        "def main() -> int:\n    return 0\n",
    ] * 50
    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=400,
        special_tokens=["<|endoftext|>", "<|im_start|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(corpus, trainer)
    path = tmp_path_factory.mktemp("bpe") / "tokenizer.json"
    tok.save(str(path))
    return path, tok


@pytest.mark.parametrize(
    "text",
    [
        "the quick brown fox",
        "Hello, world! 123",
        "unicode héllo über 日本語 test",
        "  leading spaces and\nnewlines\t tabs",
        "<|endoftext|>wrapped<|im_start|> specials <|endoftext|>",
        "",
    ],
)
def test_bpe_encode_matches_tokenizers_lib(trained_bpe, text):
    from dora_tpu.models.tokenizer import BPETokenizer

    path, upstream = trained_bpe
    ours = BPETokenizer.from_file(path)
    assert ours.encode(text) == upstream.encode(text).ids


def test_bpe_decode_roundtrip(trained_bpe):
    from dora_tpu.models.tokenizer import BPETokenizer

    path, upstream = trained_bpe
    ours = BPETokenizer.from_file(path)
    text = "the quick brown fox says héllo 123"
    ids = ours.encode(text)
    assert ours.decode(ids) == text
    assert upstream.decode(ids) == text


def test_bpe_qwen2_style_pretokenizer(tmp_path):
    """Qwen2-family tokenizer.json uses Sequence[Split(cl100k regex),
    ByteLevel(use_regex=False)] — the split pattern must be honored."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
    from tokenizers import Regex

    from dora_tpu.models.tokenizer import BPETokenizer

    cl100k = (
        r"""(?i:'s|'t|'re|'ve|'m|'ll|'d)|[^\r\n\p{L}\p{N}]?\p{L}+|\p{N}"""
        r"""| ?[^\s\p{L}\p{N}]+[\r\n]*|\s*[\r\n]+|\s+(?!\S)|\s+"""
    )
    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.Sequence(
        [
            pre_tokenizers.Split(Regex(cl100k), behavior="isolated"),
            pre_tokenizers.ByteLevel(add_prefix_space=False, use_regex=False),
        ]
    )
    tok.decoder = decoders.ByteLevel()
    corpus = [
        "items.append(value) I'M SURE it's fine 12345",
        "def f(x):\n    return x.append(1)\n",
    ] * 100
    trainer = trainers.BpeTrainer(
        vocab_size=320,
        special_tokens=["<|endoftext|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(corpus, trainer)
    path = tmp_path / "tokenizer.json"
    tok.save(str(path))

    ours = BPETokenizer.from_file(path)
    for text in ["items.append(42)", "I'M SURE it's", "x 12345\n\nnext"]:
        assert ours.encode(text) == tok.encode(text).ids, text


def test_generate_bounds_guard(qwen2_checkpoint):
    from dora_tpu.models.hf import qwen2

    path, _ = qwen2_checkpoint
    cfg, params = qwen2.load(path, max_seq=16)
    prompt = np.zeros((1, 10), np.int32)
    with pytest.raises(ValueError, match="max_seq"):
        qwen2.generate(params, cfg, prompt, 10)


# ---------------------------------------------------------------------------
# Qwen2-VL (vision tower + M-RoPE)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen2vl_checkpoint(tmp_path_factory):
    from transformers import Qwen2VLConfig, Qwen2VLForConditionalGeneration

    config = Qwen2VLConfig(
        vocab_size=300,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        tie_word_embeddings=False,
        rope_scaling={"type": "mrope", "mrope_section": [2, 3, 3]},
        image_token_id=290,
        video_token_id=291,
        vision_start_token_id=292,
        vision_end_token_id=293,
        vision_config={
            "depth": 2,
            "embed_dim": 32,
            "num_heads": 2,
            "mlp_ratio": 2,
            "patch_size": 4,
            "temporal_patch_size": 2,
            "spatial_merge_size": 2,
            "in_channels": 3,
            "hidden_size": 64,
        },
        attn_implementation="eager",
    )
    torch.manual_seed(9)
    model = Qwen2VLForConditionalGeneration(config).eval()
    path = tmp_path_factory.mktemp("qwen2vl-tiny")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def _vlm_inputs(cfg, rng, text_len_before=3, text_len_after=4):
    """input_ids with a <|vision_start|><|image_pad|>*N run + patches."""
    grid_thw = np.array([[1, 4, 4]])  # 16 patches -> 4 merged tokens
    n_patches = int(grid_thw.prod())
    n_merged = n_patches // 4
    patch_dim = 3 * 2 * 4 * 4  # C * temporal * ps * ps
    pixel_values = rng.normal(size=(n_patches, patch_dim)).astype(np.float32)
    ids = (
        list(rng.integers(0, 280, size=text_len_before))
        + [292]  # vision_start
        + [290] * n_merged  # image_pad
        + list(rng.integers(0, 280, size=text_len_after))
    )
    return np.array([ids], dtype=np.int64), pixel_values, grid_thw


def test_qwen2vl_vision_tower_matches_torch(qwen2vl_checkpoint):
    from dora_tpu.models.hf import qwen2_vl

    path, torch_model = qwen2vl_checkpoint
    cfg, params = qwen2_vl.load(path, max_seq=128)
    rng = np.random.default_rng(10)
    _, pixel_values, grid_thw = _vlm_inputs(cfg, rng)

    ours = np.asarray(qwen2_vl.encode_images(params, cfg, pixel_values, grid_thw))
    with torch.no_grad():
        theirs = torch_model.model.visual(
            torch.tensor(pixel_values), grid_thw=torch.tensor(grid_thw)
        ).numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-3)


def test_qwen2vl_logits_match_torch(qwen2vl_checkpoint):
    from dora_tpu.models.hf import qwen2_vl

    path, torch_model = qwen2vl_checkpoint
    cfg, params = qwen2_vl.load(path, max_seq=128)
    rng = np.random.default_rng(11)
    input_ids, pixel_values, grid_thw = _vlm_inputs(cfg, rng)

    feats = qwen2_vl.encode_images(params, cfg, pixel_values, grid_thw)
    position_ids, _ = qwen2_vl.rope_index(cfg, input_ids, grid_thw)
    ours = np.asarray(
        qwen2_vl.forward(
            params, cfg, np.asarray(input_ids, np.int32), feats, position_ids
        )
    )
    with torch.no_grad():
        theirs = torch_model(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(pixel_values),
            image_grid_thw=torch.tensor(grid_thw),
        ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_qwen2vl_greedy_matches_torch(qwen2vl_checkpoint):
    from dora_tpu.models.hf import qwen2_vl

    path, torch_model = qwen2vl_checkpoint
    cfg, params = qwen2_vl.load(path, max_seq=128)
    rng = np.random.default_rng(12)
    input_ids, pixel_values, grid_thw = _vlm_inputs(cfg, rng)

    ours = np.asarray(
        qwen2_vl.generate(params, cfg, input_ids, pixel_values, grid_thw, 8)
    )
    with torch.no_grad():
        theirs = torch_model.generate(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(pixel_values),
            image_grid_thw=torch.tensor(grid_thw),
            max_new_tokens=8,
            do_sample=False,
            use_cache=True,
            pad_token_id=0,
        ).numpy()[:, input_ids.shape[1] :]
    np.testing.assert_array_equal(ours, theirs)


def test_qwen2vl_text_only_matches_qwen2_rope(qwen2vl_checkpoint):
    """Without images, M-RoPE degenerates to standard RoPE."""
    from dora_tpu.models.hf import qwen2_vl

    path, torch_model = qwen2vl_checkpoint
    cfg, params = qwen2_vl.load(path, max_seq=128)
    rng = np.random.default_rng(13)
    ids = rng.integers(0, 280, size=(1, 9)).astype(np.int64)

    position_ids, _ = qwen2_vl.rope_index(cfg, ids, None)
    ours = np.asarray(
        qwen2_vl.forward(params, cfg, ids.astype(np.int32), None, position_ids)
    )
    with torch.no_grad():
        theirs = torch_model(input_ids=torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_qwen2vl_preprocess_matches_hf_processor():
    """In-graph patchify/normalize parity with Qwen2VLImageProcessor
    (resize disabled: resampling kernels differ by design; geometry,
    normalization, and the window-major patch layout must be exact)."""
    from transformers.models.qwen2_vl.image_processing_qwen2_vl import (
        Qwen2VLImageProcessor,
    )

    from dora_tpu.models.hf import qwen2_vl

    rng = np.random.default_rng(14)
    image = rng.integers(0, 256, size=(32, 32, 3)).astype(np.uint8)
    proc = Qwen2VLImageProcessor(
        do_resize=False,
        patch_size=4,
        temporal_patch_size=2,
        merge_size=2,
    )
    out = proc(images=[image], return_tensors="np")
    theirs = out["pixel_values"]
    assert tuple(out["image_grid_thw"][0]) == (1, 8, 8)

    vcfg = qwen2_vl.VisionConfig(
        depth=1, embed_dim=8, heads=1, mlp_ratio=1.0, patch_size=4,
        temporal_patch_size=2, spatial_merge_size=2, in_channels=3, out_dim=8,
    )
    ours = np.asarray(qwen2_vl.preprocess_image(jnp.asarray(image), vcfg, 32, 32))
    np.testing.assert_allclose(ours, theirs, atol=1e-5, rtol=1e-4)


def test_vlm_operator_serves_hf_checkpoint(qwen2vl_checkpoint, monkeypatch):
    """The node-hub VLM operator serves a real checkpoint end to end:
    image in, greedy tokens out, matching the torch generate."""
    from dora_tpu.models.hf import qwen2_vl
    from dora_tpu.nodehub import ops

    path, torch_model = qwen2vl_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "6")
    monkeypatch.setenv("DORA_MAX_SEQ", "128")
    monkeypatch.setenv("IMAGE_HEIGHT", "16")
    monkeypatch.setenv("IMAGE_WIDTH", "16")
    monkeypatch.setenv("DORA_PROMPT", "hi")

    op = ops.make_vlm()
    rng = np.random.default_rng(15)
    image = rng.integers(0, 256, size=(16, 16, 3)).astype(np.uint8)
    _, out = op.step(op.init_state, {"image": jnp.asarray(image)})
    tokens = np.asarray(out["tokens"])
    assert tokens.shape == (6,)

    # Torch reference on the identical preprocessed inputs.
    cfg, params = qwen2_vl.load(path, max_seq=128)
    target_h, target_w = qwen2_vl.smart_resize(16, 16, factor=8)
    patches = np.asarray(
        qwen2_vl.preprocess_image(
            jnp.asarray(image).astype(jnp.float32) / 255.0,
            cfg.vision, target_h, target_w,
        )
    )
    from dora_tpu.models import tokenizer as byte_tok

    input_ids = qwen2_vl.build_prompt_ids(
        cfg, [t % cfg.vocab for t in byte_tok.encode("hi")], target_h, target_w
    )
    ps = cfg.vision.patch_size
    grid = np.array([[1, target_h // ps, target_w // ps]])
    with torch.no_grad():
        theirs = torch_model.generate(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(patches),
            image_grid_thw=torch.tensor(grid),
            max_new_tokens=6,
            do_sample=False,
            pad_token_id=0,
        ).numpy()[:, input_ids.shape[1] :]
    np.testing.assert_array_equal(tokens[None], theirs)


# ---------------------------------------------------------------------------
# YOLOS object detection
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def yolos_checkpoint(tmp_path_factory):
    from transformers import YolosConfig, YolosForObjectDetection

    config = YolosConfig(
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=2,
        intermediate_size=64,
        image_size=[32, 48],
        patch_size=8,
        num_detection_tokens=5,
        num_labels=7,
        qkv_bias=True,
        attn_implementation="eager",
    )
    torch.manual_seed(17)
    model = YolosForObjectDetection(config).eval()
    path = tmp_path_factory.mktemp("yolos-tiny")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def test_yolos_logits_and_boxes_match_torch(yolos_checkpoint):
    from dora_tpu.models.hf import yolos

    path, torch_model = yolos_checkpoint
    cfg, params = yolos.load(path)
    assert cfg.image_size == (32, 48) and cfg.n_det == 5

    rng = np.random.default_rng(18)
    pixels = rng.normal(size=(2, 3, 32, 48)).astype(np.float32)
    logits, boxes = yolos.forward(params, cfg, yolos.nchw(pixels))
    with torch.no_grad():
        out = torch_model(pixel_values=torch.tensor(pixels))
    np.testing.assert_allclose(
        np.asarray(logits), out.logits.numpy(), atol=3e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(boxes), out.pred_boxes.numpy(), atol=3e-4, rtol=2e-3
    )


def test_yolos_detect_matches_hf_postprocess(yolos_checkpoint):
    from transformers.models.yolos.image_processing_yolos import (
        YolosImageProcessor,
    )

    from dora_tpu.models.hf import yolos

    path, torch_model = yolos_checkpoint
    cfg, params = yolos.load(path)
    rng = np.random.default_rng(19)
    pixels = rng.normal(size=(1, 3, 32, 48)).astype(np.float32)

    ours = yolos.detect(params, cfg, yolos.nchw(pixels), threshold=0.0, top_k=5)
    with torch.no_grad():
        out = torch_model(pixel_values=torch.tensor(pixels))
    proc = YolosImageProcessor()
    hf = proc.post_process_object_detection(
        out, threshold=0.0, target_sizes=[(1.0, 1.0)]
    )[0]
    order = np.argsort(-hf["scores"].numpy(), kind="stable")
    np.testing.assert_allclose(
        np.asarray(ours["scores"][0]), hf["scores"].numpy()[order],
        atol=1e-4, rtol=1e-3,
    )
    np.testing.assert_array_equal(
        np.asarray(ours["classes"][0]), hf["labels"].numpy()[order]
    )
    np.testing.assert_allclose(
        np.asarray(ours["boxes"][0]), hf["boxes"].numpy()[order],
        atol=3e-4, rtol=2e-3,
    )


def test_detector_operator_serves_hf_checkpoint(yolos_checkpoint, monkeypatch):
    from dora_tpu.nodehub import ops

    path, _ = yolos_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    monkeypatch.setenv("DORA_DETECT_THRESHOLD", "0.0")

    op = ops.make_detector()
    rng = np.random.default_rng(20)
    image = rng.integers(0, 256, size=(32, 48, 3)).astype(np.uint8)
    _, out = op.step(op.init_state, {"image": jnp.asarray(image)})
    assert np.asarray(out["boxes"]).shape == (5, 4)
    assert np.asarray(out["scores"]).shape == (5,)
    assert np.asarray(out["classes"]).shape == (5,)


def test_asr_operator_serves_hf_checkpoint(whisper_checkpoint, monkeypatch):
    from dora_tpu.nodehub import ops

    path, _ = whisper_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "5")

    op = ops.make_asr()
    rng = np.random.default_rng(16)
    audio = (rng.normal(size=1600) * 0.1).astype(np.float32)
    _, out = op.step(op.init_state, {"audio": jnp.asarray(audio)})
    assert np.asarray(out["tokens"]).shape == (5,)


# ---------------------------------------------------------------------------
# Marian / Opus-MT translation
# ---------------------------------------------------------------------------


def _tiny_spm(tmp_path, name: str) -> None:
    """Fabricate a tiny sentencepiece unigram model file (ModelProto)."""
    from dora_tpu.models.spm import (
        TYPE_CONTROL,
        TYPE_NORMAL,
        TYPE_UNKNOWN,
        build_model_proto,
    )

    pieces = [
        ("<unk>", 0.0, TYPE_UNKNOWN),
        ("<s>", 0.0, TYPE_CONTROL),
        ("</s>", 0.0, TYPE_CONTROL),
        ("▁", -4.0, TYPE_NORMAL),
        ("▁the", -1.0, TYPE_NORMAL),
        ("▁cat", -2.0, TYPE_NORMAL),
        ("▁dog", -2.2, TYPE_NORMAL),
        ("▁sat", -2.4, TYPE_NORMAL),
        ("s", -3.0, TYPE_NORMAL),
        ("a", -3.1, TYPE_NORMAL),
        ("t", -3.2, TYPE_NORMAL),
        ("c", -3.3, TYPE_NORMAL),
        ("▁ca", -3.4, TYPE_NORMAL),
    ]
    (tmp_path / name).write_bytes(build_model_proto(pieces))


@pytest.fixture(scope="module")
def marian_checkpoint(tmp_path_factory):
    import json

    from transformers import MarianConfig, MarianMTModel

    config = MarianConfig(
        vocab_size=97,
        d_model=32,
        encoder_layers=2,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=64,
        decoder_ffn_dim=64,
        max_position_embeddings=64,
        scale_embedding=True,
        activation_function="swish",
        pad_token_id=96,
        eos_token_id=0,
        decoder_start_token_id=96,
    )
    torch.manual_seed(7)
    model = MarianMTModel(config).eval()
    path = tmp_path_factory.mktemp("marian")
    model.save_pretrained(path, safe_serialization=True)
    # Tokenizer files: vocab.json maps every fabricated spm piece + specials.
    _tiny_spm(path, "source.spm")
    _tiny_spm(path, "target.spm")
    from dora_tpu.models.spm import parse_model

    vocab = {"<unk>": 1, "</s>": 0, "<pad>": 96}
    for piece, _, _ in parse_model(path / "source.spm"):
        if piece not in vocab:
            vocab[piece] = len(vocab) + 1
    (path / "vocab.json").write_text(json.dumps(vocab))
    return path, model, config


def test_marian_logits_match_torch(marian_checkpoint):
    from dora_tpu.models.hf import marian

    path, model, _ = marian_checkpoint
    cfg, params = marian.load(path, max_tokens=12)
    rng = np.random.default_rng(3)
    src = rng.integers(1, 90, (2, 7)).astype(np.int32)
    dec = rng.integers(1, 90, (2, 5)).astype(np.int32)
    dec[:, 0] = cfg.decoder_start_token
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(src, dtype=torch.long),
            decoder_input_ids=torch.tensor(dec, dtype=torch.long),
        ).logits.numpy()
    ours = np.asarray(marian.forward(params, cfg, src, dec))
    np.testing.assert_allclose(ours, ref, atol=2e-5, rtol=2e-5)


def test_marian_greedy_matches_torch(marian_checkpoint):
    """Greedy decode with right-padded + masked source matches torch
    generate(num_beams=1) up to (and including) the first EOS."""
    from dora_tpu.models.hf import marian

    path, model, _ = marian_checkpoint
    cfg, params = marian.load(path, max_tokens=10)
    src_real = np.array([[5, 9, 23, 41, 2, 0]], np.int32)
    pad_to = 10
    src = np.full((1, pad_to), cfg.pad_token, np.int32)
    src[0, : src_real.shape[1]] = src_real
    mask_np = np.arange(pad_to)[None, :] < src_real.shape[1]
    with torch.no_grad():
        ref = model.generate(
            torch.tensor(src, dtype=torch.long),
            attention_mask=torch.tensor(mask_np, dtype=torch.long),
            max_new_tokens=8,
            num_beams=1,
            do_sample=False,
        ).numpy()[0][1:]  # strip decoder_start
    ours = np.asarray(
        marian.translate(params, cfg, src, 8, src_mask=jnp.asarray(mask_np))
    )[0]

    def upto_eos(ids):
        out = []
        for t in ids:
            out.append(int(t))
            if int(t) == cfg.eos_token:
                break
        return out

    assert upto_eos(ours) == upto_eos(ref)


def test_spm_viterbi_segmentation():
    """Unigram Viterbi picks the max-score segmentation, not greedy-longest:
    with score(▁ca)+score(t) = -6.6 < score(▁cat) = -2.0 the whole-word
    piece wins; unknown chars fall back to single-char unk pieces."""
    from dora_tpu.models.spm import SentencePieceModel, parse_model, build_model_proto
    from dora_tpu.models.spm import TYPE_NORMAL, TYPE_UNKNOWN

    pieces = [
        ("<unk>", 0.0, TYPE_UNKNOWN),
        ("▁", -4.0, TYPE_NORMAL),
        ("▁the", -1.0, TYPE_NORMAL),
        ("▁cat", -2.0, TYPE_NORMAL),
        ("▁ca", -3.4, TYPE_NORMAL),
        ("t", -3.2, TYPE_NORMAL),
        ("s", -3.0, TYPE_NORMAL),
    ]
    model = SentencePieceModel(pieces)
    assert model.encode("the cat") == ["▁the", "▁cat"]
    assert model.encode("the cats") == ["▁the", "▁cat", "s"]
    # 'x' is not in the vocab: single-char unknown fallback, lattice stays
    # connected and the rest still segments optimally.
    assert model.encode("the x") == ["▁the", "▁", "x"]
    # roundtrip through serialize + parse
    reparsed = SentencePieceModel(
        [p for p in _roundtrip_pieces(pieces)]
    )
    assert reparsed.encode("the cat") == ["▁the", "▁cat"]


def _roundtrip_pieces(pieces):
    import tempfile
    from pathlib import Path

    from dora_tpu.models.spm import build_model_proto, parse_model

    with tempfile.TemporaryDirectory() as d:
        p = Path(d) / "m.spm"
        p.write_bytes(build_model_proto(pieces))
        return parse_model(p)


def test_marian_tokenizer_roundtrip(marian_checkpoint):
    from dora_tpu.models.hf.marian import MarianTokenizer

    path, _, _ = marian_checkpoint
    tok = MarianTokenizer(path)
    ids = tok.encode("the cat sat")
    assert ids[-1] == tok.eos_id
    assert tok.decode(ids) == "the cat sat"
    # unknown characters survive as <unk> ids without crashing decode
    ids = tok.encode("the zebra")
    assert tok.unk_id in ids


# ---------------------------------------------------------------------------
# Wav2Vec2 audio-frame classification (VAD-class)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wav2vec2_checkpoint(tmp_path_factory):
    from transformers import (
        Wav2Vec2Config,
        Wav2Vec2ForAudioFrameClassification,
    )

    config = Wav2Vec2Config(
        vocab_size=32,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        conv_dim=[16, 16, 32],
        conv_stride=[5, 2, 2],
        conv_kernel=[10, 3, 3],
        num_conv_pos_embeddings=16,
        num_conv_pos_embedding_groups=4,
        num_labels=2,
        do_stable_layer_norm=False,
        feat_extract_norm="group",
    )
    torch.manual_seed(11)
    model = Wav2Vec2ForAudioFrameClassification(config).eval()
    path = tmp_path_factory.mktemp("wav2vec2")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def test_wav2vec2_frame_logits_match_torch(wav2vec2_checkpoint):
    from dora_tpu.models.hf import wav2vec2

    path, model = wav2vec2_checkpoint
    cfg, params = wav2vec2.load(path)
    rng = np.random.default_rng(0)
    audio = rng.standard_normal((2, 4000)).astype(np.float32)
    with torch.no_grad():
        ref = model(torch.tensor(audio)).logits.numpy()
    ours = np.asarray(wav2vec2.forward(params, cfg, audio))
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, atol=2e-5, rtol=2e-5)


def test_wav2vec2_speech_probability_matches_torch(wav2vec2_checkpoint):
    """The VAD surface: multi-label frame heads read with per-label
    sigmoid; speech presence = max over labels."""
    from dora_tpu.models.hf import wav2vec2

    path, model = wav2vec2_checkpoint
    cfg, params = wav2vec2.load(path)
    rng = np.random.default_rng(5)
    audio = rng.standard_normal((1, 3200)).astype(np.float32)
    with torch.no_grad():
        ref = (
            torch.sigmoid(model(torch.tensor(audio)).logits)
            .max(dim=-1)
            .values.numpy()
        )
    ours = np.asarray(wav2vec2.speech_probability(params, cfg, audio))
    np.testing.assert_allclose(ours, ref, atol=2e-5, rtol=2e-5)
    assert (ours >= 0).all() and (ours <= 1).all()


def test_vad_operator_serves_hf_checkpoint(wav2vec2_checkpoint, monkeypatch):
    from dora_tpu.nodehub import ops

    path, _ = wav2vec2_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    op = ops.make_vad()
    rng = np.random.default_rng(9)
    audio = (rng.normal(size=3200) * 0.2).astype(np.float32)
    _, out = op.step(op.init_state, {"audio": jnp.asarray(audio)})
    prob = np.asarray(out["prob"])
    assert prob.shape == (1,)
    assert 0.0 <= float(prob[0]) <= 1.0


# ---------------------------------------------------------------------------
# InternVL (second VLM family: InternViT + pixel shuffle + Qwen2)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def internvl_checkpoint(tmp_path_factory):
    from transformers import InternVLConfig, InternVLForConditionalGeneration

    config = InternVLConfig(
        vision_config=dict(
            hidden_size=32,
            num_hidden_layers=2,
            num_attention_heads=2,
            intermediate_size=64,
            image_size=[16, 16],
            patch_size=[4, 4],
            use_qk_norm=True,
            layer_scale_init_value=0.1,
            norm_type="layer_norm",
            use_absolute_position_embeddings=True,
            use_mean_pooling=True,
            attention_bias=True,
        ),
        text_config=dict(
            model_type="qwen2",
            vocab_size=300,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            max_position_embeddings=256,
            rope_theta=10000.0,
            tie_word_embeddings=False,
        ),
        image_token_id=290,
        downsample_ratio=0.5,
        projector_hidden_act="gelu",
        attn_implementation="eager",
    )
    torch.manual_seed(23)
    model = InternVLForConditionalGeneration(config).eval()
    path = tmp_path_factory.mktemp("internvl-tiny")
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def _internvl_inputs(cfg, rng, n_tiles=2, text_len=4):
    """<IMG_CONTEXT> runs for n_tiles tiles + trailing text ids."""
    pixel_values = rng.normal(size=(n_tiles, 3, 16, 16)).astype(np.float32)
    ids = [cfg.image_token_id] * (cfg.tokens_per_tile * n_tiles) + list(
        rng.integers(0, 280, size=text_len)
    )
    return np.array([ids], dtype=np.int64), pixel_values


def test_internvl_vision_features_match_torch(internvl_checkpoint):
    from dora_tpu.models.hf import internvl

    path, torch_model = internvl_checkpoint
    cfg, params = internvl.load(path, max_seq=128)
    assert cfg.tokens_per_tile == 4  # (16/4)^2 patches * 0.5^2
    rng = np.random.default_rng(24)
    _, pixel_values = _internvl_inputs(cfg, rng)

    ours = np.asarray(internvl.encode_images(params, cfg, pixel_values))
    with torch.no_grad():
        theirs = (
            torch_model.model.get_image_features(torch.tensor(pixel_values))
            .reshape(-1, cfg.text.dim)
            .numpy()
        )
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_internvl_logits_match_torch(internvl_checkpoint):
    from dora_tpu.models.hf import internvl

    path, torch_model = internvl_checkpoint
    cfg, params = internvl.load(path, max_seq=128)
    rng = np.random.default_rng(25)
    input_ids, pixel_values = _internvl_inputs(cfg, rng)

    feats = internvl.encode_images(params, cfg, pixel_values)
    ours = np.asarray(
        internvl.forward(params, cfg, np.asarray(input_ids, np.int32), feats)
    )
    with torch.no_grad():
        theirs = torch_model(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(pixel_values),
        ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_internvl_greedy_matches_torch(internvl_checkpoint):
    from dora_tpu.models.hf import internvl

    path, torch_model = internvl_checkpoint
    cfg, params = internvl.load(path, max_seq=128)
    rng = np.random.default_rng(26)
    input_ids, pixel_values = _internvl_inputs(cfg, rng)

    ours = np.asarray(
        internvl.generate(params, cfg, input_ids, pixel_values, 8)
    )
    with torch.no_grad():
        theirs = torch_model.generate(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(pixel_values),
            max_new_tokens=8,
            do_sample=False,
            use_cache=True,
            pad_token_id=0,
        ).numpy()[:, input_ids.shape[1] :]
    np.testing.assert_array_equal(ours, theirs)


def test_internvl_text_only_matches_torch(internvl_checkpoint):
    from dora_tpu.models.hf import internvl

    path, torch_model = internvl_checkpoint
    cfg, params = internvl.load(path, max_seq=128)
    rng = np.random.default_rng(27)
    ids = rng.integers(0, 280, size=(1, 7))

    ours = np.asarray(internvl.forward(params, cfg, ids.astype(np.int32), None))
    with torch.no_grad():
        theirs = torch_model(input_ids=torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=3e-4, rtol=2e-3)


def test_internvl_tile_grid_matches_reference_selection():
    """Geometry parity with the reference's dynamic_preprocess
    (dora_internvl/main.py:46-97): closest aspect ratio wins; thumbnail
    appended whenever more than one tile."""
    from dora_tpu.models.hf import internvl

    # 2:1 landscape -> 2x1 grid in [1, 12] tiles, + thumbnail = 3
    assert internvl.tile_grid(896, 448) == (2, 1, 3)
    # square -> single tile, no thumbnail
    assert internvl.tile_grid(448, 448) == (1, 1, 1)
    # 16:9 1280x720 -> aspect 1.777; candidates include (7,4)=1.75 &
    # (9,5)=1.8 but 12-tile cap keeps e.g. (2,1)? No: best within cap.
    cols, rows, n = internvl.tile_grid(1280, 720)
    assert cols * rows <= 12 and n == cols * rows + 1
    assert abs(cols / rows - 1280 / 720) <= min(
        abs(c / r - 1280 / 720)
        for c, r in internvl.target_ratios()
    ) + 1e-9
    # portrait mirrors landscape
    assert internvl.tile_grid(448, 896)[:2] == (1, 2)


def test_internvl_preprocess_tiles_shapes_and_normalization():
    from dora_tpu.models.hf import internvl

    rng = np.random.default_rng(28)
    image = rng.integers(0, 256, size=(90, 180, 3), dtype=np.uint8)
    cols, rows, n = internvl.tile_grid(180, 90, tile=32)
    tiles = np.asarray(
        internvl.preprocess_tiles(jnp.asarray(image), cols, rows, tile=32)
    )
    assert tiles.shape == (n, 3, 32, 32)
    # IMAGENET normalization: a mid-gray image maps near (0.5-mean)/std
    gray = jnp.full((64, 64, 3), 128, jnp.uint8)
    t = np.asarray(internvl.preprocess_tiles(gray, 1, 1, tile=32))
    expected = (128 / 255 - np.array(internvl.IMAGENET_MEAN)) / np.array(
        internvl.IMAGENET_STD
    )
    np.testing.assert_allclose(t.mean(axis=(0, 2, 3)), expected, atol=1e-3)


def test_internvl_operator_serves_hf_checkpoint(internvl_checkpoint, monkeypatch):
    """The node-hub VLM operator routes InternVL checkpoints: image in,
    greedy tokens out, matching torch generate on identical tiles."""
    from dora_tpu.models.hf import internvl
    from dora_tpu.nodehub import ops

    path, torch_model = internvl_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "6")
    monkeypatch.setenv("DORA_MAX_SEQ", "128")
    monkeypatch.setenv("IMAGE_HEIGHT", "16")
    monkeypatch.setenv("IMAGE_WIDTH", "32")
    monkeypatch.setenv("DORA_PROMPT", "hi")

    op = ops.make_vlm()
    rng = np.random.default_rng(29)
    image = rng.integers(0, 256, size=(16, 32, 3)).astype(np.uint8)
    _, out = op.step(op.init_state, {"image": jnp.asarray(image)})
    tokens = np.asarray(out["tokens"])
    assert tokens.shape == (6,)

    # Torch reference on the identical preprocessed tiles.
    cfg, params = internvl.load(path, max_seq=128)
    cols, rows, n_tiles = internvl.tile_grid(32, 16, tile=16)
    tiles = np.asarray(
        internvl.preprocess_tiles(jnp.asarray(image), cols, rows, tile=16)
    )
    from dora_tpu.models import tokenizer as byte_tok

    input_ids = internvl.build_prompt_ids(
        cfg, [t % cfg.text.vocab for t in byte_tok.encode("hi")], n_tiles
    )
    with torch.no_grad():
        theirs = torch_model.generate(
            input_ids=torch.tensor(input_ids),
            pixel_values=torch.tensor(tiles),
            max_new_tokens=6,
            do_sample=False,
            pad_token_id=0,
        ).numpy()[:, input_ids.shape[1] :]
    np.testing.assert_array_equal(tokens[None], theirs)


# ---------------------------------------------------------------------------
# VITS / MMS-TTS (pretrained text-to-speech)
# ---------------------------------------------------------------------------


def _vits_config(stochastic: bool):
    from transformers import VitsConfig

    return VitsConfig(
        vocab_size=40,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=2,
        ffn_dim=64,
        ffn_kernel_size=3,
        window_size=2,
        flow_size=16,
        spectrogram_bins=9,
        duration_predictor_kernel_size=3,
        duration_predictor_filter_channels=24,
        use_stochastic_duration_prediction=stochastic,
        duration_predictor_num_flows=2,
        duration_predictor_flow_bins=4,
        depth_separable_num_layers=2,
        depth_separable_channels=2,
        prior_encoder_num_flows=2,
        prior_encoder_num_wavenet_layers=2,
        wavenet_kernel_size=3,
        upsample_initial_channel=16,
        upsample_rates=[4, 4],
        upsample_kernel_sizes=[8, 8],
        resblock_kernel_sizes=[3],
        resblock_dilation_sizes=[[1, 3]],
        # parity: no sampling noise anywhere
        noise_scale=0.0,
        noise_scale_duration=0.0,
        num_speakers=1,
        speaker_embedding_size=0,
    )


@pytest.fixture(scope="module", params=[False, True],
                ids=["plain-duration", "stochastic-duration"])
def vits_checkpoint(request, tmp_path_factory):
    from transformers import VitsModel

    torch.manual_seed(31)
    model = VitsModel(_vits_config(request.param)).eval()
    path = tmp_path_factory.mktemp(
        f"vits-tiny-{'sdp' if request.param else 'dp'}"
    )
    model.save_pretrained(path, safe_serialization=True)
    return path, model


def test_vits_text_encoder_matches_torch(vits_checkpoint):
    from dora_tpu.models.hf import vits

    path, torch_model = vits_checkpoint
    cfg, params = vits.load(path)
    rng = np.random.default_rng(32)
    ids = rng.integers(1, cfg.vocab, size=(1, 11))

    hidden, means, log_var = vits.encode_text(params, cfg, ids)
    with torch.no_grad():
        mask = torch.ones(1, 11, 1)
        out = torch_model.text_encoder(
            input_ids=torch.tensor(ids), padding_mask=mask
        )
    np.testing.assert_allclose(
        np.asarray(hidden).transpose(0, 2, 1),
        out.last_hidden_state.numpy(), atol=2e-4, rtol=2e-3,
    )
    np.testing.assert_allclose(
        np.asarray(means), out.prior_means.numpy(), atol=2e-4, rtol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(log_var), out.prior_log_variances.numpy(),
        atol=2e-4, rtol=2e-3,
    )


def test_vits_waveform_matches_torch(vits_checkpoint):
    """Full deterministic synthesis (noise scales 0): same durations,
    same waveform as torch VitsModel."""
    from dora_tpu.models.hf import vits

    path, torch_model = vits_checkpoint
    cfg, params = vits.load(path)
    assert cfg.noise_scale == 0.0 and cfg.noise_scale_duration == 0.0
    rng = np.random.default_rng(33)
    ids = rng.integers(1, cfg.vocab, size=(1, 7))

    ours = vits.synthesize(params, cfg, ids)
    with torch.no_grad():
        theirs = torch_model(input_ids=torch.tensor(ids)).waveform.numpy()
    assert ours.shape == theirs.shape, (ours.shape, theirs.shape)
    np.testing.assert_allclose(ours, theirs, atol=1e-4, rtol=2e-3)


def test_tts_operator_serves_vits_checkpoint(vits_checkpoint, monkeypatch):
    """make_tts routes VITS checkpoints: the operator's audio equals the
    torch VitsModel's waveform on the identical token ids."""
    from dora_tpu.models.hf import vits as vits_mod
    from dora_tpu.nodehub import ops

    path, torch_model = vits_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    op = ops.make_tts()
    _, out = op.step(op.init_state, {"text": jnp.asarray(
        np.frombuffer(b"hello", dtype=np.uint8))})
    audio = np.asarray(out["audio"])
    assert audio.ndim == 1 and audio.size > 0
    assert np.abs(audio).max() <= 1.0

    # Identical ids through torch (no vocab.json in the fabricated
    # checkpoint -> the operator's byte-fallback + pad interleave).
    cfg, _ = vits_mod.load(path)
    ids = [0]
    for b in b"hello":
        ids += [b % cfg.vocab, 0]
    with torch.no_grad():
        theirs = torch_model(
            input_ids=torch.tensor([ids], dtype=torch.long)
        ).waveform.numpy()[0]
    assert audio.shape == theirs.shape
    np.testing.assert_allclose(audio, theirs, atol=1e-4, rtol=2e-3)


def test_qwen2vl_speculative_matches_greedy(qwen2vl_checkpoint):
    """Prompt-lookup speculation on the pretrained family: bit-identical
    tokens to vanilla greedy (and therefore to torch), fewer passes."""
    from dora_tpu.models.hf import qwen2_vl

    path, _ = qwen2vl_checkpoint
    cfg, params = qwen2_vl.load(path, max_seq=128)
    rng = np.random.default_rng(44)
    input_ids, pixel_values, grid_thw = _vlm_inputs(cfg, rng)

    vanilla = np.asarray(
        qwen2_vl.generate(params, cfg, input_ids, pixel_values, grid_thw, 12)
    )
    spec, passes = qwen2_vl.generate_speculative(
        params, cfg, input_ids, pixel_values, grid_thw, 12
    )
    np.testing.assert_array_equal(vanilla, np.asarray(spec))
    # Strictly fewer passes than tokens (deterministic fixture seeds;
    # observed 8): a zero-acceptance regression would need exactly 12.
    assert int(passes) < 12, f"no drafts accepted ({int(passes)} passes)"


def test_vlm_operator_speculative_serving(qwen2vl_checkpoint, monkeypatch):
    """DORA_SPEC_DECODE on the pretrained operator: same tokens as the
    vanilla serving step."""
    from dora_tpu.nodehub import ops

    path, _ = qwen2vl_checkpoint
    monkeypatch.setenv("DORA_HF_CHECKPOINT", str(path))
    monkeypatch.setenv("DORA_MAX_NEW_TOKENS", "6")
    monkeypatch.setenv("DORA_MAX_SEQ", "128")
    monkeypatch.setenv("IMAGE_HEIGHT", "16")
    monkeypatch.setenv("IMAGE_WIDTH", "16")
    monkeypatch.setenv("DORA_PROMPT", "hi")
    rng = np.random.default_rng(45)
    image = rng.integers(0, 256, size=(16, 16, 3)).astype(np.uint8)

    op = ops.make_vlm()
    _, vanilla = op.step(op.init_state, {"image": jnp.asarray(image)})

    monkeypatch.setenv("DORA_SPEC_DECODE", "1")
    op_spec = ops.make_vlm()
    _, spec = op_spec.step(op_spec.init_state, {"image": jnp.asarray(image)})
    np.testing.assert_array_equal(
        np.asarray(vanilla["tokens"]), np.asarray(spec["tokens"])
    )


def test_internvl_speculative_matches_greedy(internvl_checkpoint):
    from dora_tpu.models.hf import internvl

    path, _ = internvl_checkpoint
    cfg, params = internvl.load(path, max_seq=128)
    rng = np.random.default_rng(46)
    input_ids, pixel_values = _internvl_inputs(cfg, rng)

    vanilla = np.asarray(
        internvl.generate(params, cfg, input_ids, pixel_values, 12)
    )
    spec, passes = internvl.generate_speculative(
        params, cfg, input_ids, pixel_values, 12
    )
    np.testing.assert_array_equal(vanilla, np.asarray(spec))
    # Strictly fewer passes than tokens (deterministic fixture seeds):
    # a zero-acceptance regression would need exactly 12.
    assert int(passes) < 12, f"no drafts accepted ({int(passes)} passes)"


def test_whisper_speculative_matches_greedy(whisper_checkpoint):
    """Prompt-lookup speculation on ASR: bit-identical transcript tokens
    to vanilla greedy, fewer decoder passes."""
    from dora_tpu.models.hf import whisper

    path, _ = whisper_checkpoint
    cfg, params = whisper.load(path)
    rng = np.random.default_rng(47)
    feats = rng.normal(size=(1, cfg.n_mels, 2 * cfg.max_source)).astype(
        np.float32
    )

    vanilla = np.asarray(whisper.transcribe_tokens(params, cfg, feats, 16))
    spec, passes = whisper.transcribe_tokens_speculative(
        params, cfg, feats, 16
    )
    np.testing.assert_array_equal(vanilla, np.asarray(spec))
    # Deterministic fixture seeds; a zero-acceptance regression needs 16.
    assert int(passes) < 16, f"no drafts accepted ({int(passes)} passes)"


def test_marian_speculative_matches_greedy(marian_checkpoint):
    """Prompt-lookup speculation on translation: bit-identical tokens to
    vanilla greedy, fewer decoder passes."""
    from dora_tpu.models.hf import marian

    path, _, _ = marian_checkpoint
    cfg, params = marian.load(path, max_tokens=16)
    src = np.array([[5, 9, 23, 41, 2, 0]], np.int32)

    vanilla = np.asarray(marian.translate(params, cfg, src, 10))
    spec, passes = marian.translate_speculative(params, cfg, src, 10)
    np.testing.assert_array_equal(vanilla, np.asarray(spec))
    # Deterministic fixture seeds; zero acceptance would need 10 passes.
    assert int(passes) < 10, f"no drafts accepted ({int(passes)} passes)"


def test_vits_bucketed_synthesis_bounded_compiles(vits_checkpoint):
    """synthesize_bucketed: N varying-length inputs produce (a) the same
    waveform as the unpadded run on the true prefix and (b) a jit cache
    that grows with the bucket grid, not with the input lengths
    (models/hf/vits.py shape note)."""
    from dora_tpu.models.hf import vits

    path, _ = vits_checkpoint
    cfg, params = vits.load(path)
    rng = np.random.default_rng(35)
    lengths = [5, 9, 13, 17, 23, 29]
    text_buckets = (16, 32)
    frame_buckets = (256, 1024, 4096)

    refs = {}
    for t in lengths:
        ids = rng.integers(1, cfg.vocab, size=(1, t))
        refs[t] = (ids, vits.synthesize(params, cfg, ids))

    before = {
        "enc": vits.encode_text._cache_size(),
        "dur": vits.predict_log_duration._cache_size(),
        "flow": vits.flow_inverse._cache_size(),
        "dec": vits.hifigan._cache_size(),
    }
    for t in lengths:
        ids, ref = refs[t]
        got = vits.synthesize_bucketed(
            params, cfg, ids, text_buckets=text_buckets,
            frame_buckets=frame_buckets,
        )
        assert got.shape == ref.shape, (t, got.shape, ref.shape)
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-3)

    grew = {
        "enc": vits.encode_text._cache_size() - before["enc"],
        "dur": vits.predict_log_duration._cache_size() - before["dur"],
        "flow": vits.flow_inverse._cache_size() - before["flow"],
        "dec": vits.hifigan._cache_size() - before["dec"],
    }
    assert grew["enc"] <= len(text_buckets), grew
    assert grew["dur"] <= len(text_buckets), grew
    assert grew["flow"] <= len(frame_buckets), grew
    assert grew["dec"] <= len(frame_buckets), grew
    # and strictly fewer compiles than distinct lengths (the point)
    assert grew["enc"] < len(lengths), grew


@pytest.mark.parametrize("width", ["DORA_INT8_DECODE", "DORA_INT4_DECODE"])
def test_qwen2vl_fused_quantized_decode(qwen2vl_checkpoint, monkeypatch,
                                        width):
    """Pretrained decode through the fused kernel tier (round 4): the
    quantized fused path emits the same tokens as the unfused path on
    the same quantized weights, for both weight widths — and
    speculation (fused M-row verify) agrees too."""
    from dora_tpu.models import vlm as vlm_mod
    from dora_tpu.models.hf import qwen2_vl

    path, _ = qwen2vl_checkpoint
    monkeypatch.setenv(width, "1")
    cfg, params = qwen2_vl.load(path, max_seq=128)
    qparams = qwen2_vl.quantize_decode(params, cfg)
    assert vlm_mod.fused_decode_ready(qparams)
    rng = np.random.default_rng(45)
    input_ids, pixel_values, grid_thw = _vlm_inputs(cfg, rng)

    fused = np.asarray(
        qwen2_vl.generate(qparams, cfg, input_ids, pixel_values, grid_thw, 10)
    )
    monkeypatch.setenv("DORA_FUSED_DECODE", "0")
    ref = np.asarray(
        qwen2_vl.generate(qparams, cfg, input_ids, pixel_values, grid_thw, 10)
    )
    np.testing.assert_array_equal(fused, ref)
    monkeypatch.delenv("DORA_FUSED_DECODE")
    spec, passes = qwen2_vl.generate_speculative(
        qparams, cfg, input_ids, pixel_values, grid_thw, 10
    )
    np.testing.assert_array_equal(np.asarray(spec), fused)


def test_internvl_fused_quantized_decode(internvl_checkpoint, monkeypatch):
    """InternVL decode through the fused kernel tier: quantized fused vs
    unfused-on-the-same-weights token equality, speculation included."""
    from dora_tpu.models import vlm as vlm_mod
    from dora_tpu.models.hf import internvl

    path, _ = internvl_checkpoint
    monkeypatch.setenv("DORA_INT8_DECODE", "1")
    cfg, params = internvl.load(path, max_seq=128)
    qparams = internvl.quantize_decode(params, cfg)
    assert vlm_mod.fused_decode_ready(qparams)
    rng = np.random.default_rng(46)
    input_ids, pixel_values = _internvl_inputs(cfg, rng)

    fused = np.asarray(
        internvl.generate(qparams, cfg, input_ids, pixel_values, 10)
    )
    monkeypatch.setenv("DORA_FUSED_DECODE", "0")
    ref = np.asarray(
        internvl.generate(qparams, cfg, input_ids, pixel_values, 10)
    )
    np.testing.assert_array_equal(fused, ref)
    monkeypatch.delenv("DORA_FUSED_DECODE")
    spec, passes = internvl.generate_speculative(
        qparams, cfg, input_ids, pixel_values, 10
    )
    np.testing.assert_array_equal(np.asarray(spec), fused)
