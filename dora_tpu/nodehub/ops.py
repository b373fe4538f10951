"""TPU-tier operator factories for the model zoo.

Reference parity: node-hub AI nodes (dora-yolo, dora-qwenvl,
dora-distil-whisper, dora-vad) — re-expressed as fused jax operators
(``jax: dora_tpu.nodehub.ops:make_*`` in a dataflow YAML). Model weights
live in the operator's ``init_state``, so they are device-resident across
ticks; the daemon never sees them.

Model size is selected with the ``DORA_MODEL_SIZE`` env var ("tiny" for
tests/CI, "bench" for benchmarking shapes); checkpoints can be loaded
with ``DORA_CHECKPOINT`` (orbax directory, see dora_tpu.models.checkpoint).
"""

from __future__ import annotations

import logging
import os

import jax

from dora_tpu.tpu.api import JaxOperator


def _size() -> str:
    return os.environ.get("DORA_MODEL_SIZE", "tiny")


def _tp_sharding():
    """Megatron tensor-parallel placement rules for transformer weights —
    applied by the fused executor when the runtime serves on a DORA_MESH
    (dora_tpu.tpu.fuse.mesh_from_env); a no-op without a mesh."""
    from dora_tpu.models.layers import tp_rules

    return tp_rules()


def _normalize(image):
    """uint8 camera frames -> float in [0,1]; float frames pass through."""
    import jax.numpy as jnp

    if image.dtype == jnp.uint8:
        return image.astype(jnp.float32) / 255.0
    return image


def _maybe_restore(params, name: str):
    path = os.environ.get("DORA_CHECKPOINT")
    if path:
        from dora_tpu.models.checkpoint import restore

        params = restore(os.path.join(path, name), params)
    return _maybe_cast(params)


def _maybe_cast(params):
    """DORA_PARAM_DTYPE=bfloat16: store weights HBM-resident in bf16
    (serving config — halves memory, MXU-native; fp32 inits are freed
    by donation)."""
    dtype = os.environ.get("DORA_PARAM_DTYPE")
    if not dtype:
        return params
    import jax.numpy as jnp

    cast = jax.jit(
        lambda p: jax.tree.map(lambda x: x.astype(jnp.dtype(dtype)), p),
        donate_argnums=0,
    )
    return cast(params)


def make_detector() -> JaxOperator:
    """Image [H,W,3] float in [0,1] -> boxes/scores/classes (fixed K).

    With DORA_HF_CHECKPOINT pointing at a YOLOS safetensors directory,
    serves the real pretrained detector (reference parity: dora-yolo
    serving ultralytics weights, dora_yolo/main.py:37-104); image must
    arrive at the checkpoint's native resolution.
    """
    from dora_tpu.models import detection

    hf_path = _hf_checkpoint("yolos")
    if hf_path:
        from dora_tpu.models.hf import yolos

        cfg, params = yolos.load(hf_path)
        params = _maybe_cast(params)
        threshold = float(os.environ.get("DORA_DETECT_THRESHOLD", "0.5"))
        top_k = int(os.environ.get("DORA_DETECT_TOPK", str(cfg.n_det)))

        def hf_step(state, inputs):
            import jax.numpy as jnp

            image = _normalize(inputs["image"])[None]
            pixels = yolos.preprocess(image, cfg)
            out = yolos.detect(state, cfg, pixels, threshold, top_k)
            # Operator contract (shared with the self-contained detector,
            # consumed by nodehub/plot.py): absolute-pixel cxcywh.
            x1, y1, x2, y2 = jnp.moveaxis(out["boxes"][0], -1, 0)
            img_h, img_w = cfg.image_size
            boxes = jnp.stack(
                [
                    (x1 + x2) / 2 * img_w,
                    (y1 + y2) / 2 * img_h,
                    (x2 - x1) * img_w,
                    (y2 - y1) * img_h,
                ],
                axis=-1,
            )
            return state, {
                "boxes": boxes,
                "scores": out["scores"][0],
                "classes": out["classes"][0],
            }

        return JaxOperator(
            step=hf_step, init_state=params, sharding=_tp_sharding()
        )

    cfg = (
        detection.DetectorConfig.tiny()
        if _size() == "tiny"
        else detection.DetectorConfig()
    )
    params = _maybe_restore(
        detection.init_params(jax.random.PRNGKey(0), cfg), "detector"
    )

    def step(state, inputs):
        images = _normalize(inputs["image"])[None]  # add batch
        preds = detection.forward(state, cfg, images)
        out = jax.vmap(lambda p: detection.postprocess(cfg, p))(preds)
        return state, {
            "boxes": out["boxes"][0],
            "scores": out["scores"][0],
            "classes": out["classes"][0],
        }

    return JaxOperator(step=step, init_state=params, sharding=_tp_sharding())


def _hf_checkpoint(model_type_prefix: str) -> str | None:
    """Path from DORA_HF_CHECKPOINT when it holds a matching HF checkpoint
    (reference nodes load checkpoints by name through transformers,
    node-hub/dora-qwenvl/dora_qwenvl/main.py:24-33; here the path points
    at a downloaded safetensors directory)."""
    import json
    from pathlib import Path

    path = os.environ.get("DORA_HF_CHECKPOINT")
    if not path:
        return None
    config = Path(path) / "config.json"
    if not config.exists():
        raise FileNotFoundError(f"DORA_HF_CHECKPOINT={path}: no config.json")
    model_type = json.loads(config.read_text()).get("model_type", "")
    return path if model_type.startswith(model_type_prefix) else None


def _hf_tokenizer(path: str):
    from pathlib import Path

    from dora_tpu.models.tokenizer import BPETokenizer

    if (Path(path) / "tokenizer.json").exists():
        return BPETokenizer.from_file(path)
    return None


def make_vlm() -> JaxOperator:
    """Image [H,W,3] -> greedy caption tokens (prompt from DORA_PROMPT).

    With DORA_HF_CHECKPOINT pointing at a Qwen2-VL or InternVL
    safetensors directory, serves the real pretrained model (weights +
    BPE tokenizer); otherwise the self-contained trainable VLM with the
    byte tokenizer.
    """
    import jax.numpy as jnp

    from dora_tpu.models import tokenizer, vlm

    internvl_path = _hf_checkpoint("internvl")
    if internvl_path:
        from dora_tpu.models.hf import internvl

        max_new = int(os.environ.get("DORA_MAX_NEW_TOKENS", "16"))
        height = int(os.environ.get("IMAGE_HEIGHT", "224"))
        width = int(os.environ.get("IMAGE_WIDTH", "224"))
        max_tiles = int(os.environ.get("DORA_MAX_TILES", "12"))
        cfg, params = internvl.load(
            internvl_path, max_seq=int(os.environ.get("DORA_MAX_SEQ", "1024"))
        )
        params = _maybe_cast(params)
        if os.environ.get("DORA_INT8_DECODE") or os.environ.get(
            "DORA_INT4_DECODE"
        ):
            params = internvl.quantize_decode(params, cfg)
        tile = cfg.vision.image_size
        cols, rows, n_tiles = internvl.tile_grid(
            width, height, tile=tile, max_num=max_tiles
        )
        tok = _hf_tokenizer(internvl_path)
        prompt_text = os.environ.get("DORA_PROMPT", "Describe this image.")
        if tok is not None:
            text_ids = tok.encode(prompt_text)
        else:
            text_ids = [t % cfg.text.vocab for t in tokenizer.encode(prompt_text)]
        prompt_ids = internvl.build_prompt_ids(cfg, text_ids, n_tiles)
        from dora_tpu.models.spec_decode import gate_speculation

        speculative = gate_speculation(
            prompt_ids.shape[1], max_new, cfg.text.max_seq
        )
        serve = internvl.make_serving_step(
            cfg, prompt_ids, cols, rows, tile, max_new,
            speculative=speculative,
        )

        def internvl_step(state, inputs):
            tokens = serve(state, _normalize(inputs["image"]))
            return state, {"tokens": tokens[0]}

        return JaxOperator(
            step=internvl_step, init_state=params, sharding=_tp_sharding()
        )

    hf_path = _hf_checkpoint("qwen2_vl")
    if hf_path:
        import numpy as np

        from dora_tpu.models.hf import qwen2_vl

        max_new = int(os.environ.get("DORA_MAX_NEW_TOKENS", "16"))
        height = int(os.environ.get("IMAGE_HEIGHT", "224"))
        width = int(os.environ.get("IMAGE_WIDTH", "224"))
        cfg, params = qwen2_vl.load(
            hf_path, max_seq=int(os.environ.get("DORA_MAX_SEQ", "1024"))
        )
        if os.environ.get("DORA_INT8_DECODE") or os.environ.get(
            "DORA_INT4_DECODE"
        ):
            # Pretrained decode through the fused kernel tier (round 4):
            # quantized LM blocks + head; decode scan and speculative
            # verify route through ops.decode_block automatically.
            params = qwen2_vl.quantize_decode(params, cfg)
        tok = _hf_tokenizer(hf_path)
        prompt_text = os.environ.get("DORA_PROMPT", "Describe this image.")
        target_h, target_w = qwen2_vl.smart_resize(
            height, width, factor=cfg.vision.patch_size * cfg.vision.spatial_merge_size
        )
        if tok is not None:
            text_ids = tok.encode(prompt_text)
        else:  # no tokenizer.json shipped: byte-fallback text encoding
            text_ids = [t % cfg.vocab for t in tokenizer.encode(prompt_text)]
        prompt_ids = qwen2_vl.build_prompt_ids(
            cfg, text_ids, target_h, target_w
        )
        from dora_tpu.models.spec_decode import gate_speculation

        speculative = gate_speculation(
            prompt_ids.shape[1], max_new, cfg.max_seq
        )
        serve = qwen2_vl.make_serving_step(
            cfg, prompt_ids, target_h, target_w, max_new,
            speculative=speculative,
        )

        def hf_step(state, inputs):
            tokens = serve(state, _normalize(inputs["image"]))
            return state, {"tokens": tokens[0]}

        return JaxOperator(
            step=hf_step, init_state=params, sharding=_tp_sharding()
        )

    from dora_tpu import backend
    from dora_tpu.parallel import fused_tp as FTP
    from dora_tpu.tpu.fuse import mesh_from_env

    cfg = vlm.VLMConfig.tiny() if _size() == "tiny" else vlm.VLMConfig.bench_2b()
    params = _maybe_restore(vlm.init_params(jax.random.PRNGKey(0), cfg), "vlm")
    mesh = mesh_from_env()
    tp = FTP.tp_degree(mesh)
    quantize = bool(
        os.environ.get("DORA_INT8_DECODE") or os.environ.get("DORA_INT4_DECODE")
    )
    if quantize and mesh is not None and not FTP.tp_compatible(
        tp, heads=cfg.heads, kv_heads=cfg.kv_heads, ffn=cfg.ffn,
        vocab=cfg.vocab,
    ):
        # The quantized layout exists for the Pallas kernels, and a
        # kernel runs on a mesh only inside the tensor-parallel tier's
        # shard_map (XLA cannot partition it). Where tp does not tile the
        # model (tp=4 over the 2b shape's 2 KV heads) the mesh serves the
        # float weights through plain XLA, sharded by tp_rules — said
        # aloud, because it is a different tier with different speed.
        logging.getLogger(__name__).warning(
            "DORA_MESH with tp=%d has no tensor-parallel kernel tier for "
            "this model (kv_heads=%d): DORA_INT8_DECODE/INT4 ignored, "
            "serving float weights on the unfused XLA path",
            tp, cfg.kv_heads,
        )
        quantize = False
    if quantize:
        # Bandwidth lever: quantized LM weights, dequantized at the MXU
        # edge (ops.int8_matmul / ops.int4 — quantize_decode picks the
        # width from the env). Applied after cast/restore so the stored
        # float weights are the quantization source.
        params = vlm.quantize_decode(params)
    prompt_text = os.environ.get("DORA_PROMPT", "describe")
    max_new = int(os.environ.get("DORA_MAX_NEW_TOKENS", "16"))
    prompt = jnp.asarray(
        [[t % cfg.vocab for t in tokenizer.encode(prompt_text)]], jnp.int32
    )

    from dora_tpu.models.spec_decode import gate_speculation

    speculative = gate_speculation(
        cfg.n_patches + prompt.shape[1], max_new, cfg.max_seq,
        batch_ok=prompt.shape[0] == 1,
    )

    # On a DORA_MESH with tp>1 and a quantized fused layout, the decode
    # scan rides the tensor-parallel KERNEL tier (parallel/fused_tp.py)
    # instead of the unfused XLA path — the fastest path and the
    # multi-chip path are the same path. The prepared tp tree rides in
    # operator state beside the params (a closed-over array would lower
    # to a constant and bake the weights into the program); it is
    # already placed on the mesh, so the executor's sharding rules leave
    # its per-rank layout alone (parallel/mesh.shard_params).
    fused = vlm.fused_decode_ready(params, prompt.shape[0])
    tp_setup = None
    if fused and not speculative and mesh is not None:
        try:
            tp_setup = FTP.prepare_decode_params(
                params, mesh, heads=cfg.heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, layers=cfg.layers,
            )
        except ValueError:  # int4 groups do not tile on this mesh
            tp_setup = None
    # Say which tier serves — nobody should have to infer it from the
    # frame rate.
    tier = (
        "speculative" if speculative
        else "fused_tp" if tp_setup is not None
        else "fused" if fused
        else "unfused"
    )
    backend.report("vlm_tier", {
        "tier": tier, "mesh": dict(mesh.shape) if mesh is not None else None,
        "tp": tp, "fused_layout": bool(fused),
    })

    def step(state, inputs):
        image = _normalize(inputs["image"])[None]
        lm = state["lm"]
        if speculative:
            # Prompt-lookup speculation: identical greedy tokens, up to
            # k+1 per model pass (vlm.generate_speculative).
            tokens, _ = vlm.generate_speculative(
                lm, cfg, image, prompt, max_new
            )
        elif "tp" in state:
            tokens = vlm.generate_tp(
                lm, state["tp"], cfg, image, prompt, max_new, mesh
            )
        else:
            tokens = vlm.generate(lm, cfg, image, prompt, max_new)
        return state, {"tokens": tokens[0]}

    state = {"lm": params}
    if tp_setup is not None:
        state["tp"] = tp_setup
    return JaxOperator(step=step, init_state=state, sharding=_tp_sharding())


def make_asr() -> JaxOperator:
    """Audio chunk [samples] float -> token ids.

    With DORA_HF_CHECKPOINT pointing at a Whisper-family safetensors
    directory, serves the real pretrained model.
    """
    from dora_tpu.models import asr, tokenizer

    hf_path = _hf_checkpoint("whisper")
    if hf_path:
        from dora_tpu.models.hf import whisper

        max_new = int(os.environ.get("DORA_MAX_NEW_TOKENS", "32"))
        cfg, params = whisper.load(hf_path)
        from dora_tpu.models.spec_decode import gate_speculation

        speculative = gate_speculation(1, max_new, cfg.max_target)
        serve = whisper.make_serving_step(cfg, max_new, speculative=speculative)

        def hf_step(state, inputs):
            tokens = serve(state, inputs["audio"])
            return state, {"tokens": tokens[0]}

        return JaxOperator(
            step=hf_step, init_state=params, sharding=_tp_sharding()
        )

    cfg = asr.ASRConfig.tiny() if _size() == "tiny" else asr.ASRConfig()
    params = _maybe_restore(asr.init_params(jax.random.PRNGKey(0), cfg), "asr")
    max_new = min(
        int(os.environ.get("DORA_MAX_NEW_TOKENS", "16")), cfg.max_tokens
    )
    bos = tokenizer.BOS % cfg.vocab

    def step(state, inputs):
        audio = inputs["audio"][None]
        tokens = asr.transcribe(state, cfg, audio, bos, max_new)
        return state, {"tokens": tokens[0]}

    return JaxOperator(step=step, init_state=params, sharding=_tp_sharding())


def make_translator() -> JaxOperator:
    """Text (utf-8 bytes or token ids) -> translated token ids.

    Reference parity: node-hub/dora-opus / dora-argotranslate (text in,
    translated text out through a pretrained encoder-decoder). Tokens ride
    the byte-level codec (dora_tpu.models.tokenizer), so the emitted ids
    decode back to text with ``tokenizer.decode``.
    """
    import jax.numpy as jnp

    from dora_tpu.models import tokenizer, translation

    cfg = (
        translation.TranslatorConfig.tiny()
        if _size() == "tiny"
        else translation.TranslatorConfig()
    )
    params = _maybe_restore(
        translation.init_params(jax.random.PRNGKey(0), cfg), "translator"
    )
    # Decode steps beyond the KV-cache capacity would silently clamp.
    max_new = min(
        int(os.environ.get("DORA_MAX_NEW_TOKENS", "16")), cfg.max_tokens
    )
    bos = tokenizer.BOS % cfg.vocab

    def step(state, inputs):
        src = inputs["text"].astype(jnp.int32) % cfg.vocab
        # Static-shape source window: trim or right-pad to max_src (the
        # pad id attends as ordinary context; real checkpoints mask it).
        src = src[: cfg.max_src]
        src = jnp.pad(src, (0, cfg.max_src - src.shape[0]),
                      constant_values=tokenizer.PAD % cfg.vocab)
        tokens = translation.translate(state, cfg, src[None], bos, max_new)
        return state, {"tokens": tokens[0]}

    return JaxOperator(step=step, init_state=params, sharding=_tp_sharding())


def make_tts() -> JaxOperator:
    """Text (utf-8 bytes / token ids) -> waveform samples.

    Reference parity: node-hub/dora-parler (text in, speech out,
    dora_parler/main.py:94-150). ``DORA_TTS_STYLE`` selects the voice
    (the reference's description prompt); output is float32 in [-1, 1]
    at ``cfg.sample_rate``.

    With DORA_HF_CHECKPOINT pointing at a VITS / MMS-TTS safetensors
    directory, serves the real pretrained model — text bytes are
    tokenized with the checkpoint's VITS convention (lowercase chars
    interleaved with pad 0) and synthesized deterministically.
    """
    import jax.numpy as jnp

    from dora_tpu.models import tokenizer, tts

    vits_path = _hf_checkpoint("vits")
    if vits_path:
        import json
        from pathlib import Path

        import numpy as np

        from dora_tpu.models.hf import vits

        cfg, params = vits.load(vits_path)
        vocab_file = Path(vits_path) / "vocab.json"
        vocab = (
            json.loads(vocab_file.read_text()) if vocab_file.exists() else None
        )

        def encode_text(raw: bytes) -> list[int]:
            text = raw.decode("utf-8", "ignore").lower()
            if vocab is None:  # no tokenizer shipped: byte-fallback ids
                ids = [b % cfg.vocab for b in text.encode()]
            else:
                ids = [vocab[ch] for ch in text if ch in vocab]
            # VITS convention: pad token 0 interleaved around each char.
            out = [0]
            for t in ids:
                out += [t, 0]
            return out

        def vits_step(state, inputs):
            raw = bytes(np.asarray(inputs["text"]).astype(np.uint8))
            ids = np.asarray([encode_text(raw)], np.int32)
            # Bucketed: pads text/frames to bucket edges so serving
            # varying-length sentences compiles at most once per bucket
            # instead of once per length (vits.synthesize_bucketed).
            wave = vits.synthesize_bucketed(state, cfg, ids)
            return state, {"audio": jnp.asarray(wave[0])}

        # host=True: synthesis length is data-dependent (predicted
        # durations), so the step cannot run under the fused jit.
        return JaxOperator(step=vits_step, init_state=params, host=True)

    cfg = tts.TTSConfig.tiny() if _size() == "tiny" else tts.TTSConfig()
    params = _maybe_restore(tts.init_params(jax.random.PRNGKey(0), cfg), "tts")
    style = int(os.environ.get("DORA_TTS_STYLE", "0")) % cfg.n_styles

    def step(state, inputs):
        text = inputs["text"].astype(jnp.int32) % cfg.vocab
        text = text[: cfg.max_text]
        text = jnp.pad(text, (0, cfg.max_text - text.shape[0]),
                       constant_values=tokenizer.PAD % cfg.vocab)
        wave = tts.synthesize(state, cfg, text[None], jnp.asarray([style]))
        return state, {"audio": wave[0]}

    return JaxOperator(step=step, init_state=params)


def make_vad() -> JaxOperator:
    """Audio chunk [samples] -> speech probability.

    With DORA_HF_CHECKPOINT pointing at a Wav2Vec2 audio-frame
    classification directory (superb/sd-class), serves the real
    pretrained model: per-chunk speech probability = max frame speech
    probability (reference job: dora-vad's Silero gate). Otherwise the
    self-contained GRU whose state threads across ticks in device
    memory."""
    import jax.numpy as jnp

    from dora_tpu.models import vad

    hf_path = _hf_checkpoint("wav2vec2")
    if hf_path:
        from dora_tpu.models.hf import wav2vec2

        cfg, params = wav2vec2.load(hf_path)

        def hf_step(state, inputs):
            probs = wav2vec2.speech_probability(state, cfg, inputs["audio"][None])
            return state, {"prob": jnp.max(probs, axis=-1)}

        return JaxOperator(step=hf_step, init_state=params)

    cfg = vad.VADConfig.tiny() if _size() == "tiny" else vad.VADConfig()
    params = _maybe_restore(vad.init_params(jax.random.PRNGKey(0), cfg), "vad")
    h0 = jnp.zeros((1, cfg.hidden), jnp.float32)

    def step(state, inputs):
        params, h = state
        audio = inputs["audio"][None]
        prob, h = vad.speech_prob(params, cfg, audio, h)
        return (params, h), {"prob": prob}

    return JaxOperator(step=step, init_state=(params, h0))
