"""What the program keeps in a slot's recurrent state after prefill AND
decode beside other live streams, against the reference's: the part of
``correct`` that the state's precision moves.

No emitted token shows whether the SSM state holds 32 bits or 16 (the
gated norm after the mixer divides its output by its own size; the
reference prints that too, ``what_if.bf16_state``), so the state itself
is compared, where the cell's clock runs: through the chunk program, the
window program and ``ssm_state_step``, in a full engine.

``serve`` is called by ``reference_falcon_h1.py`` in its own process,
after the dataflow has exited and before the reference claims the chip's
memory. It builds the program's engine as the server does
(``llm_server``'s choice of module, its ``make_engine`` under the cell's
node environment, the same checkpoint) and serves the sampled prompts
through ``PagedBatchEngine.step()``, each for ``decode`` tokens, beside
fillers (the same prompts rotated and cut, with other lengths of reply)
that keep every slot taken: 8 more streams than slots, so the audited
streams are prefilled in chunks between the others' windows, sit frozen
in windows while they wait, decode beside the other slots' rows, and see
slots beside them freed and taken again. The moment an audited stream
ends, its slot's SSM state is read in EVERY layer: it then stands after
prompt + all emitted tokens but the last (which no tick consumed).

The reference teacher-forces exactly those tokens (prompt + what THIS
engine emitted), token by token in float32, and keeps every layer's
state at that position. ``rel_err`` = rms(program - reference) /
rms(reference) over a layer's ``[heads, head_dim, state]`` values. The
control, computed in every run, is the reference with its state rounded
to bfloat16 after every token in every layer, against the same float32
states: what a faultless program that kept its state in bfloat16 would
read. This audits the program (module, loader, engine, environment, both
programs and the kernel), not the memory of the server that served the
window.
"""

from __future__ import annotations

import os
import time

EXTRA_STREAMS = 8  # fillers beyond the slots: every freed slot is taken again


def rel_err(got, want) -> float:
    import numpy as np

    want = want.astype(np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def fillers(prompts: list[list[int]], n: int, decode: int) -> list[tuple[list[int], int]]:
    """``n`` (prompt, max_new) pairs made of the sampled prompts: rotated
    (ids stay in the vocabulary's slice, no two alike) and cut to other
    lengths, with replies from a quarter of ``decode`` to twice it."""
    out = []
    for k in range(n):
        base = prompts[k % len(prompts)]
        turn = (7 * k + 3) % len(base)
        rotated = base[turn:] + base[:turn]
        keep = max(1, len(rotated) * (1 + k % 4) // 4)
        out.append((rotated[:keep], max(1, decode * (1 + k % 8) // 4)))
    return out


def serve(checkpoint: str, env: dict, prompts: list[list[int]], decode: int) -> dict:
    """Serve ``prompts`` for ``decode`` tokens each beside fillers.
    -> ``{"emitted": [tokens a prompt], "states": [[a layer's [H, P, N]
    float32 state] a prompt], ...}``."""
    import numpy as np

    t0 = time.perf_counter()
    os.environ.update({k: str(v) for k, v in env.items()})
    os.environ.setdefault("DORA_INT8_DECODE", "1")  # as llm_server.main does
    from dora_tpu.models.hf.loader import read_config
    from dora_tpu.nodehub import llm_server

    module = llm_server.model_module(read_config(checkpoint).get("model_type"))
    cfg, params = module.load(checkpoint, max_seq=int(os.environ.get("DORA_MAX_SEQ", "2048")))
    engine = llm_server.make_engine(module.quantize_decode(params, cfg), cfg, module=module)
    slots = len(engine.slots)
    rest = fillers(prompts, slots + EXTRA_STREAMS - len(prompts), decode)
    # a filler, then a sampled prompt, and so on: the audited streams are
    # admitted among the others, and the last fillers wait for a slot
    queue = []
    for k in range(max(len(prompts), len(rest))):
        if k < len(rest):
            queue.append((f"filler-{k}", *rest[k]))
        if k < len(prompts):
            queue.append((f"audit-{k}", prompts[k], decode))
    slot_of, emitted, states = {}, {}, {}
    in_slots, windows = 0, 0
    while queue or engine.active or engine.prefilling:
        while queue and engine.can_admit(len(queue[0][1]), queue[0][2]):
            rid, prompt, max_new = queue.pop(0)
            slot_of[rid] = engine.slots.index(None)  # the slot submit() takes
            engine.submit(rid, prompt, max_new)
            emitted[rid] = []
        in_slots += engine.active
        windows += 1
        for rid, token, done in engine.step():
            emitted[rid].append(token)
            if done and rid.startswith("audit-"):
                # frozen since its last tick; nothing was admitted since
                states[rid] = [
                    np.asarray(engine.slot_state[layer]["ssm"][slot_of[rid]])
                    for layer in sorted(engine.slot_state, key=int)]
        if windows > 64 * (slots + EXTRA_STREAMS):
            raise RuntimeError(f"audit: {sorted(set(emitted) - set(states))} never ended")
    audited = [f"audit-{k}" for k in range(len(prompts))]
    out = {
        "emitted": [emitted[rid] for rid in audited],
        "states": [states[rid] for rid in audited],
        "slots": [slot_of[rid] for rid in audited],
        "streams": len(emitted), "windows": windows,
        "streams_in_slots_a_window": in_slots / max(windows, 1),
        "state_dtype": str(engine.slot_state["0"]["ssm"].dtype),
        "state_bytes_a_slot": int(cfg.state_bytes_per_slot),
        "prefix_cache": engine.prefix_cache is not None,
    }
    del engine, params
    out["seconds"] = time.perf_counter() - t0
    return out
