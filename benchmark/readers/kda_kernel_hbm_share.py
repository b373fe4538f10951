"""Share of the chip's peak HBM bandwidth that the delta rule's one-token
step kernel reaches on a ``kimi_linear`` configuration's KDA states: [mean
live rows a tick (the program's ``kda_row_ticks`` / ``kda_decode_ticks`` /
KDA layers, between the capture's edges) x the kernel's calls in the
capture x the bytes one row's state takes read and written
(``lib/model_bytes_kda_mla.state_step_bytes``: 4,194,304)] / the device
kind's peak bytes per second (``lib/peaks.json``) / the device time the
state's movement takes in the capture: the kernel's summed device time AND
that of the compiler's own copies of a whole state array that the device
waits for (``copies``: ``copy-done`` operations of shape ``f32[slots, heads,
d_k, d_v]``), as ``gdn_kernel_hbm_share`` reads Olmo-Hybrid's (that reader
names Olmo's counters and config keys; this one Kimi Linear's). The kernel
moves no state for a row that is not live, so the live rows are the bytes.
None where the capture holds no such kernel or the program no such
counters."""
import re

import model_bytes_kda_mla as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    cfg = run["config"]["model"]
    row_ticks = mb.per(*edges, "kda_row_ticks", "kda_decode_ticks")
    if row_ticks is None or not mb.kda_layers(cfg):
        return None
    rows = row_ticks / mb.kda_layers(cfg)
    lin = cfg["linear_attn_config"]
    rx = re.compile(args["match"])
    whole_state = re.compile(
        rf"{args['copies']}.* f32\[\d+,{lin['num_heads']},{lin['head_dim']},"
        rf"{lin['head_dim']}\]") if args.get("copies") else None
    calls, ns = 0, 0
    for lines in trace_reduce.device_planes(run["events"]).values():
        for name, _, dur in lines[trace_reduce.OPS_LINE]:
            if rx.search(name):
                calls, ns = calls + 1, ns + dur
            elif whole_state is not None and whole_state.search(name):
                ns += dur
    if not calls or not ns:
        return None
    bytes_ = rows * calls * mb.state_step_bytes(cfg)
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ns / 1e9)
