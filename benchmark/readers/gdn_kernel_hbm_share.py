"""Share of the chip's peak HBM bandwidth that the delta rule's one-token
step kernel reaches on the state it has to move: [mean live rows a tick
(the program's ``gdn_row_ticks`` / ``gdn_decode_ticks`` / linear layers,
between the capture's edges) x the kernel's calls in the capture x the
bytes one row's state takes read and written
(``lib/model_bytes_gdn_hybrid.state_step_bytes``)] / the device kind's peak
bytes per second (``lib/peaks.json``) / the device time the state's
movement takes in the capture: the kernel's summed device time AND that
of the compiler's own copies of a whole state array that the device waits
for (``copies``: ``copy-done`` operations of shape ``f32[slots, heads, d_k,
d_v]``; ``ssm_kernel_hbm_share`` says why: a state XLA keeps on chip
around the kernel crossed the bus outside it). The kernel moves no state
for a row that is not live, so the live rows are the bytes. None where the
capture holds no such kernel or the program no such counters."""
import re

import model_bytes_gdn_hybrid as mb
import trace_reduce


def read(run: dict, args: dict):
    edges = mb.capture_edges(run)
    if not run.get("events") or not edges:
        return None
    cfg = run["config"]["model"]
    row_ticks = mb.per(*edges, "gdn_row_ticks", "gdn_decode_ticks")
    if row_ticks is None or not mb.linear_layers(cfg):
        return None
    rows = row_ticks / mb.linear_layers(cfg)
    rx = re.compile(args["match"])
    whole_state = re.compile(
        rf"{args['copies']}.* f32\[\d+,{cfg['linear_num_value_heads']},"
        rf"{cfg['linear_key_head_dim']},{cfg['linear_value_head_dim']}\]"
    ) if args.get("copies") else None
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
