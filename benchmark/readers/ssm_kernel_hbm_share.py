"""Share of the chip's peak HBM bandwidth that the one-token state-update
kernel reaches on the state it has to move: [mean live rows a tick (the
program's counters) x the kernel's calls in the capture x the bytes one
row's state takes read and written (``lib/model_bytes_falcon_h1``)] /
the device kind's peak bytes per second (``lib/peaks.json``) / the
device time the state's movement takes in the capture. The kernel moves
no state for a row that is not live, so the live rows are the bytes. The
rows are counted over the ticks the capture holds
(``model_bytes_falcon_h1.live_rows_in_capture``).

The time is the kernel's summed device time AND that of the compiler's
own copies of a whole state array that the device waits for (``copies``:
``copy-done`` operations of shape ``f32[slots, heads, head_dim, state]``).
XLA keeps some layers' state in on-chip memory around the kernel (3 of 9
layers at the published widths: fetched beside the operations before the
kernel, written back after it, 67 MB each way), and the kernel then runs
in a third of its time on bytes that crossed the HBM bus outside it:
without the copies' time the share read 101.7 % (PR 33). The fetch that
is hidden beside other operations is still left out, so the share is an
upper bound. None where the capture holds no such kernel or the program
no such counters."""
import re

import model_bytes_falcon_h1 as mb
import trace_reduce


def read(run: dict, args: dict):
    rows = mb.live_rows_in_capture(run)
    if not run.get("events") or rows is None:
        return None
    rx = re.compile(args["match"])
    cfg = run["config"]["model"]
    whole_state = re.compile(
        rf"{args['copies']}.* f32\[\d+,{cfg['mamba_n_heads']},{cfg['mamba_d_head']},"
        rf"{cfg['mamba_d_state']}\]") if args.get("copies") else None
    calls, ns = 0, 0
    for lines in trace_reduce.device_planes(run["events"]).values():
        for name, _, dur in lines[trace_reduce.OPS_LINE]:
            if rx.search(name):
                calls, ns = calls + 1, ns + dur
            elif whole_state is not None and whole_state.search(name):
                ns += dur
    if not calls or not ns:
        return None
    bytes_ = rows * calls * mb.state_step_bytes(run["config"]["model"])
    return 100.0 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / (ns / 1e9)
