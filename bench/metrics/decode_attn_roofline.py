"""The paged decode kernel's least time over its time in the trace, in
percent. Least time is the larger of its operations over the bf16 peak
and its bytes over HBM bandwidth: every page that holds a live row, K
and V, for every decode step the window computed (``counts.py``), per
chip. At 4 x G operations per byte read (G query heads per KV head) the
kernel is memory-bound on a TPU v5e.

In the trace the kernel is the Mosaic custom call
(``custom_call_target="tpu_custom_call"``) inside the paged decode
program (``jit_paged_step``): the only kernel that program runs.
"""

import counts

KERNEL = 'custom_call_target="tpu_custom_call"'
PROGRAM = "jit_paged_step"


def read(run):
    m, page = run.m, run.conf["engine"]["page_size"]
    rows = [x for r in run.rounds for x in r.decode_rows]
    t_kernel = run.trace.op_seconds(
        lambda e: KERNEL in e.name and e.module.startswith(PROGRAM))
    if not rows or t_kernel <= 0:
        return None
    least, _ = counts.least_seconds(
        counts.paged_attn_flops(m, rows) / run.chips,
        counts.paged_attn_bytes(m, rows, page) / run.chips, run.peak)
    return 100.0 * least / t_kernel
