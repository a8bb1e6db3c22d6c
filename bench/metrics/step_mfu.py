"""Model operations of the window's work over what the chips could do
in the traced window at their bf16 peak, in percent: every prompt
admitted in the window prefilled whole, and every kept decoded token
with attention over its context (``counts.py``)."""

import counts


def read(run):
    m = run.m
    if not run.trace.n_devices:
        return None
    flops = sum(counts.prefill_flops(m, p) for r in run.rounds
                for p in r.prefill_tokens)
    flops += counts.decode_flops(m, [x for r in run.rounds
                                     for x in r.kept_rows])
    cap = run.trace.window_s * run.chips * run.peak.flops
    return 100.0 * flops / cap if flops > 0 else None
