"""Share (%) of its roofline that the Genz-Malik rule reaches: the least
time the chip could take for the rule's work, the larger of operations over
peak FLOP/s and bytes over peak bytes/s, over the device time of the eval
programs (``jit_eval_step``) in the trace.

The work is that of the regions the window's solves had to evaluate (their
evaluation count over the rule's nodes), counted from shapes by
``harness.work``, never from XLA's cost analysis.  The peaks are the
published ones of the device (``harness.peaks``).  Which bound applies is
printed on standard error.
"""

import sys

from harness import peaks, work

EVAL_PROGRAM = "jit_eval_step"


def read(run):
    if run.trace is None:
        return None
    eval_s = run.trace.program_s(EVAL_PROGRAM)
    if eval_s <= 0 or not run.solves:
        return None
    d = run.d
    regions = sum(s.result.n_evals for s in run.solves) / work.gm_nodes(d)
    flops, nbytes = work.gm_work(
        d, regions, run.cell.reference.flops_per_point(d), run.itemsize
    )
    peak = peaks.lookup(run.device_kind)
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    print(f"solve.gm_roofline: {bound}-bound, flops={flops} bytes={nbytes} "
          f"eval_device_s={eval_s}", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / eval_s
