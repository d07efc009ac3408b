"""The held experts' share of their roofline: the least time the chip
could take for the grouped matmuls of the (token, held expert) pairs that
the run's dispatches routed (FLOPs at the bf16 peak or bytes at HBM
bandwidth, whichever is larger; the architecture module's
``expert_work``) over the device time of the operations that compute
them in the engine's two step programs, found by the names they compile
to (``expert_ops``: the operations that read the held experts' weights).
None where the configuration's architecture has no held experts, the
program records no routing, or the trace holds no such operation."""
import re

from bench.lib import work
from bench.lib.derive import STEP_PROGRAMS
from bench.lib.trace import KERNEL


def value(run, cell):
    arch, red = cell.arch, run.trace
    if red is None or not hasattr(arch, "expert_ops"):
        return None
    ops, prog = re.compile(arch.expert_ops(cell.model)), re.compile(STEP_PROGRAMS)
    sel = [i for i, n in enumerate(red.op_names)
           if KERNEL not in n and ops.search(n) and red.op_module[i] >= 0
           and prog.search(red.module_names[red.op_module[i]])]
    seconds = float((red.ops[sel, 1] - red.ops[sel, 0]).sum()) / 1e9 if sel else 0.0
    flops, nbytes = arch.expert_work(cell.model, run)
    if seconds <= 0 or flops <= 0:
        return None
    share, bound = work.roofline_share(flops, nbytes, seconds, run.extra["peak"])
    run.extra.setdefault("bounds", {})["experts"] = bound
    return 100.0 * share
