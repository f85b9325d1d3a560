"""Target tokens of one step over the MEDIAN step wall time of the window,
over the chips. A step's wall runs from the start of its ``data`` part to
the end of its ``train.report``, so data waits and the report rendezvous of
the ordinary step count against it.

The median, not the window's mean: on a shared one-chip host a run now and
then loses seconds to a stall that no part of the program explains (2 of 70
runs lost 10 % and 41 % of a 20 s window in one call, PERF.md section 6),
which would put a set of six over the admission rule on its own. What the
median leaves out is reported beside it as ``stall_pct``."""
from benchmarks.harness.result import median


def read(run):
    facts = run["facts"]
    walls = [e[4] - e[0] for e in facts["edges"]]
    return facts["tokens_per_step"] / median(walls) / run["chips"]
