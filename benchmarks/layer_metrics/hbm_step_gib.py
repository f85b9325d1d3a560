"""Layer: Device. What the compiled step reserves on one device, from
``compiled.memory_analysis()``: arguments + temporaries + outputs -
aliased. (``memory_stats()['peak_bytes_in_use']`` does not see a running
program's temporaries on this runtime.)"""


def read(run):
    return run["facts"]["memory"]["step"]["total_bytes"] / 2**30
