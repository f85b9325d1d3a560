"""Host clock from the start of the benchmark's process to the first step
of the window: interpreter and imports, cluster start, the gang worker
reaching the chip, state, reference check, compile or cache load, warm-up."""


def read(run):
    return run["facts"]["marks"]["window_start"] - run["process_start"]
