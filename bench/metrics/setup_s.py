"""Set-up time: from process start to the first timed query (weights,
corpus, provider indexes, warm-up, compile or cache load)."""


def value(run, cell):
    return run.setup_s
