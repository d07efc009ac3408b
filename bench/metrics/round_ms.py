"""Mean wall time of a federated round (collect, aggregate, build the
prompts), from the benchmark's span around the program's calls."""


def value(run, cell):
    r = [(e - s) * 1e3 for s, e, _ in run.rounds if run.t0 <= s < run.t1]
    return sum(r) / len(r) if r else None
