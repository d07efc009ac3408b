"""Answer tokens the engine emitted in the window (every decoded token,
and the first token of every finished prompt), over the window's
length: all the work of the window, whether or not its query finished
before the window closed.  A dispatch cut by the window's edge counts in
the share of its span that lies inside."""


def value(run, cell):
    return run.steps.answer_tokens(run.t0, run.t1) / run.seconds
