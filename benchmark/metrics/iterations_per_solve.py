"""iterations_per_solve: the solver's own iteration count
(``SolveResult.niterations``), mean over the window's solves."""


def read(run):
    its = [o.niterations for o in run.outcomes if o.ok]
    return sum(its) / len(its) if its else None
