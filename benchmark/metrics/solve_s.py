"""solve_s: the window's seconds over the certified solves it holds.
Solves run back to back, each from the solver call (right-hand side
upload included) until x is on the host, so the window is all solves."""


def read(run):
    done = [o for o in run.outcomes if o.ok]
    if not done:
        return None
    return (run.window[1] - run.window[0]) / len(done)
