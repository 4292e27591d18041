"""call_host_ms_per_solve: host milliseconds of each solver call that
are not spent waiting for the device — the program's ``acg.call`` span
less its ``acg.call.device_wait`` — mean over the window's calls.  The
rest of the call is preparing and uploading the right-hand side,
dispatch, the scalar fetch, the copy of x to the host and assembling
the result (acg_tpu/obs/trace.py)."""

from benchmark.program_spans import outermost, window_spans


def read(run):
    spans = window_spans(run)
    calls = outermost(spans or [], "acg.call")
    if not calls:
        return None
    waits = [s for s in spans if s[0] == "acg.call.device_wait"]
    host = []
    for _, c0, c1, t in calls:
        wait = sum(e - s for _, s, e, wt in waits
                   if wt == t and c0 <= s and e <= c1)
        host.append(c1 - c0 - wait)
    return 1e3 * sum(host) / len(host)
