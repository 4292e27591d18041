"""serve_host_ms_per_batch: host milliseconds the serve path spends per
dispatched batch outside the device wait — the self time of the
program's ``acg.serve.*`` spans (stack, dispatch, demux, and each
response's classification and audit document) and ``acg.call.*`` spans
other than ``acg.call.device_wait``, over the window's
``acg.serve.batch`` spans (acg_tpu/obs/trace.py)."""

from benchmark.program_spans import self_seconds, window_spans


def read(run):
    spans = window_spans(run)
    if not spans:
        return None
    batches = sum(1 for s in spans if s[0] == "acg.serve.batch")
    if batches == 0:
        return None
    host = sum(x for s, x in self_seconds(spans)
               if s[0].startswith(("acg.serve.", "acg.call"))
               and s[0] != "acg.call.device_wait")
    return 1e3 * host / batches
