"""Host time of the detection sweep's device calls per compaction pass
started in the window, in ms: ``sweep.upload`` (padding and shipping a
class's matrix) plus ``sweep.batch`` (dispatch through the results'
copy to the host).  Beside ``sweep.device_ms`` it shows what dispatch
and copies add to the device's own time."""
from bench import program_spans as ps


def read(r):
    return ps.per_pass_ms(r, lambda log, p: log.outermost_ns(
        p, {"sweep.upload", "sweep.batch"}))
