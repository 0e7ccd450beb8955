"""1 - (union of the device's op intervals) / traced window, both from the
trace: the window runs from the first module event's start to the last one's
end, so busy time and window are read off one clock."""


def read(run, params):
    if run.trace is None:
        return None
    busy, window = run.trace.busy_seconds(), run.trace.window_seconds()
    return 100.0 * (1.0 - busy / window) if busy > 0 and window > 0 else None
