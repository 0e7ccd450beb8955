"""Seconds of one run-level phase of the program (``obs.spans.phase``), read
after the run from the program's always-on store: the last phase of that name
in ``deeplearning_tpu.obs.spans.phases()``. params: ``name``. A program
without phases, or one that recorded none of that name: nothing returned."""


def read(run, params):
    try:
        from deeplearning_tpu.obs import spans
    except ImportError:
        return None
    phases = getattr(spans, "phases", None)
    if phases is None:
        return None
    seconds = [e["seconds"] for e in phases() if e.get("name") == params["name"]]
    return float(seconds[-1]) if seconds else None
