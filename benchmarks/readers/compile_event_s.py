"""Seconds of one named entry of the program's own compile telemetry
(``obs.xla.compile_events``), as the driver copied it. params: ``fn``."""


def read(run, params):
    events = [e for e in run.facts.get("compile_events", ())
              if e["fn"] == params["fn"]]
    return float(events[-1]["seconds"]) if events else None
