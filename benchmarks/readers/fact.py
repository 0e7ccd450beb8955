"""A number the driver put into ``facts`` from the program's own counters
(the step's metrics, averaged over the window). params: ``key``. A program
without the counter: nothing returned."""


def read(run, params):
    value = run.facts.get(params["key"])
    return None if value is None else float(value)
