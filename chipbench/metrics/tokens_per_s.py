"""Output tokens produced inside the window, per second of the window.

Every token the decode tier made between the window's two ends, whichever
request it belongs to: an admit makes a request's first token and a step one
token for each live row, so the count is the decode tier's ``admits`` plus
``step_rows`` read at the window's start and at its close."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    if not a or not b:
        return None
    made = (b["step_rows"] - a["step_rows"]) + (b["admits"] - a["admits"])
    return made / run.seconds
