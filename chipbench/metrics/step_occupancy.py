"""Share of the step program's slot-rows that held a live request, over the
window: the decode tier's ``step_rows`` over ``steps`` times slots."""


def read(run):
    a, b = run.counters.get("start"), run.counters.get("end")
    if not a or not b or b["steps"] <= a["steps"]:
        return None
    return 100.0 * (b["step_rows"] - a["step_rows"]) / ((b["steps"] - a["steps"]) * run.slots)
