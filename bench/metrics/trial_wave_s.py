"""Mean timed calibration wave of the live trials in the window's retunes
(the runtimes in ``TickReport.history``)."""


def read(ctx, res):
    times = [rt for t in res["records"].get("retunes") or []
             for _, rt in t.history]
    if not times:
        return None
    return sum(times) / len(times)
