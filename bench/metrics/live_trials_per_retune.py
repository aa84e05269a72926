"""Live trials the online tuner ran per retune in the window
(``TickReport.live_trials`` of the ticks that tuned live)."""


def read(ctx, res):
    retunes = res["records"].get("retunes") or []
    if not retunes:
        return None
    return sum(t.live_trials for t in retunes) / len(retunes)
