"""Device time of the jitted decode program per call, from the trace."""

PROGRAM = "jit_decode"


def read(ctx, res):
    tr = res.get("trace")
    calls = tr.program_calls(PROGRAM) if tr is not None else []
    if not calls:
        return None
    return sum(d for _, d in calls) / len(calls) * 1e-6
