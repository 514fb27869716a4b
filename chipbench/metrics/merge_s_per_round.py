"""Self time of the harness's 'merge' spans per window round (host clock)."""


def read(run):
    if run.spans is None or not run.spans.calls.get("merge"):
        return None
    return run.spans.self_s["merge"] / run.rounds
