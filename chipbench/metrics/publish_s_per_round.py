"""Self time of the harness's 'publish' spans per window round (host clock)."""


def read(run):
    if run.spans is None or not run.spans.calls.get("publish"):
        return None
    return run.spans.self_s["publish"] / run.rounds
