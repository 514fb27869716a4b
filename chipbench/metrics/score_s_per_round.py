"""Self time of the harness's 'score' spans per window round (host clock)."""


def read(run):
    if run.spans is None or not run.spans.calls.get("score"):
        return None
    return run.spans.self_s["score"] / run.rounds
