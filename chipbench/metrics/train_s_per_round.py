"""Self time of the harness's 'train' spans per window round (host clock)."""


def read(run):
    if run.spans is None or not run.spans.calls.get("train"):
        return None
    return run.spans.self_s["train"] / run.rounds
